"""Profiler spans of the served path, request ids, and the fixpoint's
device scopes (DESIGN.md 10.7).

A CPU profiler trace of a small ``AsyncServer`` run must hold every span of
the served path, nested on the executor thread that ran the batch, with
the batch's request ids on the routing marker and the attempt; the engine's
``stage_seconds`` must be the sum of its ``engine.*`` spans; and the
compiled fixpoint must keep its program name and carry its named scopes.
"""
import asyncio
import collections
import glob
import re

import jax
import pytest
from jax.profiler import ProfileData

from repro.core import sparql
from repro.data import synth
from repro.db import GraphDB
from repro.engine.plan import CompiledPlan
from repro.engine.template import canonicalize
from repro.serve import AsyncServer

MEMBERS_OF = "{{ ?d subOrganizationOf {uni} . ?s memberOf ?d }}"
SPANS = (
    "serve.route", "serve.attempt", "serve.lock_wait", "engine.batch",
    "engine.plan", "engine.solve", "engine.prune", "plan.inputs",
    "plan.fixpoint", "plan.copy_back", "plan.memo", "plan.build",
)
Span = collections.namedtuple("Span", "name start end thread args")


def read_spans(directory) -> list:
    """The program's spans in the one trace under ``directory``."""
    (path,) = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.split(".")[0] in ("serve", "engine", "plan"):
                    out.append(Span(e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    (plane.name, i), dict(e.stats)))
    return out


def ids(value) -> list[int]:
    """Request ids as a span carries them (one id reads back as an int)."""
    return [int(x) for x in str(value).split()]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Six requests over two replicas (batches of at most four), every plan
    built inside the trace: (spans, results)."""
    db = GraphDB(synth.lubm_like(n_universities=2, seed=0))
    out = tmp_path_factory.mktemp("trace")

    async def main():
        async with AsyncServer(db, replicas=2, max_batch=4,
                               default_deadline_ms=600_000) as server:
            jax.profiler.start_trace(str(out))
            try:
                res = await asyncio.gather(*[
                    server.submit(MEMBERS_OF.format(uni=f"Univ{i % 2}"))
                    for i in range(6)])
            finally:
                jax.profiler.stop_trace()
        return res

    res = asyncio.run(main())
    assert all(r.ok for r in res)
    return read_spans(out), res


@pytest.mark.parametrize("name", SPANS)
def test_span_is_in_the_trace(traced, name):
    spans, _ = traced
    assert any(s.name == name for s in spans)


def test_engine_and_plan_spans_nest_in_an_attempt_on_one_thread(traced):
    spans, _ = traced
    attempts = [s for s in spans if s.name == "serve.attempt"]
    inner = [s for s in spans if s.name.startswith(("engine.", "plan."))]
    assert inner
    for s in inner:
        assert any(a.thread == s.thread and a.start <= s.start
                   and s.end <= a.end for a in attempts), s


def test_route_and_attempt_carry_the_batch_request_ids(traced):
    spans, res = traced
    attempts = [s for s in spans if s.name == "serve.attempt"]
    routes = [s for s in spans if s.name == "serve.route"]
    batches = [ids(a.args["rids"]) for a in attempts]
    assert sorted(i for b in batches for i in b) == sorted(
        r.request_id for r in res)
    assert sorted(map(tuple, batches)) == sorted(
        tuple(ids(r.args["rids"])) for r in routes)
    replica_of = {i: a.args["replica"] for a, b in zip(attempts, batches)
                  for i in b}
    assert all(replica_of[r.request_id] == r.replica for r in res)
    assert all(a.args["attempt"] == 1 for a in attempts)


def test_route_scores_name_every_candidate(traced):
    spans, _ = traced
    for r in (s for s in spans if s.name == "serve.route"):
        scores = dict(tok.split(":") for tok in r.args["scores"].split())
        assert sorted(scores) == ["r0", "r1"]
        assert r.args["replica"] in scores
        assert all(float(v) >= 1.0 for v in scores.values())
        assert r.end == r.start or r.end - r.start < 1e6  # a marker


@pytest.fixture(scope="module")
def engine_traced(tmp_path_factory):
    """Four requests through one engine on this thread alone (no other
    thread contends for the interpreter between a span and its clock):
    (spans, stage_seconds)."""
    eng = GraphDB(synth.lubm_like(n_universities=2, seed=0))._engine
    out = tmp_path_factory.mktemp("engine_trace")
    jax.profiler.start_trace(str(out))
    try:
        eng.execute_many([MEMBERS_OF.format(uni=f"Univ{i % 2}")
                          for i in range(4)])
    finally:
        jax.profiler.stop_trace()
    return read_spans(out), eng.stats().stage_seconds


@pytest.mark.parametrize("stage", ["plan", "solve", "prune"])
def test_stage_seconds_is_the_sum_of_engine_spans(engine_traced, stage):
    spans, stages = engine_traced
    mine = [s for s in spans if s.name == f"engine.{stage}"]
    traced_s = sum(s.end - s.start for s in mine) / 1e9
    # each span opens just before its interval is clocked, closes just after
    assert stages[stage] > 0 and mine
    assert -1e-6 * len(mine) <= traced_s - stages[stage] <= 2e-4 * len(mine)


def test_prune_span_counts_the_crossed_label_blocks(engine_traced):
    spans, _ = engine_traced
    g = synth.lubm_like(n_universities=2, seed=0)
    hist = g.label_histogram()
    blocks = (hist[g.label_id("subOrganizationOf")]
              + hist[g.label_id("memberOf")])
    assert 0 < blocks < g.n_edges
    prunes = [s for s in spans if s.name == "engine.prune"]
    assert prunes
    assert all(int(s.args["triples"]) == blocks for s in prunes)


def test_request_ids_are_unique_on_every_outcome():
    db = GraphDB(synth.lubm_like(n_universities=2, seed=0))

    async def main():
        async with AsyncServer(db, replicas=1, max_queue=2,
                               default_deadline_ms=600_000) as server:
            futs = [
                server.submit("{ ?x"),  # rejected at parse
                server.submit(MEMBERS_OF.format(uni="Univ1"), deadline_ms=0),
            ]
            futs += [server.submit(MEMBERS_OF.format(uni="Univ0"))
                     for _ in range(4)]  # two admitted, two over the queue
            return await asyncio.gather(*futs)

    res = asyncio.run(main())
    assert {r.outcome for r in res} == {"ok", "overloaded", "error", "deadline"}
    rids = [r.request_id for r in res]
    assert all(isinstance(i, int) for i in rids)
    assert len(set(rids)) == len(rids)


@pytest.fixture(scope="module")
def fixpoint_hlo():
    """The compiled fixpoint of a sparse plan: every op's ``op_name`` is its
    full scope path, as a device trace's ``tf_op`` shows it."""
    g = synth.lubm_like(n_universities=1, seed=0)
    inst = canonicalize(sparql.parse(MEMBERS_OF.format(uni="Univ0")))
    plan = CompiledPlan(inst.template, g, engine="sparse")
    return plan.fixpoint.lower(
        *plan.fixpoint_inputs([inst.constants])).compile().as_text()


def test_fixpoint_program_keeps_its_name(fixpoint_hlo):
    assert re.search(r"^HloModule jit__run\b", fixpoint_hlo, re.M)


@pytest.mark.parametrize("scope", ["fixpoint", "edge_bits", "segor"])
def test_fixpoint_ops_carry_their_scopes(fixpoint_hlo, scope):
    paths = re.findall(r'op_name="(jit\(_run\)/fixpoint/[^"]*)"', fixpoint_hlo)
    assert any(scope in path.split("/") for path in paths)
