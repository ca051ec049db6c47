#!/usr/bin/env python3
"""Drive the query path once on a TPU and check every answer on the host.

    python3 chip_smoke.py               # one chip: edge tier, then dense tier
    python3 chip_smoke.py --four-chips  # partitioned engine on four chips

One chip, in one process:

* **edge tier** — a LUBM-shaped corpus of 10,000 universities (~3.76M
  triples, ~1.65M nodes) is streamed to an N-Triples file, ingested and
  served through ``AsyncServer`` (2 replicas, fixed batch of 4) on the
  engine ``engine="auto"`` picks.  A delete and a re-insert, each followed
  by ``fence()``, make the plans resume warm on the chip;
* **dense tier** — 250 universities (~41k nodes, inside the dense budget)
  on the forced ``packed_fused`` and ``packed`` engines.

``--four-chips`` runs only the partitioned engine over a four-device node
mesh on the full corpus, with the same requests on one chip as comparison,
and checks that its operands and chi span all four devices.

Every request must come back ``ok`` with the survivor triples that
``dualsim.solve_worklist`` (the sequential numpy reference) gives on the
host for the same constants and the same snapshot.  The script exits
nonzero, and prints no result line, on any failure or where JAX finds no
TPU.  Its last line is ``{"ok": true, "device": {...}}``; times printed
before it are the named chip's wall clock, compilation included where
marked cold.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import tempfile
import time

import numpy as np

EDGE_UNIVERSITIES = 10_000
DENSE_UNIVERSITIES = 250
N_REQUESTS = 16
N_CONSTANTS = 4
BATCH = 4
REPLICAS = 2
QUERY = "{{ ?d subOrganizationOf {uni} . ?s memberOf ?d }}"
# edge-list engines: the tier that serves past the dense [n, n] budget
EDGE_TIER = ("sparse", "jacobi_packed", "partitioned")
# a cold wave compiles every plan; nothing here may shed or time out
DEADLINE_MS = 1_200_000.0


_T0 = time.perf_counter()


def log(msg: str) -> None:
    """Progress line, so a stalled phase shows where it stopped."""
    print(f"[{time.perf_counter() - _T0:8.1f} s] {msg}", flush=True)


class SmokeFailure(AssertionError):
    """A phase produced a wrong or missing answer."""


def expect(cond: bool, msg: str) -> None:
    """Fail the run (independent of ``python -O``) unless ``cond`` holds."""
    if not cond:
        raise SmokeFailure(msg)


def require_tpu():
    """The first TPU device; exits nonzero where JAX finds none."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return dev


# --------------------------------------------------------------------- #
# data and the host reference
# --------------------------------------------------------------------- #
def lubm_graph(n_universities: int, seed: int = 0):
    """Stream a LUBM-shaped corpus through N-Triples; returns (graph, n)."""
    from repro.data import rdf, synth

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "lubm.nt")
        n = rdf.dump_stream(
            synth.lubm_stream(n_universities=n_universities, seed=seed), path
        )
        graph = rdf.load_stream(path)
    return graph, n


def make_requests(n_universities: int, seed: int = 0) -> list[str]:
    """``N_REQUESTS`` template instances over ``N_CONSTANTS`` universities."""
    rng = np.random.default_rng(seed)
    k = min(N_CONSTANTS, n_universities)
    unis = rng.choice(n_universities, size=k, replace=False)
    return [QUERY.format(uni=f"Univ{unis[i % k]}") for i in range(N_REQUESTS)]


def survivor_rows(graph, mask: np.ndarray) -> np.ndarray:
    """Surviving triples as sorted id rows (stable across re-inserts)."""
    return np.unique(graph.triples[mask], axis=0)


class Reference:
    """``solve_worklist`` survivors per (snapshot, query), computed once."""

    def __init__(self):
        self._memo: dict = {}

    def rows(self, graph, query: str) -> np.ndarray:
        from repro.core import dualsim, pruning, soi, sparql

        key = (id(graph), query)
        if key not in self._memo:
            s = soi.build_soi(sparql.parse(query))
            chi, _ = dualsim.solve_worklist(soi.compile_soi(s, graph), graph)
            mask, _ = pruning.prune_triples(s, chi, graph)
            # the graph rides along so its id() cannot be reused
            self._memo[key] = (graph, survivor_rows(graph, mask))
        return self._memo[key][1]


def check_wave(results, queries, ref: Reference, label: str) -> None:
    """Every outcome ``ok`` and equal to the reference on its snapshot."""
    for i, (q, r) in enumerate(zip(queries, results)):
        expect(r.ok, f"{label}: request {i} ended {r.outcome}: {r.detail}")
        rs = r.result
        got = survivor_rows(rs.snapshot, rs.survivor_mask)
        want = ref.rows(rs.snapshot, q)
        expect(
            np.array_equal(got, want),
            f"{label}: request {i} ({q}) kept {len(got)} triples, "
            f"the reference {len(want)}",
        )


def churn_triple(graph, query: str) -> tuple[str, str, str]:
    """A ``memberOf`` triple the query's answer depends on."""
    uni = query.split("subOrganizationOf")[1].split()[0]
    names, idx = graph.node_names, graph.node_index()
    lab = graph.label_index()
    dept = graph.triples[
        (graph.triples[:, 1] == lab["subOrganizationOf"])
        & (graph.triples[:, 2] == idx[uni])
    ][0, 0]
    row = graph.triples[
        (graph.triples[:, 1] == lab["memberOf"]) & (graph.triples[:, 2] == dept)
    ][0]
    return names[row[0]], "memberOf", names[row[2]]


# --------------------------------------------------------------------- #
# serving
# --------------------------------------------------------------------- #
class CompileCounter:
    """Counts XLA compilations (persistent-cache loads included)."""

    def __init__(self):
        import jax.monitoring

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


async def _wave(server, queries, label: str):
    log(f"{label} wave: {len(queries)} requests")
    t0 = time.perf_counter()
    results = await asyncio.gather(
        *[server.submit(q, tenant=f"t{i % 2}") for i, q in enumerate(queries)]
    )
    return results, time.perf_counter() - t0


def serve(db, queries, ref: Reference, *, mutate: bool = False) -> dict:
    """Cold wave, warm wave and (``mutate``) two warm resumes; all checked.

    Returns wall seconds per wave, the warm wave's median per-request
    share of its microbatch's wall time, the warm wave's survivor rows, the
    served plan and the router's aggregate counters.
    """
    from repro.serve import AsyncServer

    async def run() -> dict:
        out: dict = {}
        async with AsyncServer(
            db, replicas=REPLICAS, max_batch=BATCH,
            default_deadline_ms=DEADLINE_MS, watchdog_budget_ms=DEADLINE_MS,
        ) as server:
            res, out["cold_s"] = await _wave(server, queries, "cold")
            check_wave(res, queries, ref, "cold")
            res, out["warm_s"] = await _wave(server, queries, "warm")
            check_wave(res, queries, ref, "warm")
            out["warm_request_ms"] = float(
                np.median([r.result.timings["total"] for r in res]) * 1e3
            )
            out["engine"] = res[0].result.engine
            out["rows"] = [
                survivor_rows(r.result.snapshot, r.result.survivor_mask)
                for r in res
            ]
            if mutate:
                triple = churn_triple(db.graph, queries[0])
                expect(db.delete([triple]) == 1, f"delete {triple} missed")
                await server.fence()
                res, out["resume_delete_s"] = await _wave(
                    server, queries, "after delete")
                check_wave(res, queries, ref, "after delete")
                expect(db.insert([triple]) == 1, f"insert {triple} missed")
                await server.fence()
                res, out["resume_insert_s"] = await _wave(
                    server, queries, "after insert")
                check_wave(res, queries, ref, "after insert")
            out["agg"] = server.router.aggregate()
            out["plan"], out["consts"] = _plan(server, queries[0])
            spec = server.router.replicas[0].engine.spec
            out["priced_by"] = (
                "hand-tuned cost model" if spec is None
                else f"MachineSpec {spec.fingerprint}"
            )
        if mutate:
            expect(out["agg"]["warm_resume_solves"] >= 1,
                   f"no warm resume ran: {out['agg']}")
        return out

    return asyncio.run(run())


def _plan(server, query: str):
    """The cached plan the first replica serves ``query`` with, and the
    query's constants."""
    eng = server.router.replicas[0].engine
    _, inst = eng.prepare(query)
    plan, hit = eng.plan_for(inst.template, BATCH)
    expect(hit, "the served plan is no longer cached")
    return plan, inst.constants


def fixpoint_text(rep: dict) -> str:
    """StableHLO of a served plan's whole jitted fixpoint (nothing runs)."""
    plan = rep["plan"]
    inputs = plan.fixpoint_inputs([rep["consts"]] * plan.batch)
    return plan.fixpoint.lower(*inputs).as_text()


# --------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------- #
def edge_phase(n_universities: int, seed: int = 0) -> dict:
    """Full-size corpus on ``engine="auto"``: cold, warm and warm resumes."""
    from repro.db import GraphDB

    t0 = time.perf_counter()
    graph, n_triples = lubm_graph(n_universities, seed)
    ingest_s = time.perf_counter() - t0
    log(f"ingested {n_triples} triples")
    queries = make_requests(n_universities, seed)
    db = GraphDB(graph, engine="auto", buckets=(BATCH,))
    out = serve(db, queries, Reference(), mutate=True)
    plan = out["plan"]
    expect(plan.engine in EDGE_TIER,
           f"auto picked {plan.engine}, not an edge-list engine")
    out.update(
        n_triples=n_triples, n_nodes=graph.n_nodes, ingest_s=ingest_s,
        auto=plan.cost.engine, queries=queries,
    )
    return out


def dense_phase(
    n_universities: int, engines=("packed_fused", "packed"), seed: int = 0
) -> dict:
    """Dense-tier corpus on each forced engine; returns a report per engine."""
    from repro.db import GraphDB

    graph, n_triples = lubm_graph(n_universities, seed)
    queries = make_requests(n_universities, seed)
    ref = Reference()
    out = {"n_triples": n_triples, "n_nodes": graph.n_nodes, "queries": queries}
    for engine in engines:
        db = GraphDB(graph, engine=engine, buckets=(BATCH,))
        rep = serve(db, queries, ref)
        expect(rep["plan"].engine == engine,
               f"forced {engine}, served {rep['plan'].engine}")
        out[engine] = rep
    return out


def partitioned_phase(n_universities: int, n_devices: int, seed: int = 0) -> dict:
    """Partitioned engine over an ``n_devices`` node mesh vs one device."""
    import jax

    from repro.db import GraphDB
    from repro.distributed import ctx as dctx

    graph, n_triples = lubm_graph(n_universities, seed)
    log(f"ingested {n_triples} triples")
    queries = make_requests(n_universities, seed)
    ref = Reference()
    mesh = dctx.node_mesh(n_devices)
    meshed = serve(GraphDB(graph, engine="partitioned", mesh=mesh,
                           buckets=(BATCH,)), queries, ref)
    single = serve(GraphDB(graph, engine="auto", buckets=(BATCH,)), queries, ref)
    expect(
        all(np.array_equal(a, b) for a, b in zip(meshed["rows"], single["rows"])),
        "partitioned survivors differ from one device's",
    )
    plan = meshed["plan"]
    expect(plan.engine == "partitioned", f"served {plan.engine}")
    devices = set(mesh.devices.flat)
    spans = {
        "edge blocks": [a.sharding.device_set for a in plan.operands.edge_src_b],
        "chi init": [plan.operands.init.sharding.device_set],
    }
    chi, _ = plan.fixpoint(
        *plan.fixpoint_inputs([meshed["consts"]] * plan.batch)
    )
    chi = jax.block_until_ready(chi)
    spans["chi"] = [chi.sharding.device_set]
    for what, sets in spans.items():
        for s in sets:
            expect(s == devices,
                   f"{what} span {len(s)} of the {len(devices)} mesh devices")
    return {"n_triples": n_triples, "n_nodes": graph.n_nodes,
            "meshed": meshed, "single": single}


# --------------------------------------------------------------------- #
def _report(label: str, rep: dict, kind: str) -> None:
    agg = rep["agg"]
    line = (
        f"{label}: engine={rep['engine']}, {N_REQUESTS} requests ok and equal "
        f"to solve_worklist per wave; cold wave {rep['cold_s']:.3f} s "
        f"({kind} time, compilation included), warm wave {rep['warm_s']:.3f} s, "
        f"warm per-request {rep['warm_request_ms']:.3f} ms ({kind} time)"
    )
    if "resume_delete_s" in rep:
        line += (
            f"; warm resume after delete {rep['resume_delete_s']:.3f} s, "
            f"after insert {rep['resume_insert_s']:.3f} s ({kind} time), "
            f"{agg['warm_resume_solves']} warm-started solves"
        )
    print(line + f"; plans built {agg['plan_builds']}", flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the partitioned engine on four chips")
    args = ap.parse_args(argv)

    dev = require_tpu()
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    compiles = CompileCounter()
    kind = dev.device_kind
    print(f"device: {dev.platform} {kind} x{len(jax.devices())}; "
          f"compile cache {cache_dir}", flush=True)

    if args.four_chips:
        expect(len(jax.devices()) >= 4, f"need 4 chips, have {len(jax.devices())}")
        rep = partitioned_phase(EDGE_UNIVERSITIES, 4)
        print(f"corpus: {rep['n_triples']} triples, {rep['n_nodes']} nodes",
              flush=True)
        _report("partitioned on 4 chips", rep["meshed"], kind)
        _report("comparison on 1 chip", rep["single"], kind)
        print("partitioned survivors identical to one chip; edge blocks, chi "
              "init and chi span all 4 devices", flush=True)
    else:
        rep = edge_phase(EDGE_UNIVERSITIES)
        print(f"edge tier corpus: {rep['n_triples']} triples, {rep['n_nodes']} "
              f"nodes, ingested in {rep['ingest_s']:.3f} s (host); auto picked "
              f"{rep['auto']}, priced by the {rep['priced_by']}", flush=True)
        expect("tpu_custom_call" in fixpoint_text(rep),
               "the edge-tier fixpoint holds no Pallas kernel")
        _report("edge tier", rep, kind)
        dense = dense_phase(DENSE_UNIVERSITIES)
        print(f"dense tier corpus: {dense['n_triples']} triples, "
              f"{dense['n_nodes']} nodes", flush=True)
        for engine in ("packed_fused", "packed"):
            expect("tpu_custom_call" in fixpoint_text(dense[engine]),
                   f"the {engine} fixpoint holds no Pallas kernel")
            _report(f"dense tier {engine}", dense[engine], kind)
    print(f"compilations: {compiles.n} ({compiles.seconds:.3f} s)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
