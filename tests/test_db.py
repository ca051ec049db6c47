"""`repro.db` public API: GraphDB mutation + versioned plan invalidation,
Session admission/microbatching, the fluent builder round-trip contract,
lazy ResultSet materialization, and UNION coverage through the full
serving path (ISSUE 2 acceptance criteria).
"""
import math

import numpy as np
import pytest

from repro.core import dualsim, pruning, soi, sparql
from repro.data import synth
from repro.db import GraphDB, Q
from repro.engine import canonicalize

from tests._hyp import given, settings, st

MEMBERS_OF = "{{ ?d subOrganizationOf {uni} . ?s memberOf ?d }}"


@pytest.fixture()
def db():
    return GraphDB(synth.lubm_like(n_universities=2, seed=0))


def _direct_mask(q, g, engine="dense"):
    mask = np.zeros(g.n_edges, dtype=bool)
    for part in sparql.union_split(q):
        s = soi.build_soi(part)
        c = soi.compile_soi(s, g)
        chi, _ = dualsim.solve_compiled(c, g, engine=engine)
        m, _ = pruning.prune_triples(s, chi, g)
        mask |= m
    return mask


# --------------------------------------------------------------------- #
# GraphDB: mutation semantics
# --------------------------------------------------------------------- #
def test_insert_delete_set_semantics(db):
    v0 = db.version
    n0 = db.n_triples
    snap0 = db.snapshot()
    assert db.insert([("DeptX", "subOrganizationOf", "Univ0")]) == 1
    assert db.version == v0 + 1 and db.n_triples == n0 + 1
    assert ("DeptX", "subOrganizationOf", "Univ0") in db
    # duplicate insert: set semantics, no mutation, no version bump
    assert db.insert([("DeptX", "subOrganizationOf", "Univ0")]) == 0
    assert db.version == v0 + 1
    # delete of an unknown triple: no-op
    assert db.delete([("NoSuch", "p", "AlsoNoSuch")]) == 0
    assert db.version == v0 + 1
    assert db.delete([("DeptX", "subOrganizationOf", "Univ0")]) == 1
    assert db.version == v0 + 2 and db.n_triples == n0
    assert ("DeptX", "subOrganizationOf", "Univ0") not in db
    # snapshot semantics: the pre-mutation graph never changed
    assert snap0.n_edges == n0
    assert snap0 is not db.snapshot()


def test_insert_extends_dictionary(db):
    n_nodes = db.n_nodes
    db.insert([("BrandNewNode", "brandNewLabel", "Univ0")])
    assert db.n_nodes == n_nodes + 1
    assert "brandNewLabel" in db.label_index
    # node ids are stable: old names keep their ids in the new snapshot
    assert db.node_index["Univ0"] == db.snapshot().node_names.index("Univ0")


def test_each_snapshot_prunes_through_its_own_label_index(db):
    q = MEMBERS_OF.format(uni="Univ0")
    new = ("Prof0_0_0", "memberOf", "Dept0_0")
    g0 = db.snapshot()
    assert new not in set(db.query(q))
    blocks0 = g0.label_blocks()
    db.query(q)
    assert g0.label_blocks() is blocks0  # built once per graph
    members0 = int(np.diff(blocks0.starts)[g0.label_id("memberOf")])

    assert db.insert([new]) == 1
    g1 = db.snapshot()
    assert g1._label_blocks is None  # a new snapshot starts without one
    rs = db.query(q)
    assert new in set(rs)
    assert np.array_equal(rs.survivor_mask, _direct_mask(sparql.parse(q), g1))
    blocks1 = g1.label_blocks()
    assert blocks1 is not blocks0
    assert int(np.diff(blocks1.starts)[g1.label_id("memberOf")]) == members0 + 1

    assert db.delete([new]) == 1
    g2 = db.snapshot()
    rs = db.query(q)
    assert new not in set(rs)
    assert np.array_equal(rs.survivor_mask, _direct_mask(sparql.parse(q), g2))
    assert g2.label_blocks() is not blocks1
    # the old snapshots keep their own indexes, untouched
    assert g0.label_blocks() is blocks0 and g1.label_blocks() is blocks1
    assert int(np.diff(blocks0.starts)[g0.label_id("memberOf")]) == members0


# --------------------------------------------------------------------- #
# versioned plan invalidation (tentpole acceptance criterion)
# --------------------------------------------------------------------- #
def test_mutation_invalidates_plans_precisely(db):
    qa = MEMBERS_OF.format(uni="Univ0")
    qb = "{ ?p publicationAuthor ?s }"

    r0 = db.query(qa)
    assert not r0.cache_hit
    assert db.query(qa).cache_hit  # warm plan
    db.query(qb)  # a second, unrelated template in the cache
    m0 = db.metrics()
    assert m0.cache.size == 2 and m0.cache.invalidations == 0

    # mutation 1: stale plans are NOT flushed (history <= 1 version) but
    # the same template rebuilds lazily against the new fingerprint
    assert db.insert([("DeptNew", "subOrganizationOf", "Univ0"),
                      ("StudentNew", "memberOf", "DeptNew")]) == 2
    r1 = db.query(qa)
    assert not r1.cache_hit  # stale plan is not reused...
    assert ("StudentNew", "memberOf", "DeptNew") in list(r1.survivor_triples())
    assert np.array_equal(r1.survivor_mask,
                          _direct_mask(sparql.parse(qa), db.graph))
    m1 = db.metrics()
    assert m1.invalidation_events == 1
    assert m1.cache.invalidations == 0  # v0 plans kept: no full-cache flush
    assert m1.cache.size == 3  # qa@v0, qb@v0, qa@v1
    assert m1.cache.evictions == m0.cache.evictions  # invalidation != LRU

    # mutation 2: v0 now falls out of the <=1-version history; exactly the
    # two v0 plans (qa@v0, qb@v0) are dropped — qa@v1 survives as history
    db.insert([("StudentNew2", "memberOf", "DeptNew")])
    r2 = db.query(qa)
    assert not r2.cache_hit
    assert ("StudentNew2", "memberOf", "DeptNew") in list(r2.survivor_triples())
    m2 = db.metrics()
    assert m2.invalidation_events == 2
    assert m2.cache.invalidations == 2  # exactly qa@v0 and qb@v0
    assert m2.cache.size == 2  # qa@v1 (history) + qa@v2

    # delete direction: survivors shrink back
    db.delete([("StudentNew", "memberOf", "DeptNew"),
               ("StudentNew2", "memberOf", "DeptNew")])
    r3 = db.query(qa)
    trips = list(r3.survivor_triples())
    assert ("StudentNew", "memberOf", "DeptNew") not in trips
    assert ("StudentNew2", "memberOf", "DeptNew") not in trips
    assert np.array_equal(r3.survivor_mask,
                          _direct_mask(sparql.parse(qa), db.graph))


def test_fingerprint_includes_name_dictionaries():
    # identical int arrays under different dictionary encodings are
    # DIFFERENT databases: constants resolve to different ids, so their
    # plans must never collide in the cache
    from repro.core.graph import Graph
    from repro.engine.engine import graph_fingerprint

    tr = np.asarray([[0, 0, 1]], np.int32)
    g1 = Graph(2, 1, tr, node_names=["a", "b"], label_names=["p"])
    g2 = Graph(2, 1, tr, node_names=["b", "a"], label_names=["p"])
    g3 = Graph(2, 1, tr, node_names=["a", "b"], label_names=["q"])
    g4 = Graph(2, 1, tr.copy(), node_names=["a", "b"], label_names=["p"])
    assert graph_fingerprint(g1) != graph_fingerprint(g2)
    assert graph_fingerprint(g1) != graph_fingerprint(g3)
    assert graph_fingerprint(g1) == graph_fingerprint(g4)
    # the node/label list boundary must be unambiguous too
    g5 = Graph(2, 1, tr, node_names=["a", "bc"], label_names=["d"])
    g6 = Graph(2, 1, tr, node_names=["a", "b"], label_names=["cd"])
    assert graph_fingerprint(g5) != graph_fingerprint(g6)


def test_execute_prepared_pins_one_snapshot():
    # regression: UNION requests used to re-run refresh() mid-batch, so one
    # execute_many call could mix two graph versions when the source mutated
    # between the microbatched solves and the multipart tail.  Drive a
    # direct (unlocked) Engine and mutate after the first microbatch.
    from repro.engine import Engine

    gdb = GraphDB(synth.lubm_like(n_universities=2, seed=0))
    eng = Engine(gdb)
    simple_q = MEMBERS_OF.format(uni="Univ0")
    union_q = ("{ ?d subOrganizationOf Univ0 } UNION "
               "{ ?d subOrganizationOf Univ1 }")
    prepared = [eng.prepare(q) for q in (simple_q, union_q)]
    snap = gdb.graph
    expected = [_direct_mask(sparql.parse(q), snap)
                for q in (simple_q, union_q)]

    orig, fired = eng._solve_microbatch, []

    def hooked(requests, bucket=None):
        out = orig(requests, bucket=bucket)
        if not fired:  # mutate the source mid-batch, exactly once
            fired.append(True)
            gdb.insert([("DeptMid", "subOrganizationOf", "Univ0"),
                        ("SMid", "memberOf", "DeptMid")])
        return out

    eng._solve_microbatch = hooked
    res = eng.execute_prepared(prepared)
    for r, exp in zip(res, expected):
        # every result reflects the snapshot pinned at call entry — none
        # sees the mid-batch mutation (old behavior: the UNION tail
        # refreshed and answered over snap.n_edges + 2 triples)
        assert r.survivors.shape[0] == snap.n_edges
        assert np.array_equal(r.survivors, exp)
    # the next call adopts the mutation as usual
    r2 = eng.execute(simple_q)
    assert r2.survivors.shape[0] == snap.n_edges + 2


def test_results_pin_their_snapshot(db):
    qa = MEMBERS_OF.format(uni="Univ0")
    r0 = db.query(qa)
    before = list(r0.survivor_triples())
    db.insert([("DeptY", "subOrganizationOf", "Univ0"),
               ("SY", "memberOf", "DeptY")])
    # the old result still reads through its own snapshot, unchanged
    assert list(r0.survivor_triples()) == before
    assert r0.stats.n_triples == r0.snapshot.n_edges
    assert db.query(qa).stats.n_triples == r0.stats.n_triples + 2


# --------------------------------------------------------------------- #
# Session: admission policy + microbatching acceptance criterion
# --------------------------------------------------------------------- #
def _submit_all(db, reqs, **kw):
    with db.session(**kw) as s:
        futures = [s.submit(q) for q in reqs]
        results = [f.result() for f in futures]
    return s, results


def test_session_microbatching_warm_zero_recompiles(db):
    # 9 requests but only 2 distinct constant tuples: dedup happens BEFORE
    # chunking, so the whole stream is ONE fixpoint solve (duplicates ride
    # an existing instance slot and never consume bucket capacity)
    n, cap = 9, 4
    reqs = [MEMBERS_OF.format(uni=f"Univ{i % 2}") for i in range(n)]
    # warm pass builds every (template, bucket) plan the stream needs
    _submit_all(db, reqs, max_delay_ms=1e6, max_pending=cap)
    inst = canonicalize(sparql.parse(reqs[0]))
    plan2, _ = db._engine.plan_for(inst, bucket=2)
    m0 = db.metrics()
    traces0 = plan2.metrics.traces

    s, results = _submit_all(db, reqs, max_delay_ms=1e6, max_pending=cap)
    m1 = db.metrics()
    # 2 unique tuples < cap: no cap-triggered flush, one solve at close
    assert m1.microbatches - m0.microbatches == 1
    assert s.flushes == 1
    # zero recompiles and zero retraces on the warm template
    assert m1.cache.misses == m0.cache.misses
    assert plan2.metrics.traces == traces0
    assert all(r.cache_hit for r in results)
    # and every rider matches its one-shot result
    direct = _direct_mask(sparql.parse(reqs[0]), db.graph)
    assert np.array_equal(results[0].survivor_mask, direct)


def test_session_cap_counts_unique_constants(db):
    # distinct constants DO hit the cap: 4 unique tuples at cap 4 flush
    # eagerly, ceil-batching the stream
    n, cap = 9, 4
    reqs = [MEMBERS_OF.format(uni=f"Univ{i}") for i in range(n)]
    _submit_all(db, reqs, max_delay_ms=1e6, max_pending=cap)  # warm pass
    m0 = db.metrics()
    s, results = _submit_all(db, reqs, max_delay_ms=1e6, max_pending=cap)
    m1 = db.metrics()
    assert s.flushes == math.ceil(n / cap) == 3
    assert m1.microbatches - m0.microbatches == 3
    assert all(r.cache_hit for r in results)


def test_session_deadline_admission(db):
    q = MEMBERS_OF.format(uni="Univ0")
    with db.session(max_delay_ms=0.0) as s:
        fut = s.submit(q)
        # zero deadline: the submit itself flushed
        assert fut.done() and s.pending == 0
    with db.session(max_delay_ms=1e6) as s:
        fut = s.submit(q)
        assert not fut.done() and s.pending == 1
        rs = fut.result()  # forces the flush
        assert fut.done() and s.pending == 0
        assert len(rs) > 0


def test_session_close_and_reject(db):
    q = MEMBERS_OF.format(uni="Univ0")
    with db.session(max_delay_ms=1e6) as s:
        fut = s.submit(q)
    assert fut.done()  # context exit flushed
    with pytest.raises(RuntimeError, match="closed"):
        s.submit(q)


def test_session_syntax_error_at_submit(db):
    with db.session() as s:
        with pytest.raises(SyntaxError, match="empty group"):
            s.submit("{}")
        assert s.pending == 0


# --------------------------------------------------------------------- #
# fluent builder: grammar + round-trip acceptance criterion
# --------------------------------------------------------------------- #
def test_builder_composes_the_full_algebra():
    q = (
        Q.triple("?d", "memberOf", "?u")
        .triple("?s", "advisor", "?d")
        .and_(Q.triple("?u", "subOrganizationOf", "Univ0"))
        .optional("{ ?s publicationAuthor ?p }")
        .union(("?s", "headOf", "?d"))
    )
    ast = q.build()
    assert isinstance(ast, sparql.Union_)
    assert isinstance(ast.left, sparql.Optional_)
    assert isinstance(ast.left.left, sparql.And)
    assert sparql.parse(q.sparql()) == ast


def test_builder_roundtrips_through_parse():
    cases = [
        Q.triple("?a", "p0", "?b"),
        Q.triple("?a", "p0", "?b").triple("?b", "p1", "C0"),
        Q.triple("?a", "p0", "?b").and_(Q.triple("?b", "p1", "?c")),
        Q.triple("?a", "p0", "?b").optional(Q.triple("?c", "p2", "?a")),
        Q.triple("?a", "p0", "?b").union(Q.triple("?a", "p1", "?b")),
        Q.triple("?s", "p0", "?d").optional(
            Q.triple("?d", "p1", "C0").union(Q.triple("?d", "p1", "C1"))
        ),
    ]
    for q in cases:
        assert sparql.parse(q.sparql()) == q.build(), q.sparql()


def test_builder_immutability_and_validation():
    base = Q.triple("?a", "p0", "?b")
    extended = base.triple("?b", "p1", "?c")
    assert base != extended and len(base.build().triples) == 1
    with pytest.raises(ValueError, match="empty builder"):
        Q().build()
    with pytest.raises(ValueError, match="invalid constant"):
        Q.triple("?a", "p0", "bad name with spaces")
    with pytest.raises(ValueError, match="invalid variable"):
        Q.triple("?9starts-with-digit", "p0", "?b")
    with pytest.raises(TypeError, match="composite"):
        Q.triple("?a", "p0", "?b").and_(Q.triple("?c", "p1", "?d")).triple(
            "?x", "p2", "?y"
        )
    with pytest.raises(TypeError, match="operand"):
        Q.triple("?a", "p0", "?b").and_(42)


def test_builder_queries_execute(db):
    q = (
        Q.triple("?d", "subOrganizationOf", "Univ0")
        .triple("?s", "memberOf", "?d")
    )
    rs = db.query(q)
    assert np.array_equal(rs.survivor_mask,
                          _direct_mask(q.build(), db.graph))


_BUILDER_TERMS = st.sampled_from(["?a", "?b", "?c", "C0", "C1"])
_BUILDER_TRIPLES = st.tuples(
    _BUILDER_TERMS, st.sampled_from(["p0", "p1", "p2"]), _BUILDER_TERMS
)
_BUILDER_BGPS = st.lists(_BUILDER_TRIPLES, min_size=1, max_size=3).map(
    lambda ts: sparql.bgp_of_triples(*ts)
)
_BUILDER_QUERIES = st.recursive(
    _BUILDER_BGPS,
    lambda children: st.builds(sparql.And, children, children)
    | st.builds(sparql.Optional_, children, children)
    | st.builds(sparql.Union_, children, children),
    max_leaves=5,
)


@settings(max_examples=60, deadline=None)
@given(_BUILDER_QUERIES)
def test_format_parse_roundtrip_property(q):
    """builder/format -> parse is the identity on random BGP/AND/OPTIONAL/
    UNION compositions (ISSUE 2 acceptance)."""
    assert sparql.parse(sparql.format_query(q)) == q


# --------------------------------------------------------------------- #
# ResultSet: lazy names, pagination, honest timings
# --------------------------------------------------------------------- #
def test_resultset_lazy_bindings_and_pagination(db):
    rs = db.query(MEMBERS_OF.format(uni="Univ0"))
    g = db.graph
    assert rs.variables == ("d", "s")
    # names match the mask through the snapshot's dictionary
    d_names = rs.bindings("d")
    assert d_names == [g.node_names[i]
                       for i in np.flatnonzero(rs.binding_mask("d"))]
    assert rs.binding_count("d") == len(d_names)
    assert rs.bindings("d") is rs.bindings("d")  # cached, built once
    # survivor iteration == mask rows, in database order
    all_triples = list(rs)
    assert len(all_triples) == len(rs) == rs.stats.n_after
    ids = np.flatnonzero(rs.survivor_mask)
    s0, p0, o0 = g.triples[ids[0]]
    assert all_triples[0] == (g.node_names[s0], g.label_names[p0],
                              g.node_names[o0])
    # pagination tiles the full set
    paged = []
    for off in range(0, len(rs), 7):
        page = rs.page(off, 7)
        assert len(page) <= 7
        paged += page
    assert paged == all_triples
    assert rs.page(len(rs), 7) == []


def test_per_request_timing_split(db):
    reqs = [MEMBERS_OF.format(uni=f"Univ{i % 2}") for i in range(4)]
    results = db.execute_many(reqs)
    # all four rode one microbatch: batch_total is a batch property...
    batch_totals = {r.timings["batch_total"] for r in results}
    assert len(batch_totals) == 1
    bt = batch_totals.pop()
    # ...and "total" is the fair per-request share of it
    for r in results:
        assert r.timings["total"] == pytest.approx(bt / len(reqs))
    assert sum(r.timings["total"] for r in results) == pytest.approx(bt)
    # single-request path: the two views coincide
    r1 = db.query(reqs[0])
    assert r1.timings["batch_total"] == r1.timings["total"]


# --------------------------------------------------------------------- #
# UNION through the full serving path (ISSUE 2 satellite)
# --------------------------------------------------------------------- #
def test_union_inside_optional_through_serving(db):
    qt = ("{ ?s memberOf ?d } OPTIONAL "
          "{ { ?d subOrganizationOf Univ0 } UNION "
          "{ ?d subOrganizationOf Univ1 } }")
    rs = db.query(qt)
    q = sparql.parse(qt)
    assert np.array_equal(rs.survivor_mask, _direct_mask(q, db.graph))
    # over-approximation direction of union_split: every survivor of the
    # mandatory part is kept (OPTIONAL may only add optional-side triples)
    mand_mask = _direct_mask(sparql.parse("{ ?s memberOf ?d }"), db.graph)
    assert np.all(rs.survivor_mask[mand_mask])
    assert rs.template_keys and len(rs.template_keys) == 2  # one per part


def test_union_mixed_into_session_batches(db):
    union_q = ("{ ?d subOrganizationOf Univ0 } UNION "
               "{ ?d subOrganizationOf Univ1 }")
    bgp_reqs = [MEMBERS_OF.format(uni=f"Univ{i % 2}") for i in range(4)]
    reqs = bgp_reqs[:2] + [union_q] + bgp_reqs[2:]
    _, results = _submit_all(db, reqs, max_delay_ms=1e6, max_pending=8)
    for q, rs in zip(reqs, results):
        assert np.array_equal(rs.survivor_mask,
                              _direct_mask(sparql.parse(q), db.graph)), q
    # the union rider did not break same-template grouping of the rest
    m = db.metrics()
    assert m.requests == len(reqs)


def test_union_results_after_insert_through_session(db):
    union_q = ("{ ?s memberOf DeptFresh } UNION "
               "{ ?d subOrganizationOf Univ0 }")
    r_before = db.query(union_q)
    db.insert([("StudentF", "memberOf", "DeptFresh")])
    r_after = db.query(union_q)
    assert ("StudentF", "memberOf", "DeptFresh") not in list(r_before)
    assert ("StudentF", "memberOf", "DeptFresh") in list(r_after)
    assert np.array_equal(
        r_after.survivor_mask, _direct_mask(sparql.parse(union_q), db.graph)
    )


# --------------------------------------------------------------------- #
# deprecation shim
# --------------------------------------------------------------------- #
def test_exec_result_import_warns_but_works():
    import repro.engine as eng_mod

    with pytest.warns(DeprecationWarning, match="ExecResult"):
        cls = eng_mod.ExecResult
    # still the real class used internally
    from repro.engine.engine import ExecResult as internal

    assert cls is internal


def test_engine_accepts_plain_graph_unchanged():
    # back-compat: Engine(Graph) still works without a GraphDB source
    from repro.engine import Engine

    g = synth.lubm_like(n_universities=2, seed=0)
    eng = Engine(g)
    res = eng.execute(MEMBERS_OF.format(uni="Univ0"))
    assert res.survivors.any()
    assert eng.refresh() == 0  # no source: refresh is a no-op


# --------------------------------------------------------------------- #
# review regressions
# --------------------------------------------------------------------- #
def test_insert_is_atomic_on_malformed_input(db):
    v0, n0 = db.version, db.n_triples
    with pytest.raises(TypeError, match="triple #1"):
        db.insert([("NewNode", "p", "C"), ("bad",)])
    # nothing leaked into the live indexes or the committed snapshot
    assert db.version == v0 and db.n_triples == n0
    assert "NewNode" not in db.node_index and "C" not in db.node_index
    assert "p" not in db.label_index
    with pytest.raises(TypeError, match="triple #0"):
        db.delete([None])
    assert db.version == v0


def test_builder_rejects_keyword_names():
    for bad in ("AND", "WHERE", "UNION", "AND:x"):
        with pytest.raises(ValueError, match="invalid"):
            Q.triple("?a", bad, "?b")
        with pytest.raises(ValueError, match="invalid"):
            Q.triple("?a", "p0", bad)
    # keyword *prefixes* are fine and round-trip (tokenizer uses \b now)
    for ok in ("ANDERSON", "WHERE2", "UNIONIZED"):
        q = Q.triple("?a", "p0", ok)
        assert sparql.parse(q.sparql()) == q.build()


def test_session_exception_exit_drops_pending(db):
    q = MEMBERS_OF.format(uni="Univ0")
    m0 = db.metrics()
    with pytest.raises(KeyError):
        with db.session(max_delay_ms=1e6) as s:
            fut = s.submit(q)
            raise KeyError("boom")
    assert s.pending == 0 and not fut.done()
    # the dropped request is never executed, and result() says so clearly
    with pytest.raises(RuntimeError, match="dropped"):
        fut.result()
    assert db.metrics().requests == m0.requests


def test_prepare_once_same_results(db):
    # prepared path (sessions) and plain execute_many agree bit-for-bit
    reqs = [MEMBERS_OF.format(uni=f"Univ{i % 2}") for i in range(3)]
    reqs.append("{ ?d subOrganizationOf Univ0 } UNION "
                "{ ?d subOrganizationOf Univ1 }")
    plain = db.execute_many(reqs)
    _, via_session = _submit_all(db, reqs, max_delay_ms=1e6, max_pending=8)
    for a, b in zip(plain, via_session):
        assert np.array_equal(a.survivor_mask, b.survivor_mask)
