"""`repro.engine` subsystem: template canonicalization, plan-cache behavior,
cost-model engine choice, microbatch demux, and end-to-end equivalence of
``Engine.execute`` with the direct solve_compiled + prune_triples path.

The zero-recompile acceptance criterion is asserted here via cache and
trace counters: a warm constant-rebound execute must not build a plan
(cache.misses unchanged = no SOI recompilation) and must not retrace the
jitted fixpoint (plan.metrics.traces unchanged)."""
import jax
import numpy as np
import pytest

from repro.core import dualsim, pruning, soi, sparql
from repro.data import synth
from repro.engine import (
    Engine,
    MicroBatcher,
    PlanCache,
    batch_layout,
    batched_soi,
    bucket_for,
    canonicalize,
    choose_engine,
)

from tests._hyp import given, settings, st


@pytest.fixture(scope="module")
def lubm():
    return synth.lubm_like(n_universities=3, seed=0)


# --------------------------------------------------------------------- #
# template canonicalization
# --------------------------------------------------------------------- #
def test_same_shape_different_constants_share_key():
    a = canonicalize(sparql.parse("{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }"))
    b = canonicalize(sparql.parse("{ ?x subOrganizationOf Univ2 . ?y memberOf ?x }"))
    assert a.template.key == b.template.key
    assert a.constants == ("Univ0",) and b.constants == ("Univ2",)
    assert a.var_names == ("d", "s") and b.var_names == ("x", "y")


def test_different_shapes_differ():
    a = canonicalize(sparql.parse("{ ?a p0 ?b }"))
    b = canonicalize(sparql.parse("{ ?a p1 ?b }"))  # label is part of the shape
    c = canonicalize(sparql.parse("{ ?a p0 ?b . ?b p0 ?c }"))
    assert len({a.template.key, b.template.key, c.template.key}) == 3


def test_repeated_constant_is_one_slot():
    # same constant twice expresses an equality two distinct constants don't
    a = canonicalize(sparql.parse("{ ?a p0 C . ?b p1 C }"))
    b = canonicalize(sparql.parse("{ ?a p0 C . ?b p1 D }"))
    assert a.template.n_slots == 1 and b.template.n_slots == 2
    assert a.template.key != b.template.key


def test_operator_structure_in_key():
    a = canonicalize(sparql.parse("{ ?a p0 ?b } AND { ?b p1 ?c }"))
    b = canonicalize(sparql.parse("{ ?a p0 ?b } OPTIONAL { ?b p1 ?c }"))
    assert a.template.key != b.template.key


# --------------------------------------------------------------------- #
# plan cache
# --------------------------------------------------------------------- #
def test_plan_cache_hit_miss_eviction():
    cache = PlanCache(capacity=2)
    built = []
    for key in ["a", "b", "a", "c", "b"]:  # c evicts b (LRU), then b rebuilds
        cache.get_or_build(key, lambda k=key: built.append(k))
    assert cache.hits == 1 and cache.misses == 4 and cache.evictions == 2
    assert built == ["a", "b", "c", "b"]
    s = cache.stats()
    assert s.size == 2 and s.hit_rate == pytest.approx(0.2)


# --------------------------------------------------------------------- #
# cost model
# --------------------------------------------------------------------- #
def _compiled(q, g):
    return soi.compile_soi(soi.build_soi(sparql.parse(q)), g)


def test_cost_model_dense_on_small_dense_graph():
    g = synth.random_graph(n_nodes=48, n_labels=2, n_edges=1500, seed=0)
    est = choose_engine(g, _compiled("{ ?a p0 ?b . ?b p1 ?c }", g))
    assert est.engine == "dense"
    assert est.costs["dense"] < est.costs["sparse"]


def test_cost_model_sparse_on_large_sparse_graph():
    g = synth.random_graph(n_nodes=20_000, n_labels=4, n_edges=40_000, seed=0)
    est = choose_engine(g, _compiled("{ ?a p0 ?b . ?b p1 ?c }", g))
    assert est.engine == "sparse"


def test_cost_model_dense_infeasible_at_scale():
    # 60k nodes: stacked bool[M, n, n] blows the dense memory budget
    g = synth.random_graph(n_nodes=60_000, n_labels=2, n_edges=50_000, seed=0)
    est = choose_engine(g, _compiled("{ ?a p0 ?b }", g))
    assert est.costs["dense"] == float("inf")
    assert est.engine == "sparse"


def test_cost_model_partitioned_needs_a_mesh():
    # single device: partitioned is pure block-padding overhead — infeasible
    g = synth.random_graph(n_nodes=60_000, n_labels=2, n_edges=50_000, seed=0)
    est = choose_engine(g, _compiled("{ ?a p0 ?b }", g), n_devices=1)
    assert est.costs["partitioned"] == float("inf")
    assert est.engine == "sparse"


def test_cost_model_partitioned_on_mesh_at_scale():
    # 8 devices + a graph past the dense budget: compute divides across the
    # mesh and the packed broadcast beats M chi-sized gathers -> partitioned
    g = synth.random_graph(n_nodes=60_000, n_labels=2, n_edges=50_000, seed=0)
    c = _compiled("{ ?a p0 ?b }", g)
    est = choose_engine(g, c, n_devices=8)
    assert est.engine == "partitioned"
    assert est.costs["partitioned"] < est.costs["sparse"]
    # communication terms only exist on a mesh: Gauss-Seidel sparse pays M
    # chi-sized collectives per sweep there, nothing on one device
    single = choose_engine(g, c, n_devices=1)
    assert est.costs["sparse"] > single.costs["sparse"]


def test_cost_model_small_graph_stays_single_shard_on_mesh():
    # a mesh alone must not flip tiny graphs off the dense engine
    g = synth.random_graph(n_nodes=48, n_labels=2, n_edges=1500, seed=0)
    est = choose_engine(g, _compiled("{ ?a p0 ?b . ?b p1 ?c }", g), n_devices=8)
    assert est.engine == "dense"


def test_dense_tier_hard_gate_matches_graph_budget():
    """ISSUE 8: past DENSE_ADJ_MAX_BYTES every dense-layout tier (dense,
    packed, packed_fused) is hard-infeasible in the cost model — never
    merely expensive — because operand *construction* would raise.  The
    smallest infeasible n makes the per-sweep cost favor the dense tier,
    so only the gate (not pricing) can exclude it."""
    from repro.core.graph import DENSE_ADJ_MAX_BYTES

    n = int(DENSE_ADJ_MAX_BYTES ** 0.5) + 1  # first n with n*n > budget
    g = synth.random_graph(n_nodes=n, n_labels=1, n_edges=10, seed=0)
    est = choose_engine(g, _compiled("{ ?a p0 ?b }", g))
    for tier in ("dense", "packed", "packed_fused"):
        assert est.costs[tier] == float("inf"), tier
    assert est.engine in ("sparse", "jacobi_packed")
    # the gate mirrors the construction-time guard exactly
    with pytest.raises(MemoryError):
        g.dense_adjacency(0)
    with pytest.raises(MemoryError):
        g.packed_adjacency(0)
    # one node fewer: construction is allowed again
    g2 = synth.random_graph(n_nodes=n - 1, n_labels=1, n_edges=10, seed=0)
    assert g2.dense_adjacency(0).shape == (n - 1, n - 1)


@pytest.mark.parametrize("backward", [False, True])
def test_packed_adjacency_packs_the_dense_plane(backward):
    """Built from the edge list, the packed adjacency is bit for bit the
    ``bitops.pack`` of the dense plane, pad bits of the last word zero."""
    from repro.core import bitops

    g = synth.random_graph(n_nodes=70, n_labels=2, n_edges=300, seed=3)
    for a in range(g.n_labels):
        want = np.asarray(bitops.pack(g.dense_adjacency(a, backward)))
        got = g.packed_adjacency(a, backward)
        assert got.dtype == np.uint32
        assert np.array_equal(got, want)


# --------------------------------------------------------------------- #
# batcher
# --------------------------------------------------------------------- #
def test_bucket_for():
    assert [bucket_for(n) for n in (1, 2, 3, 5, 16, 99)] == [1, 2, 4, 8, 16, 16]


def test_batched_soi_instance_boundaries():
    s = soi.build_soi(sparql.parse("{ ?a p0 ?b . ?b p1 ?c }"))
    layout = batch_layout([s, s, s])
    assert layout.offsets == [0, s.n_vars, 2 * s.n_vars]
    # per-instance renaming: instance i's variables carry suffix "#i"
    union = layout.soi
    for i in range(3):
        sl = layout.chi_slice(i)
        assert all(b.endswith(f"#{i}") for b in union.base[sl])
    assert union.n_vars == 3 * s.n_vars
    assert len(union.edge_ineqs) == 3 * len(s.edge_ineqs)
    # back-compat wrapper returns the same union
    assert batched_soi([s, s, s]).base == union.base


def test_microbatcher_dedups_before_chunking():
    # 20 duplicate submits at cap 16: ONE microbatch (bucket 1), not two —
    # dedup by constants happens before chunking
    mb = MicroBatcher(buckets=(1, 2, 4, 8, 16))
    q = "{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }"
    for i in range(20):
        mb.add(i, canonicalize(sparql.parse(q)))
    groups = list(mb.drain())
    assert len(groups) == 1
    assert groups[0].bucket == 1
    assert len(groups[0].requests) == 20  # every rider still demuxes


def test_microbatcher_chunks_by_unique_constants():
    # 17 unique + 3 duplicate tuples at cap 16 -> chunks of 16 and 1 uniques
    mb = MicroBatcher(buckets=(1, 2, 4, 8, 16))
    reqs = [f"{{ ?d subOrganizationOf Univ{i} . ?s memberOf ?d }}"
            for i in range(17)]
    reqs += reqs[:3]
    for i, q in enumerate(reqs):
        mb.add(i, canonicalize(sparql.parse(q)))
    groups = list(mb.drain())
    assert [len({inst.constants for _, inst in g.requests}) for g in groups] \
        == [16, 1]
    assert sum(len(g.requests) for g in groups) == 20


def test_microbatcher_groups_by_template():
    mb = MicroBatcher(buckets=(1, 2, 4))
    q_a = ["{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }",
           "{ ?x subOrganizationOf Univ1 . ?y memberOf ?x }",
           "{ ?d subOrganizationOf Univ2 . ?s memberOf ?d }"]
    q_b = ["{ ?p publicationAuthor ?s }"]
    for i, q in enumerate(q_a + q_b):
        mb.add(i, canonicalize(sparql.parse(q)))
    groups = list(mb.drain())
    assert len(mb) == 0
    sizes = sorted(len(g.requests) for g in groups)
    assert sizes == [1, 3]
    big = next(g for g in groups if len(g.requests) == 3)
    assert big.bucket == 4  # 3 requests pad up to the 4-bucket


# --------------------------------------------------------------------- #
# warm path: zero recompiles, zero retraces (acceptance criterion)
# --------------------------------------------------------------------- #
def test_warm_rebind_no_recompile_no_retrace(lubm):
    eng = Engine(lubm)
    r0 = eng.execute("{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }")
    assert not r0.cache_hit
    builds_after_cold = eng.cache.misses
    plan, _ = eng.plan_for(
        canonicalize(sparql.parse("{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }"))
    )
    traces_after_cold = plan.metrics.traces
    assert traces_after_cold == 1

    for uni in ["Univ1", "Univ2", "Univ0"]:
        r = eng.execute(f"{{ ?q subOrganizationOf {uni} . ?m memberOf ?q }}")
        assert r.cache_hit
    # zero SOI recompilation (no plan builds) and zero jit retraces
    assert eng.cache.misses == builds_after_cold
    assert plan.metrics.traces == traces_after_cold
    assert plan.metrics.executions == 4


def test_packed_fused_warm_rebind_no_recompile_no_retrace(lubm):
    """The end-to-end packed engine serves constant rebinds on one trace:
    constants scatter into the packed init as uint32 words, so the warm
    path's avals never change shape or dtype (ISSUE 5 acceptance)."""
    eng = Engine(lubm, engine="packed_fused")
    r0 = eng.execute("{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }")
    assert not r0.cache_hit and r0.engine == "packed_fused"
    plan, _ = eng.plan_for(
        canonicalize(sparql.parse("{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }"))
    )
    assert plan.metrics.traces == 1
    for uni in ["Univ1", "Univ2", "Univ0"]:
        r = eng.execute(f"{{ ?q subOrganizationOf {uni} . ?m memberOf ?q }}")
        assert r.cache_hit and r.engine == "packed_fused"
        assert np.array_equal(
            r.survivors, _direct_mask(
                sparql.parse(f"{{ ?q subOrganizationOf {uni} . ?m memberOf ?q }}"),
                lubm,
            )
        )
    assert plan.metrics.traces == 1  # zero retraces across rebinds


def test_adjacency_shared_across_plans(lubm):
    # adjacency depends only on (engine, mats, graph): plans for different
    # batch buckets of one template must share the device arrays
    eng = Engine(lubm, engine="dense")
    qs = [
        f"{{ ?d subOrganizationOf {u} . ?s memberOf ?d }}"
        for u in ("Univ0", "Univ1")
    ]
    eng.execute(qs[0])  # bucket-1 plan
    eng.execute_many(qs)  # bucket-2 plan, same template
    inst = canonicalize(sparql.parse(qs[0]))
    p1, _ = eng.plan_for(inst, bucket=1)
    p2, _ = eng.plan_for(inst, bucket=2)
    assert p1 is not p2
    assert p1.operands.adj_dense is p2.operands.adj_dense


def test_results_differ_across_constants(lubm):
    eng = Engine(lubm)
    rows = [
        eng.execute(f"{{ ?d subOrganizationOf {u} . ?s memberOf ?d }}")
        for u in ("Univ0", "Univ1")
    ]
    assert not np.array_equal(rows[0].survivors, rows[1].survivors)
    # each answer only keeps the requested university's component
    assert rows[0].bindings["d"].sum() > 0
    assert not np.any(rows[0].bindings["d"] & rows[1].bindings["d"])


def test_unknown_constant_gives_empty_result(lubm):
    eng = Engine(lubm)
    r = eng.execute("{ ?d subOrganizationOf UnivNoSuch . ?s memberOf ?d }")
    assert r.stats.n_after == 0 and not r.survivors.any()


# --------------------------------------------------------------------- #
# end-to-end equivalence with the direct pipeline
# --------------------------------------------------------------------- #
def _direct_mask(q, g, engine="dense"):
    mask = np.zeros(g.n_edges, dtype=bool)
    for part in sparql.union_split(q):
        s = soi.build_soi(part)
        c = soi.compile_soi(s, g)
        chi, _ = dualsim.solve_compiled(c, g, engine=engine)
        m, _ = pruning.prune_triples(s, chi, g)
        mask |= m
    return mask


E2E_QUERIES = [
    "{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }",
    "{ ?x memberOf ?y . ?y subOrganizationOf ?z . ?x undergraduateDegreeFrom ?z }",
    "{ ?s memberOf ?d } OPTIONAL { ?s advisor ?a }",
    "{ ?d subOrganizationOf Univ0 } UNION { ?d subOrganizationOf Univ1 }",
    "{ ?p publicationAuthor ?s . ?s memberOf ?d } AND { ?d subOrganizationOf Univ2 }",
]


@pytest.mark.parametrize("qt", E2E_QUERIES)
def test_engine_matches_direct_path(lubm, qt):
    eng = Engine(lubm)
    res = eng.execute(qt)
    assert np.array_equal(res.survivors, _direct_mask(sparql.parse(qt), lubm))
    assert res.stats.n_after == int(res.survivors.sum())


@pytest.mark.parametrize(
    "engine", ["dense", "sparse", "packed", "jacobi_packed", "partitioned"]
)
def test_engine_override_same_fixpoint(lubm, engine):
    qt = "{ ?d subOrganizationOf Univ1 . ?s memberOf ?d }"
    res = Engine(lubm, engine=engine).execute(qt)
    assert res.engine == engine
    assert np.array_equal(res.survivors, _direct_mask(sparql.parse(qt), lubm))


def test_partitioned_warm_rebind_no_recompile_no_retrace(lubm):
    """Acceptance: engine="partitioned" serves constant rebinds with zero
    plan builds and zero jit retraces, like every other engine."""
    eng = Engine(lubm, engine="partitioned")
    r0 = eng.execute("{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }")
    assert not r0.cache_hit and r0.engine == "partitioned"
    plan, _ = eng.plan_for(
        canonicalize(sparql.parse("{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }"))
    )
    builds, traces = eng.cache.misses, plan.metrics.traces
    for uni in ["Univ1", "Univ2", "Univ0"]:
        r = eng.execute(f"{{ ?q subOrganizationOf {uni} . ?m memberOf ?q }}")
        assert r.cache_hit
    assert eng.cache.misses == builds
    assert plan.metrics.traces == traces


def test_partitioned_mesh_must_divide_n_blocks(lubm):
    """A mesh whose size does not divide the destination blocks raises
    instead of leaving every partitioned operand on the first device."""
    from jax.sharding import Mesh

    from repro.distributed import ctx as dctx
    from repro.engine.plan import CompiledPlan

    mesh = Mesh(np.asarray(jax.devices()[:1] * 3), (dctx.NODE_AXIS,))
    template = canonicalize(
        sparql.parse("{ ?d subOrganizationOf Univ0 . ?s memberOf ?d }")
    ).template
    with pytest.raises(ValueError, match="does not divide"):
        CompiledPlan(template, lubm, engine="partitioned", mesh=mesh,
                     n_blocks=4)


@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs simulated devices: "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8",
)
def test_partitioned_engine_on_device_mesh(lubm):
    """Multi-device CI job: the partitioned engine shards chi over a real
    mesh (one destination block per device) and still matches the direct
    single-shard pipeline."""
    from repro.distributed import ctx as dctx

    mesh = dctx.node_mesh()
    eng = Engine(lubm, engine="partitioned", mesh=mesh)
    assert eng.n_blocks == len(jax.devices())
    qs = [f"{{ ?d subOrganizationOf {u} . ?s memberOf ?d }}"
          for u in ("Univ0", "Univ1", "Univ2")]
    for q in qs:
        res = eng.execute(q)
        assert res.engine == "partitioned"
        assert np.array_equal(res.survivors, _direct_mask(sparql.parse(q), lubm))
    # warm path stays zero-retrace on the mesh too
    plan, hit = eng.plan_for(canonicalize(sparql.parse(qs[0])))
    assert hit and plan.metrics.traces == 1
    # chi's node axis is actually sharded across the mesh
    assert plan.chi_spec is not None
    assert plan.operands.edge_src_b[0].sharding.num_devices == len(jax.devices())


@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs simulated devices: "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8",
)
def test_auto_picks_partitioned_on_mesh_past_dense_budget():
    """Acceptance: auto + a >= 2-device mesh on a graph past the dense
    budget serves through solve_partitioned, zero warm retraces."""
    from repro.distributed import ctx as dctx

    g = synth.random_graph(n_nodes=60_000, n_labels=2, n_edges=50_000, seed=0)
    eng = Engine(g, engine="auto", mesh=dctx.node_mesh())
    q = "{ ?a p0 ?b . ?b p1 ?a }"
    r0 = eng.execute(q)
    assert r0.engine == "partitioned" and not r0.cache_hit
    plan, _ = eng.plan_for(canonicalize(sparql.parse(q)))
    assert plan.cost is not None and plan.cost.engine == "partitioned"
    traces = plan.metrics.traces
    r1 = eng.execute(q)
    assert r1.cache_hit and plan.metrics.traces == traces
    assert np.array_equal(r0.survivors, r1.survivors)


def test_execute_many_matches_execute(lubm):
    reqs = [
        f"{{ ?d subOrganizationOf {u} . ?s memberOf ?d }}"
        for u in ("Univ0", "Univ1", "Univ2", "Univ0", "Univ1")
    ] + ["{ ?d subOrganizationOf Univ0 } UNION { ?d subOrganizationOf Univ1 }"]
    eng = Engine(lubm)
    batched = eng.execute_many(reqs)
    singles = [Engine(lubm).execute(q) for q in reqs]
    for b, s, q in zip(batched, singles, reqs):
        assert np.array_equal(b.survivors, s.survivors), q
        assert b.sweeps > 0
    # the five same-template requests rode one microbatch (3 unique -> bucket 4)
    assert batched[0].batch == 4
    m = eng.metrics()
    assert m.requests == len(reqs)
    assert m.microbatches >= 1


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_engine_matches_direct_path_property(seed):
    """Engine.execute survivors == direct solve_compiled + prune_triples on
    random constant-parameterized queries over lubm_like data."""
    g = synth.lubm_like(n_universities=2, seed=1)
    rng = np.random.default_rng(seed)
    unis = [n for n in g.node_names if n.startswith("Univ")]
    u = unis[rng.integers(len(unis))]
    qt = (
        f"{{ ?d subOrganizationOf {u} . ?s memberOf ?d . ?s advisor ?a }}"
        if rng.random() < 0.5
        else f"{{ ?s undergraduateDegreeFrom {u} }} OPTIONAL {{ ?p publicationAuthor ?s }}"
    )
    res = Engine(g).execute(qt)
    assert np.array_equal(res.survivors, _direct_mask(sparql.parse(qt), g))
