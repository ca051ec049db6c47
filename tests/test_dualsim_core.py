"""Dual-simulation engine correctness: all engines vs the Ma et al. oracle
(paper Def. 2 / Prop. 1/2), plus the paper's worked examples."""
import jax.numpy as jnp
import numpy as np
import pytest
from tests._hyp import given, settings, st

from repro.core import dualsim, soi
from repro.core.graph import Graph
from repro.core.hhk import dual_simulation_hhk
from repro.core.ma_baseline import dual_simulation_ma
from repro.data import synth

ENGINES = ["dense", "packed", "packed_fused", "sparse", "worklist"]


def _random_instance(seed):
    rng = np.random.default_rng(seed)
    n_labels = int(rng.integers(1, 4))
    pat = synth.random_pattern(
        n_vars=int(rng.integers(2, 5)),
        n_labels=n_labels,
        n_edges=int(rng.integers(1, 7)),
        seed=seed,
    )
    db = synth.random_graph(
        n_nodes=int(rng.integers(3, 40)),
        n_labels=n_labels,
        n_edges=int(rng.integers(5, 120)),
        seed=seed + 1,
    )
    return pat, db


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000))
def test_engines_match_ma_oracle(seed):
    pat, db = _random_instance(seed)
    s_ma, _ = dual_simulation_ma(pat, db)
    for eng in ENGINES:
        s, _ = dualsim.largest_dual_simulation(pat, db, engine=eng)
        assert np.array_equal(s, s_ma), f"{eng} != Ma et al. (seed {seed})"


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_hhk_matches_ma_oracle(seed):
    pat, db = _random_instance(seed)
    s_ma, _ = dual_simulation_ma(pat, db)
    s_hhk, _ = dual_simulation_hhk(pat, db)
    assert np.array_equal(s_hhk, s_ma), f"HHK != Ma et al. (seed {seed})"


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_union_of_dual_simulations_is_dual_simulation(seed):
    """Prop. 1's proof ingredient: S_max contains every dual simulation, so
    adding any match-induced relation to S_max leaves it unchanged."""
    pat, db = _random_instance(seed)
    s, _ = dualsim.largest_dual_simulation(pat, db, engine="dense")
    s_ma, _ = dual_simulation_ma(pat, db)
    assert np.array_equal(s | s_ma, s_ma)


def test_paper_fig4_counterexample():
    """Fig. 4: the largest dual simulation may keep nodes in no match.
    P: v <-> w (2-cycle).  K: p1 <-> p2, and p3 -> p2, p3 -> p4, p4 -> p3
    arranged so p4 'looks' matched through distributed obligations."""
    pat = Graph.from_arrays(2, 1, [(0, 0, 1), (1, 0, 0)])
    # K: p1->p2, p2->p1 (true match); p3->p2 (p3 has out-edge into the cycle)
    # p4->p3, p3->p4: p3/p4 form their own 2-cycle -> they ARE matches;
    # instead take: p4->p1, p2->p4: p4 has in+out edges but is in no 2-cycle.
    db = Graph.from_arrays(4, 1, [(0, 0, 1), (1, 0, 0), (3, 0, 0), (1, 0, 3)])
    s, _ = dualsim.largest_dual_simulation(pat, db, engine="dense")
    s_ma, _ = dual_simulation_ma(pat, db)
    assert np.array_equal(s, s_ma)
    # p4 (id 3) survives on both pattern nodes although (p4, p1) and (p2, p4)
    # do not close a 2-cycle -> dual simulation over-approximates matches.
    assert s[0, 3] and s[1, 3]


def test_empty_propagation_disconnects_component():
    """If a pattern edge has no support, its whole connected component's
    candidate sets collapse to empty."""
    pat = Graph.from_arrays(3, 2, [(0, 0, 1), (1, 1, 2)])
    db = Graph.from_arrays(4, 2, [(0, 0, 1), (1, 0, 2)])  # label 1 missing
    for eng in ENGINES:
        s, _ = dualsim.largest_dual_simulation(pat, db, engine=eng)
        assert not s.any(), eng


def test_eq12_vs_eq13_same_fixpoint():
    """The summary-vector init (Eq. 13) is exact, not just sound."""
    pat, db = _random_instance(123)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    chi13, _ = dualsim.solve_worklist(c, db, eq13_init=True)
    chi12, _ = dualsim.solve_worklist(c, db, eq13_init=False)
    assert np.array_equal(chi13, chi12)


@pytest.mark.parametrize("heuristic", ["sparse_first", "fifo"])
def test_worklist_heuristics_same_fixpoint(heuristic):
    pat, db = _random_instance(7)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    chi, evals = dualsim.solve_worklist(c, db, heuristic=heuristic)
    s_ma, _ = dual_simulation_ma(pat, db)
    # re-order rows to pattern order
    s, _ = dualsim.largest_dual_simulation(pat, db, engine="worklist")
    assert np.array_equal(s, s_ma)
    assert evals > 0


def test_max_sweeps_cap():
    pat, db = _random_instance(5)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    ops = dualsim.make_dense_operands(c, db)
    chi, it = dualsim.solve_dense(ops, max_sweeps=1)
    assert int(it) <= 1


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 5000))
def test_optimized_engines_same_fixpoint(seed):
    """§Perf engines (jacobi_packed, partitioned) reach the same largest
    solution as the paper-faithful Gauss–Seidel sparse engine."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 30)) * 16  # partitionable
    db = synth.random_graph(n, 3, int(rng.integers(10, 200)), seed=seed)
    pat = synth.random_pattern(3, 3, 4, seed=seed)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    ops = dualsim.make_sparse_operands(c, db)
    chi_gs, _ = dualsim.solve_sparse(ops, mode="gs")
    chi_j, _ = dualsim.solve_sparse(ops, mode="jacobi_packed")
    assert np.array_equal(np.asarray(chi_gs), np.asarray(chi_j))
    ops_p = dualsim.make_partitioned_operands(c, db, n_blocks=4)
    chi_p, _ = dualsim.solve_partitioned(ops_p)
    assert np.array_equal(np.asarray(chi_gs), np.asarray(chi_p)[:, :n])


def test_partitioned_operands_layout():
    """Every edge lands in the block owning its destination; pad rows use
    the out-of-range local id and are dropped by the segment reduce."""
    db = synth.random_graph(64, 2, 300, seed=3)
    pat = synth.random_pattern(2, 2, 2, seed=3)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    ops = dualsim.make_partitioned_operands(c, db, n_blocks=8)
    n_local = dualsim.padded_node_count(64, 8) // 8  # 32-aligned per block
    for src_b, dst_b in zip(ops.edge_src_b, ops.edge_dst_b):
        assert src_b.shape == dst_b.shape
        d = np.asarray(dst_b)
        assert ((d >= 0) & (d <= n_local)).all()


def test_partitioned_operands_pad_unaligned_graph():
    """n % n_blocks != 0 is the partitioner's problem now: the node axis is
    padded to the next block multiple, pad columns stay dead, and the sliced
    fixpoint matches the unpartitioned engines."""
    db = synth.random_graph(61, 2, 200, seed=9)  # 61 % 8 != 0
    pat = synth.random_pattern(2, 2, 3, seed=9)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    ops = dualsim.make_partitioned_operands(c, db, n_blocks=8)
    n_pad = dualsim.padded_node_count(61, 8)
    # each of the 8 blocks is padded to a 32-bit word multiple (Sect. 12)
    assert n_pad == 256 and ops.init.shape[-1] == n_pad
    assert not np.asarray(ops.init)[:, 61:].any()  # pad columns dead
    chi_p, _ = dualsim.solve_partitioned(ops)
    assert not np.asarray(chi_p)[:, 61:].any()
    chi_ref, _ = dualsim.solve_sparse(dualsim.make_sparse_operands(c, db))
    assert np.array_equal(np.asarray(chi_p)[:, :61], np.asarray(chi_ref))


def test_partitioned_operands_adj_cache_shared():
    """Edge blocks depend only on (mats, graph, n_blocks): two compilations
    against one graph share the device arrays through the adjacency cache."""
    db = synth.random_graph(32, 2, 100, seed=4)
    pat = synth.random_pattern(2, 2, 2, seed=4)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    cache: dict = {}
    a = dualsim.make_partitioned_operands(c, db, n_blocks=4, adj_cache=cache)
    b = dualsim.make_partitioned_operands(c, db, n_blocks=4, adj_cache=cache)
    assert a.edge_src_b[0] is b.edge_src_b[0]
    # a different block count is a different layout, not a false hit
    d = dualsim.make_partitioned_operands(c, db, n_blocks=2, adj_cache=cache)
    assert d.edge_src_b[0] is not a.edge_src_b[0]


# --------------------------------------------------------------------- #
# cross-engine equivalence: all five batched engines vs the paper's
# sequential worklist, over random BGP / AND / OPTIONAL queries
# --------------------------------------------------------------------- #
ALL_BATCHED = (
    "dense", "packed", "packed_fused", "sparse", "jacobi_packed",
    "partitioned",
)


def _random_query(rng, n_labels: int, node_names):
    from repro.core.sparql import And, BGP, Const, Optional_, Triple, Var

    def term():
        if rng.random() < 0.15:
            return Const(str(node_names[rng.integers(len(node_names))]))
        return Var(f"v{rng.integers(4)}")

    def bgp():
        return BGP(tuple(
            Triple(term(), f"p{rng.integers(n_labels)}", term())
            for _ in range(rng.integers(1, 4))
        ))

    q = bgp()
    r = rng.random()
    if r < 0.35:
        q = And(q, bgp())
    elif r < 0.7:
        q = Optional_(q, bgp())
    return q


def _check_cross_engine(seed: int) -> None:
    rng = np.random.default_rng(seed)
    n_labels = int(rng.integers(1, 4))
    db = synth.random_graph(
        n_nodes=int(rng.integers(3, 40)),
        n_labels=n_labels,
        n_edges=int(rng.integers(5, 120)),
        seed=seed + 1,
    )
    q = _random_query(rng, n_labels, db.node_names)
    c = soi.compile_soi(soi.build_soi(q), db)
    ref, _ = dualsim.solve_worklist(c, db)
    for eng in ALL_BATCHED:
        chi, _ = dualsim.solve_compiled(c, db, engine=eng, n_blocks=4)
        assert np.array_equal(chi, ref), f"{eng} != worklist (seed {seed})"


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_cross_engine_equivalence_property(seed):
    """dense / packed / packed_fused / sparse(gs) / sparse(jacobi_packed) /
    partitioned all reach solve_worklist's fixpoint on random graph x query
    instances."""
    _check_cross_engine(seed)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 7, 42])
def test_cross_engine_equivalence_fixed_seeds(seed):
    """Deterministic slice of the property above (runs without hypothesis)."""
    _check_cross_engine(seed)


def test_packed_fused_impls_match():
    """Both lowerings of the fused engine (Pallas kernel in interpret mode,
    word-wise XLA) compute the worklist fixpoint in the same sweep count."""
    db = synth.random_graph(45, 3, 150, seed=13)
    pat = synth.random_pattern(3, 3, 4, seed=13)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    ref, _ = dualsim.solve_worklist(c, db)
    ops = dualsim.make_packed_operands(c, db)
    chi_k, it_k = dualsim.solve_packed_fused(ops, impl="interpret")
    chi_w, it_w = dualsim.solve_packed_fused(ops, impl="words")
    assert np.array_equal(np.asarray(chi_k), ref)
    assert np.array_equal(np.asarray(chi_w), ref)
    assert int(it_k) == int(it_w)


@pytest.mark.parametrize("mode", ["gs", "jacobi_packed"])
def test_sparse_impls_match(mode):
    """Both segmented-OR lowerings (blocked Pallas kernel in interpret
    mode, word-wise XLA) drive the edge-list engine to the worklist
    fixpoint in the same sweep count, in both sweep orders."""
    db = synth.random_graph(77, 3, 260, seed=21)  # 77 % 32 != 0
    pat = synth.random_pattern(3, 3, 4, seed=21)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    ref, _ = dualsim.solve_worklist(c, db)
    ops = dualsim.make_sparse_operands(c, db)
    chi_k, it_k = dualsim.solve_sparse(ops, mode=mode, impl="kernel")
    chi_w, it_w = dualsim.solve_sparse(ops, mode=mode, impl="words")
    assert np.array_equal(np.asarray(chi_k), ref)
    assert np.array_equal(np.asarray(chi_w), ref)
    assert int(it_k) == int(it_w)


def test_sparse_kernel_without_blocked_layout_raises():
    """impl="kernel" on operands lacking the blocked segmented-OR layout is
    an error, never a quiet drop to the word-wise lowering; callers that
    build operands by hand ask for impl="words" explicitly."""
    import dataclasses

    db = synth.random_graph(40, 2, 90, seed=5)
    pat = synth.random_pattern(3, 2, 3, seed=5)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    flat = dataclasses.replace(
        dualsim.make_sparse_operands(c, db),
        seg_src_b=None, seg_dst_b=None, seg_win=None,
    )
    with pytest.raises(ValueError, match="blocked segmented-OR layout"):
        dualsim.solve_sparse(flat, impl="kernel")
    ref, _ = dualsim.solve_worklist(c, db)
    chi, _ = dualsim.solve_sparse(flat, impl="words")
    assert np.array_equal(np.asarray(chi), ref)


# --------------------------------------------------------------------- #
# packed-chi invariants: the while_loop never packs or unpacks (ISSUE 5).
# The jaxpr machinery lives in tools.reprolint.dynamic so the same check
# runs standalone in the CI reprolint job (ISSUE 7).
# --------------------------------------------------------------------- #
from tools.reprolint import dynamic as rl_dynamic  # noqa: E402


def test_packed_fused_while_body_has_no_pack_or_unpack():
    """ISSUE 5 acceptance, asserted for the KERNEL lowering (what
    accelerators serve): chi is uint32 words through the entire
    lax.while_loop — the body jaxpr contains none of the primitives pack
    (shift_left + reduce_sum) or unpack (shift_right + 32-lane broadcast)
    lower to, no bool [V, n] plane is materialized, and the loop carry
    holds no boolean chi.  The CPU ``words`` lowering is exempt by
    construction: it extracts frontier bits with jnp shifts inside the
    body (DESIGN.md Sect. 9, "Lowerings")."""
    db = synth.random_graph(70, 2, 200, seed=3)  # 70 % 32 != 0
    pat = synth.random_pattern(3, 2, 3, seed=3)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    ops = dualsim.make_packed_operands(c, db)
    bodies = rl_dynamic._while_bodies(
        lambda o: dualsim.solve_packed_fused(o, impl="interpret"), ops
    )
    assert bodies, "fused solver lost its while_loop"
    for body in bodies:
        assert rl_dynamic.check_fused_body(body) == []


def test_edge_engines_while_body_is_pack_free():
    """ISSUE 8 acceptance: every edge-list engine (sparse gs,
    jacobi_packed — words and kernel lowerings — and partitioned) carries
    packed uint32 chi through the while_loop with NO per-sweep pack
    (``reduce_sum``) and no bool ``[V, n]`` plane; ``y`` arrives already
    packed from the segmented-OR primitive."""
    db = synth.random_graph(70, 2, 200, seed=4)  # 70 % 32 != 0
    pat = synth.random_pattern(3, 2, 3, seed=4)
    c = soi.compile_soi(dualsim.pattern_graph_soi(pat), db)
    ops_s = dualsim.make_sparse_operands(c, db)
    cases = [
        (ops_s, lambda o: dualsim.solve_sparse(o, mode="gs", impl="words")),
        (ops_s, lambda o: dualsim.solve_sparse(o, mode="gs", impl="kernel")),
        (ops_s, lambda o: dualsim.solve_sparse(o, mode="jacobi_packed",
                                               impl="words")),
        (ops_s, lambda o: dualsim.solve_sparse(o, mode="jacobi_packed",
                                               impl="kernel")),
        (dualsim.make_partitioned_operands(c, db, n_blocks=4),
         dualsim.solve_partitioned),
    ]
    for ops, solve in cases:
        bodies = rl_dynamic._while_bodies(solve, ops)
        assert bodies
        for body in bodies:
            assert rl_dynamic.check_edge_body(body) == []


def test_fused_body_audit_flags_a_pack_round_trip():
    """The audit is not vacuous: a while body that unpacks and re-packs
    chi, or carries it as a bool plane, is reported."""
    import jax

    from repro.core import bitops

    chi = jnp.zeros((3, 3), jnp.uint32)

    def round_trip(c):
        def body(state):
            x, i = state
            return bitops.pack(bitops.unpack(x, 70)), i + 1

        return jax.lax.while_loop(lambda s: s[1] < 2, body, (c, 0))

    (body,) = rl_dynamic._while_bodies(round_trip, chi)
    found = " ".join(rl_dynamic.check_fused_body(body))
    assert "pack/unpack primitives" in found and "bool" in found

    def bool_carry(c):
        return jax.lax.while_loop(
            lambda s: s[1] < 2, lambda s: (~s[0], s[1] + 1),
            (bitops.unpack(c, 70), 0),
        )

    (body,) = rl_dynamic._while_bodies(bool_carry, chi)
    assert any("bool chi plane" in v for v in rl_dynamic.check_fused_body(body))


def test_dynamic_cross_check_runs_clean():
    """The standalone CI cross-check (all packed engines) reports clean."""
    assert rl_dynamic.check_packed_engines() == []
