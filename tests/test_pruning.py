"""Pruning invariants (paper Sect. 5 / Tables 3-5): dual-simulation pruning
never changes any query's result set."""
import types

import numpy as np
import pytest
from tests._hyp import given, settings, st

from repro.core import dualsim, join, pruning, soi, sparql
from repro.core.graph import Graph
from repro.data import synth


def _solve_and_prune(q, g):
    mask = np.zeros(g.n_edges, dtype=bool)
    for part in sparql.union_split(q):
        s = soi.build_soi(part)
        c = soi.compile_soi(s, g)
        chi, _ = dualsim.solve_compiled(c, g, engine="dense")
        m, _ = pruning.prune_triples(s, chi, g)
        mask |= m
    from repro.core.graph import subgraph_triples

    return subgraph_triples(g, mask)


def _bindings_set(b):
    names = sorted(b.cols)
    return {tuple(b.cols[n][i] for n in names) for i in range(b.n_rows)} , names


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 500))
def test_bgp_results_identical_after_pruning(seed):
    g = synth.dbpedia_like(n_nodes=30, n_labels=4, n_edges=100, seed=seed)
    q = sparql.parse("{ ?a p0 ?b . ?b p1 ?c }")
    full = join.evaluate(q, g)
    pruned_g = _solve_and_prune(q, g)
    pr = join.evaluate(q, pruned_g)
    assert _bindings_set(full) == _bindings_set(pr)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 500))
def test_optional_results_identical_after_pruning(seed):
    g = synth.dbpedia_like(n_nodes=30, n_labels=4, n_edges=100, seed=seed)
    q = sparql.parse("{ ?a p0 ?b } OPTIONAL { ?b p1 ?c }")
    full = join.evaluate(q, g)
    pruned_g = _solve_and_prune(q, g)
    pr = join.evaluate(q, pruned_g)
    assert _bindings_set(full) == _bindings_set(pr)


def test_pruning_stats_lubm():
    g = synth.lubm_like(n_universities=3, seed=0)
    q = synth.lubm_l1_like()
    s = soi.build_soi(q)
    c = soi.compile_soi(s, g)
    chi, _ = dualsim.solve_compiled(c, g, engine="dense")
    _, stats = pruning.prune_triples(s, chi, g)
    assert 0 <= stats.n_after <= stats.n_triples
    assert 0.0 <= stats.fraction_pruned <= 1.0
    # every triple of every match survives
    m = join.evaluate(q, g)
    req = join.required_triples(q, g, m)
    assert req <= stats.n_after


# --------------------------------------------------------------------- #
# the label-blocked prune against a full scan over every triple
# --------------------------------------------------------------------- #
def _full_scan_prune(pattern_edges, chi, g):
    """Every triple tested against every pattern edge: the prune's
    definition, kept here as the oracle."""
    mask = np.zeros(g.n_edges, dtype=bool)
    per_edge = []
    label_of, s_of, o_of = g.triples[:, 1], g.triples[:, 0], g.triples[:, 2]
    for v, a, w in pattern_edges:
        if isinstance(a, str):
            la = g.label_index().get(a) if g.label_names is not None else None
            if la is None:
                per_edge.append(0)
                continue
        else:
            la = int(a)
        hit = (label_of == la) & chi[v][s_of] & chi[w][o_of]
        per_edge.append(int(hit.sum()))
        mask |= hit
    n_after = int(mask.sum())
    return mask, pruning.PruneStats(
        n_triples=g.n_edges,
        n_after=n_after,
        fraction_pruned=1.0 - n_after / max(g.n_edges, 1),
        per_edge_survivors=per_edge,
    )


def _edges(rng, n_vars, labels, k):
    return [(int(rng.integers(n_vars)), labels[int(rng.integers(len(labels)))],
             int(rng.integers(n_vars))) for _ in range(k)]


def _case(name, seed):
    """(graph, pattern edges, chi) for one equivalence case."""
    rng = np.random.default_rng(seed)
    n_vars = 4
    if name == "int_labels":
        g = synth.random_graph(40, 5, 300, seed=seed)
        edges = _edges(rng, n_vars, list(range(5)), 4)
    elif name == "string_labels":
        g = synth.dbpedia_like(n_nodes=30, n_labels=6, n_edges=200, seed=seed)
        edges = _edges(rng, n_vars, g.label_names, 4)
    elif name == "absent_string_label":
        g = synth.dbpedia_like(n_nodes=30, n_labels=4, n_edges=100, seed=seed)
        edges = [(0, "noSuchLabel", 1), (1, "p0", 2)]
    elif name == "label_without_triples":
        g = synth.random_graph(30, 3, 120, seed=seed)
        g = Graph(n_nodes=g.n_nodes, n_labels=5, triples=g.triples,
                  node_names=g.node_names,
                  label_names=["p0", "p1", "p2", "empty3", "empty4"])
        edges = [(0, 3, 1), (1, "empty4", 2), (2, 1, 3)]
    elif name == "int_label_past_the_table":
        g = synth.random_graph(30, 3, 120, seed=seed)
        edges = [(0, 7, 1), (1, 2, 2)]
    elif name == "empty_graph":
        g = Graph(n_nodes=8, n_labels=2, triples=np.zeros((0, 3), np.int32),
                  node_names=[f"n{i}" for i in range(8)],
                  label_names=["p0", "p1"])
        edges = [(0, "p0", 1), (1, 1, 2)]
    elif name == "repeated_pattern_edge":
        g = synth.random_graph(30, 3, 150, seed=seed)
        edges = [(0, 1, 1), (2, 0, 3), (0, 1, 1)]
    elif name == "self_loop":
        g = synth.random_graph(20, 3, 100, seed=seed)
        loops = np.stack([np.arange(20), rng.integers(0, 3, 20),
                          np.arange(20)], axis=1).astype(np.int32)
        g = Graph.from_arrays(20, 3, np.vstack([g.triples, loops]))
        edges = [(0, 0, 0), (1, 2, 1), (0, 1, 2)]
    elif name == "over_255_labels":
        g = synth.random_graph(50, 300, 2000, seed=seed)
        edges = _edges(rng, n_vars, list(range(250, 300)), 3) + [(0, "p299", 1)]
    else:
        raise ValueError(name)
    chi = rng.random((n_vars, g.n_nodes)) < 0.6
    return g, edges, chi


PRUNE_CASES = ["int_labels", "string_labels", "absent_string_label",
               "label_without_triples", "int_label_past_the_table",
               "empty_graph", "repeated_pattern_edge", "self_loop",
               "over_255_labels"]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("case", PRUNE_CASES)
def test_label_blocked_prune_equals_full_scan(case, seed):
    g, edges, chi = _case(case, seed)
    mask, stats = pruning.prune_triples(
        types.SimpleNamespace(pattern_edges=edges), chi, g)
    want_mask, want = _full_scan_prune(edges, chi, g)
    assert mask.dtype == bool and mask.shape == (g.n_edges,)
    assert np.array_equal(mask, want_mask)
    assert stats.n_triples == want.n_triples
    assert stats.n_after == want.n_after
    assert stats.per_edge_survivors == want.per_edge_survivors
    assert stats.fraction_pruned == want.fraction_pruned
    # crossed: each known label's block, once per pattern edge naming it
    hist = g.label_histogram()
    ids = [g.label_index().get(a) if isinstance(a, str) else a
           for _, a, _ in edges]
    assert stats.triples_crossed == sum(
        int(hist[la]) for la in ids if la is not None and la < g.n_labels)


@pytest.mark.parametrize("n_labels", [1, 200, 256, 257, 300])
def test_label_blocks_group_rows_stably(n_labels):
    g = synth.random_graph(40, n_labels, 1500, seed=n_labels)
    blocks = g.label_blocks()
    assert g.label_blocks() is blocks  # built once per graph
    assert blocks.starts[0] == 0 and blocks.starts[-1] == g.n_edges
    assert blocks.src.dtype == blocks.dst.dtype == np.int32
    for a in range(n_labels):
        rows = blocks.order[blocks.starts[a]:blocks.starts[a + 1]]
        assert np.array_equal(rows, np.flatnonzero(g.triples[:, 1] == a))
        sl = slice(blocks.starts[a], blocks.starts[a + 1])
        assert np.array_equal(blocks.src[sl], g.triples[rows, 0])
        assert np.array_equal(blocks.dst[sl], g.triples[rows, 2])


def test_prune_of_a_solved_lubm_query_equals_full_scan():
    g = synth.lubm_like(n_universities=2, seed=0)
    s = soi.build_soi(synth.lubm_l1_like())
    chi, _ = dualsim.solve_compiled(soi.compile_soi(s, g), g, engine="dense")
    mask, stats = pruning.prune_triples(s, chi, g)
    want_mask, want = _full_scan_prune(s.pattern_edges, chi, g)
    assert np.array_equal(mask, want_mask) and stats.n_after > 0
    assert stats.per_edge_survivors == want.per_edge_survivors
