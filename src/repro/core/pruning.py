"""Per-query database pruning (paper Sect. 5, Tables 3-5).

A triple ``(s, a, o)`` of the database *survives* pruning iff some pattern
edge ``(v, a, w)`` of the query's SOI has ``chi[v][s] and chi[w][o]``; all
other triples are irrelevant for any match (Theorems 1/2) and can be dropped
before handing the query to a downstream join processor.

Each pattern edge crosses only its own label's block of the graph's
:meth:`~repro.core.graph.Graph.label_blocks` index, not every triple.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .graph import Graph, subgraph_triples
from .soi import SOI


@dataclasses.dataclass
class PruneStats:
    n_triples: int
    n_after: int
    fraction_pruned: float
    per_edge_survivors: list[int]
    # triples read, summed over the pattern edges' label blocks
    triples_crossed: int = 0


def prune_triples(
    soi: SOI, chi: np.ndarray, g: Graph
) -> tuple[np.ndarray, PruneStats]:
    """Boolean survivor mask over ``g.triples`` plus stats."""
    mask = np.zeros(g.n_edges, dtype=bool)
    per_edge = []
    crossed = 0
    order, starts, src, dst = g.label_blocks()
    for v, a, w in soi.pattern_edges:
        if isinstance(a, str):
            la = g.label_index().get(a) if g.label_names is not None else None
        else:
            la = int(a)
        if la is None or not 0 <= la < g.n_labels:
            per_edge.append(0)
            continue
        lo, hi = starts[la], starts[la + 1]
        hit = chi[v][src[lo:hi]] & chi[w][dst[lo:hi]]
        mask[order[lo:hi][hit]] = True
        per_edge.append(int(hit.sum()))
        crossed += int(hi - lo)
    n_after = int(mask.sum())
    return mask, PruneStats(
        n_triples=g.n_edges,
        n_after=n_after,
        fraction_pruned=1.0 - n_after / max(g.n_edges, 1),
        per_edge_survivors=per_edge,
        triples_crossed=crossed,
    )


def pruned_graph(soi: SOI, chi: np.ndarray, g: Graph) -> tuple[Graph, PruneStats]:
    mask, stats = prune_triples(soi, chi, g)
    return subgraph_triples(g, mask), stats
