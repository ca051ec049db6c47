"""Algorithmic bytes of the edge sweep, on an SOI counted by hand."""
import numpy as np

import reference
from work import edge_sweep


def test_application_bytes_by_hand():
    # 5 edges: 5 x (4 + 4) id bytes; 6 rows x 2 words x 4 bytes read + written
    assert edge_sweep.application_bytes(5, 6, 40) == 40 + 96
    assert edge_sweep.words(32) == 1 and edge_sweep.words(33) == 2


def test_solve_bytes_of_a_chain_counted_by_hand():
    # { ?x a ?y . ?y b ?z }: 3 variables, operators (a,fwd) (a,bwd) (b,fwd)
    # (b,bwd); label a has 5 edges, b has 3, over 40 nodes (2 words)
    soi = reference.build_soi(reference.parse("{ ?x a ?y . ?y b ?z }"))
    assert len(soi.names) == 3
    edges = {"a": 5, "b": 3}
    ops = [edges[a] for _, a, _ in soi.edges for _ in (0, 1)]
    batch, sweeps = 2, 3
    got = edge_sweep.solve_bytes(ops, batch * len(soi.names), 40, sweeps)
    per_sweep = 2 * (8 * 5 + 8 * 6 * 2) + 2 * (8 * 3 + 8 * 6 * 2)
    assert per_sweep == 512
    assert got == sweeps * per_sweep


def test_reference_constants_get_a_row_of_their_own():
    soi = reference.build_soi(reference.parse("{ ?d sub U1 . ?s member ?d }"))
    assert soi.const == [None, "U1", None]
    assert np.array_equal(sorted(a for _, a, _ in soi.edges), ["member", "sub"])
