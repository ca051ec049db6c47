"""Bytes one edge-sweep operator application needs, by the algorithm.

An operator is one (label, direction) of the query's system of
inequalities; applying it to ``V`` variable rows over ``n`` nodes reads each
of the label's ``E`` edges' two int32 ids once, reads the source frontier's
``V x ceil(n / 32)`` uint32 words once and writes the destination's words
once.  How today's implementation moves those bits (an int8 message plane,
one-hot tiles) is not counted: a faster layout does the same work.
"""
from __future__ import annotations

# the served fixpoint's XLA program: ``jax.jit`` of CompiledPlan's ``_run``
FIXPOINT_PROGRAM = r"^jit__run$"
WORD_BYTES = 4
ID_BYTES = 4


def words(n_nodes: int) -> int:
    return -(-n_nodes // 32)


def application_bytes(n_edges: int, n_vars: int, n_nodes: int) -> int:
    """Bytes of one operator application."""
    return 2 * ID_BYTES * n_edges + 2 * WORD_BYTES * n_vars * words(n_nodes)


def solve_bytes(operator_edges, n_vars: int, n_nodes: int, sweeps: int) -> int:
    """Bytes of a solve that applies every operator once per sweep.

    ``operator_edges``: the edge count of each distinct operator;
    ``n_vars``: the batch's variable rows (bucket x variables per instance).
    """
    per_sweep = sum(application_bytes(e, n_vars, n_nodes)
                    for e in operator_edges)
    return sweeps * per_sweep
