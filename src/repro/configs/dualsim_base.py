"""ArchSpec for the paper's own workloads: dual-simulation query processing
over LUBM-scale and DBpedia-scale graph databases (dry-run + roofline).

Cells (all ``kind="dualsim"``):

* ``*_q_sparse``   — one query (paper-faithful SOI sweep), sparse engine.
* ``*_batch16``    — 16 constant-parameterized instances of one query
  template solved together (vmap over the Eq.-13 init), the serving regime.
* ``block_dense``  — dense/MXU engine on a 16k-node partition block (the
  bit-matrix regime the paper's Sect. 3.2 engineering targets).
* ``q_partitioned`` — beyond-paper optimized engine (EXPERIMENTS §Perf):
  destination-partitioned (vertex-cut) edge blocks + one bit-packed
  frontier broadcast per sweep — 38x lower collective term than q_sparse.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import dualsim
from repro.distributed import shard as sh
from .base import Cell, sds


@dataclasses.dataclass(frozen=True)
class DualsimScale:
    n_nodes: int
    edges_per_mat: tuple[int, ...]  # one entry per (label, dir) operator
    n_vars: int
    n_ineqs: int
    n_copies: int = 0


class DualsimArch:
    family = "dualsim"

    def __init__(self, arch_id: str, scale: DualsimScale, batch16_nodes: int,
                 dense_block: int = 16384):
        self.id = arch_id
        self.scale = scale
        self.batch16_nodes = batch16_nodes
        self.dense_block = dense_block

    def cells(self) -> dict[str, Cell]:
        return {
            "q_sparse": Cell("q_sparse", "dualsim"),
            "batch16_sparse": Cell("batch16_sparse", "dualsim",
                                   extras=dict(n_queries=16)),
            "block_dense": Cell("block_dense", "dualsim"),
            # beyond-paper optimized engine (EXPERIMENTS §Perf): vertex-cut
            # destination-partitioned edges + bit-packed frontier broadcast.
            "q_partitioned": Cell("q_partitioned", "dualsim",
                                  extras=dict(n_blocks=256)),
        }

    def skip_reason(self, cell_name: str) -> str | None:
        return None

    # ------------------------------------------------------------------ #
    def _abstract_operands(self, n_nodes: int, dense: bool,
                           q: int = 1) -> dualsim.Operands:
        """q > 1 = disjoint-union batching: q constant-parameterized copies
        of the query template solved as one SOI (n_vars and the per-operator
        inequality counts scale by q; edges are shared)."""
        s = self.scale
        n_mats = len(s.edges_per_mat)
        per_mat = max(1, s.n_ineqs // n_mats)
        kw = dict(
            init=sds((q * s.n_vars, n_nodes), jnp.bool_),
            mat_rhs=tuple(sds((q * per_mat,), jnp.int32) for _ in range(n_mats)),
            mat_table=tuple(
                sds((q * s.n_vars, 1), jnp.int32) for _ in range(n_mats)
            ),
            copy_rhs=sds((q * s.n_copies,), jnp.int32),
            var_copy=sds((q * s.n_vars, max(s.n_copies, 1)), jnp.int32),
        )
        if dense:
            kw["adj_dense"] = sds((n_mats, n_nodes, n_nodes), jnp.bool_)
        else:
            kw["edge_src"] = tuple(sds((e,), jnp.int32) for e in s.edges_per_mat)
            kw["edge_dst"] = tuple(sds((e,), jnp.int32) for e in s.edges_per_mat)
        return dualsim.Operands(**kw)

    def abstract_state(self, cell: Cell) -> Any:
        if cell.name == "block_dense":
            return self._abstract_operands(self.dense_block, dense=True)
        if cell.name == "batch16_sparse":
            return self._abstract_operands(
                self.batch16_nodes, dense=False, q=cell.extras["n_queries"]
            )
        if cell.name == "q_partitioned":
            s = self.scale
            w = cell.extras["n_blocks"]
            n = -(-s.n_nodes // 8192) * 8192  # pad for packed sharding
            ops = self._abstract_operands(n, dense=False)
            eb = [int(e / w * 1.2) for e in s.edges_per_mat]  # 20% imbalance
            return dataclasses.replace(
                ops,
                edge_src=None, edge_dst=None,
                edge_src_b=tuple(sds((w, e), jnp.int32) for e in eb),
                edge_dst_b=tuple(sds((w, e), jnp.int32) for e in eb),
            )
        return self._abstract_operands(self.scale.n_nodes, dense=False)

    def abstract_inputs(self, cell: Cell) -> dict:
        return {}

    def step(self, cell: Cell) -> Callable:
        if cell.name == "block_dense":

            def run_dense(state, batch):
                return dualsim.solve_dense(
                    state, dtype=jnp.bfloat16, max_sweeps=30,
                    chi_spec=P(None, "model"),
                )

            return run_dense
        # single query: chi columns over every axis; batched queries:
        # query-variable dim over 'data' (query parallelism), columns over
        # 'model'.
        batched = cell.name == "batch16_sparse"
        chi_spec = P("data", "model") if batched else P(None, ("data", "model"))
        if cell.name == "q_partitioned":

            def run_part(state, batch):
                return dualsim.solve_partitioned(
                    state, max_sweeps=60, chi_spec=chi_spec
                )

            return run_part

        def run_sparse(state, batch):
            # the abstract state carries flat edge lists only
            return dualsim.solve_sparse(
                state, max_sweeps=30, chi_spec=chi_spec, impl="words"
            )

        return run_sparse

    # ------------------------------------------------------------------ #
    def state_shardings(self, mesh: Mesh, cell: Cell) -> Any:
        dense = cell.name == "block_dense"
        specs = (
            sh.dualsim_dense_specs(mesh) if dense else sh.dualsim_sparse_specs(mesh)
        )
        state = self.abstract_state(cell)

        batched = cell.name == "batch16_sparse"

        def one(path, leaf):
            key = str(path[0].name)
            spec = specs.get(key, P())
            if key == "init" and batched:
                spec = P("data", "model")  # query-parallel over 'data'
            if key in ("edge_src_b", "edge_dst_b"):
                spec = P(("data", "model"), None)  # block dim = chi shards
            return NamedSharding(mesh, sh.safe_spec(tuple(leaf.shape), spec, mesh))

        return jax.tree_util.tree_map_with_path(one, state)

    def input_shardings(self, mesh: Mesh, cell: Cell) -> Any:
        return {}

    def model_flops(self, cell: Cell) -> float:
        """Useful ops: per sweep each edge feeds V OR-AND ops per direction;
        assume the paper's observed ~5 sweep average (Sect. 5.3)."""
        s = self.scale
        sweeps = 5.0
        if cell.name == "block_dense":
            e = sum(self.scale.edges_per_mat) * (
                self.dense_block / self.scale.n_nodes
            )
            return 2.0 * sweeps * s.n_vars * e
        q = cell.extras.get("n_queries", 1)
        return 2.0 * sweeps * q * s.n_vars * sum(s.edges_per_mat)

    def hlo_trip_factor(self, cell: Cell) -> float:
        # fixpoint while body counted once; ~5 GS sweeps typical; the
        # Jacobi-style partitioned engine inflates ~2x (measured).
        return 10.0 if cell.name == "q_partitioned" else 5.0

    def trip_schedule(self, cell: Cell) -> list[float]:
        return [self.hlo_trip_factor(cell)]

    def reduced(self):
        return None
