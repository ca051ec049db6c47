"""Compiled, constant-rebindable execution plans (DESIGN.md 5.2).

A :class:`CompiledPlan` is everything about a query that does not depend on
the constants: the (batched) SOI built from the template, its compilation
against one graph's label table, the engine-specific device operands with
static shapes, and a jitted fixpoint.  The per-request constants enter as an
*input* — a ``bool[K, n]`` stack of one-hot rows scattered into the Eq.-13
init inside the traced function — so rebinding a template to new constants
re-runs the same trace: zero SOI recompilation, zero jit retraces.

Slot handling: the template SOI marks constants as ``$slot{k}`` (see
:mod:`repro.engine.template`).  For compilation we strip those markers so
:func:`repro.core.soi.compile_soi` gives slot rows the full structural
(Eq.-13 summary) init of a variable; binding then ANDs in the one-hot row,
which reproduces exactly what ``compile_soi`` does for a literal constant
(singleton intersected with the summaries; all-zero when the constant is not
in the database).  One slot may map to *several* internal variables — the
SOI builder gives constants a private singleton variable per BGP — so the
scatter index list carries one entry per (instance, slot variable).

A build is the profiler span ``plan.build``; an execute is ``plan.inputs``
(constant rows and warm start up to the device), ``plan.fixpoint`` (the
jitted solve, waited for), ``plan.copy_back`` (chi and the sweep count
back to the host) and ``plan.memo`` (chi packed into the warm-start memo).
On the device the solve runs under ``jax.named_scope("fixpoint")``.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import bitops, dualsim, soi as soi_mod
from repro.core.graph import Graph, GraphDelta

from . import cost as cost_mod
from .batcher import BatchLayout, batch_layout
from .cache import BoundedDict
from .template import QueryTemplate, slot_index


def _shard_partitioned_operands(
    ops: dualsim.Operands, mesh: jax.sharding.Mesh, chi_spec
) -> dualsim.Operands:
    """Place partitioned operands on the mesh: edge blocks [W, Eb] shard
    block-major along the mesh (block w lives where chi block w lives, so
    every segment reduction is device-local), init shards like chi.  A
    device_put onto the sharding an array already has is a no-op, so cached
    edge blocks are not re-copied across plans."""
    block = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(mesh.axis_names, None)
    )
    put = lambda xs: tuple(jax.device_put(x, block) for x in xs)
    # init_packed stays replicated: its word axis (n/32) need not divide the
    # mesh (device_put rejects uneven sharding), it is read once at loop
    # start, and the loop state constraint distributes chi from there
    replicated = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec()
    )
    return dataclasses.replace(
        ops,
        init=jax.device_put(ops.init, chi_spec),
        init_packed=jax.device_put(ops.init_packed, replicated),
        edge_src_b=put(ops.edge_src_b),
        edge_dst_b=put(ops.edge_dst_b),
    )


@dataclasses.dataclass
class PlanMetrics:
    """Observable counters for the zero-recompile acceptance test."""

    traces: int = 0  # times the jitted fixpoint was (re)traced
    executions: int = 0  # times it was called
    build_seconds: float = 0.0  # host-side SOI build + compile + operands
    patches: int = 0  # shape-stable graph deltas adopted in place
    warm_resumes: int = 0  # executions warm-started from a previous chi


class CompiledPlan:
    """One (template, graph, bucket) entry of the plan cache."""

    def __init__(
        self,
        template: QueryTemplate,
        db: Graph,
        *,
        engine: str = "auto",
        batch: int = 1,
        node_index: dict[str, int] | None = None,
        backend: str | None = None,
        adj_cache: dict | None = None,
        mesh: jax.sharding.Mesh | None = None,
        n_blocks: int | None = None,
        incremental: bool = True,
        spec=None,
    ):
        """Compile ``template`` against ``db`` at batch size ``batch``.

        ``spec`` is the :class:`repro.engine.machine.MachineSpec` the
        ``engine="auto"`` selection prices with (``None``: the persisted
        machine spec, then the hand-tuned fallback — DESIGN.md Sect. 13).
        """
        with TraceAnnotation(
            "plan.build", template=template.span_key, bucket=batch
        ) as span:
            self._build(
                template, db, engine, batch, node_index, backend, adj_cache,
                mesh, n_blocks, incremental, spec,
            )
            span.set_metadata(engine=self.engine)

    def _build(
        self, template, db, engine, batch, node_index, backend, adj_cache,
        mesh, n_blocks, incremental, spec,
    ) -> None:
        t0 = time.perf_counter()
        backend = backend or jax.default_backend()
        self.template = template
        self.batch = batch
        self.n_nodes = db.n_nodes
        self.mesh = mesh
        self.incremental = incremental
        n_devices = int(mesh.devices.size) if mesh is not None else 1
        self.n_blocks = n_blocks if n_blocks is not None else max(n_devices, 1)
        # chi is [V, n]: shard the node axis across every mesh axis; the
        # V axis (variables) stays replicated — it is tiny and irregular
        self.chi_spec = (
            jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec(None, mesh.axis_names)
            )
            if mesh is not None
            else None
        )
        if node_index is None:
            node_index = db.node_index() if db.node_names is not None else {}
        self._node_index = node_index

        base = soi_mod.build_soi(template.query)
        self.base_soi = base
        self.layout: BatchLayout = batch_layout([base] * batch)
        union = self.layout.soi

        # strip slot markers so compile_soi inits slot rows like variables
        stripped = dataclasses.replace(
            union,
            is_const=[
                None if (c is not None and slot_index(c) is not None) else c
                for c in union.is_const
            ],
        )
        self._stripped = stripped  # kept for shape-stable recompiles (patch)
        self.csoi = soi_mod.compile_soi(stripped, db, node_index=node_index)

        # (instance, slot variable) scatter order; row j of const_rows lands
        # in init row scatter_ids[j] and carries constants[slot_of[j]]
        per_part = [
            (vid, slot_index(c))
            for vid, c in enumerate(base.is_const)
            if c is not None and slot_index(c) is not None
        ]
        self._scatter_ids = np.asarray(
            [
                self.layout.offsets[i] + vid
                for i in range(batch)
                for vid, _ in per_part
            ],
            dtype=np.int32,
        )
        self._scatter_slot = [k for _ in range(batch) for _, k in per_part]
        self._scatter_instance = [
            i for i in range(batch) for _ in per_part
        ]

        # the lowering follows the platform the operands live on; ``backend``
        # only prices the engine choice, so an override can never put a CPU
        # lowering (interpret mode, the word-wise XLA path) on an accelerator
        on_cpu = jax.default_backend() == "cpu"
        self.cost: cost_mod.CostEstimate | None = None
        if engine == "auto":
            self.cost = cost_mod.choose_engine(
                db, self.csoi, backend=backend, n_devices=n_devices, spec=spec
            )
            engine = self.cost.engine
        self.engine = engine

        if engine == "dense":
            self.operands = dualsim.make_dense_operands(self.csoi, db, adj_cache)
            solver = dualsim.solve_dense
        elif engine == "packed":
            self.operands = dualsim.make_packed_operands(self.csoi, db, adj_cache)
            # compiled Pallas kernel on accelerators; interpret only on CPU
            # (the cost model prices the two regimes very differently)
            solver = functools.partial(dualsim.solve_packed, interpret=on_cpu)
        elif engine == "packed_fused":
            self.operands = dualsim.make_packed_operands(self.csoi, db, adj_cache)
            # fused Pallas kernel on accelerators; on CPU the word-wise XLA
            # lowering (kernel emulation would cost ~9x — DESIGN.md Sect. 9)
            solver = functools.partial(
                dualsim.solve_packed_fused,
                impl=("words" if on_cpu else "kernel"),
            )
        elif engine in ("sparse", "jacobi_packed"):
            # both sparse modes run the segmented-OR sweep over bit-packed
            # chi, lowered like packed_fused's: blocked Pallas kernel on
            # accelerators, the word-wise XLA path on CPU
            self.operands = dualsim.make_sparse_operands(self.csoi, db, adj_cache)
            solver = functools.partial(
                dualsim.solve_sparse,
                mode=("jacobi_packed" if engine == "jacobi_packed" else "gs"),
                impl=("words" if on_cpu else "kernel"),
                interpret=on_cpu,
                chi_spec=self.chi_spec,
            )
        elif engine == "partitioned":
            self.operands = dualsim.make_partitioned_operands(
                self.csoi, db, self.n_blocks, adj_cache
            )
            if mesh is not None:
                if self.n_blocks % n_devices:
                    raise ValueError(
                        f"n_blocks={self.n_blocks} does not divide over the "
                        f"{n_devices}-device mesh; the partitioned operands "
                        "would stay on one device"
                    )
                self.operands = _shard_partitioned_operands(
                    self.operands, mesh, self.chi_spec
                )
            solver = functools.partial(
                dualsim.solve_partitioned, chi_spec=self.chi_spec
            )
        else:
            raise ValueError(f"unknown engine {engine!r}")

        self._adj_cache = adj_cache
        # incremental maintenance state (DESIGN.md Sect. 8): the last solved
        # chi per constant tuple (bit-packed, 8x smaller than bool), and
        # re-seeded warm starts staged by patch_graph for the next
        # execution of the same constants
        self._chi_memo: BoundedDict = BoundedDict(capacity=4)
        self._warm: dict = {}
        self.last_sweeps: int | None = None
        # engines whose while_loop state is bit-packed take constants and
        # warm starts as uint32 words; bool chi never touches the device.
        # Since ISSUE 8 that is every edge-list engine — sparse included.
        self._packed_chi = engine in ("packed_fused", "sparse",
                                      "jacobi_packed", "partitioned")

        self.metrics = PlanMetrics()
        scatter = jnp.asarray(self._scatter_ids)
        n_nodes = self.n_nodes

        def _run(ops: dualsim.Operands, const_rows: jax.Array, chi0: jax.Array):
            # executes at trace time only: the counter observes retraces.
            # chi0 is the warm-start upper bound; the cold path passes the
            # init itself, making the AND below an identity — one trace
            # serves both regimes.
            self.metrics.traces += 1
            init = ops.init_packed if self._packed_chi else ops.init
            if const_rows.shape[0]:
                if const_rows.shape[-1] != init.shape[-1]:
                    # partitioned layout: init is block-padded past n_nodes
                    # (zero pad words/columns are dead either way)
                    const_rows = jnp.pad(
                        const_rows,
                        ((0, 0), (0, init.shape[-1] - const_rows.shape[-1])),
                    )
                init = init.at[scatter].set(init[scatter] & const_rows)
            init = init & chi0
            if self._packed_chi:
                ops = dataclasses.replace(ops, init_packed=init)
            else:
                ops = dataclasses.replace(ops, init=init)
            with jax.named_scope("fixpoint"):
                chi, sweeps = solver(ops)
            return chi[:, :n_nodes], sweeps

        # the jitted fixpoint: ``fixpoint(*fixpoint_inputs(bindings))``
        self.fixpoint = jax.jit(_run)
        self.metrics.build_seconds = time.perf_counter() - t0

    # ------------------------------------------------------------------ #
    @property
    def n_slot_rows(self) -> int:
        """Init rows the per-request constants scatter into."""
        return len(self._scatter_ids)

    def const_rows(self, bindings: Sequence[tuple[str, ...]]) -> np.ndarray:
        """One-hot ``bool[K, n]`` rows for a batch of constant tuples.

        ``bindings[i]`` is instance i's slot->constant assignment; a constant
        missing from the database yields an all-zero row (forces that
        instance's component empty, same as ``compile_soi``).
        """
        if len(bindings) != self.batch:
            raise ValueError(
                f"plan is compiled for batch={self.batch}, "
                f"got {len(bindings)} binding tuples"
            )
        rows = np.zeros((self.n_slot_rows, self.n_nodes), dtype=bool)
        for j, (i, k) in enumerate(
            zip(self._scatter_instance, self._scatter_slot)
        ):
            if k >= len(bindings[i]):
                raise ValueError(
                    f"instance {i} binds {len(bindings[i])} constants, "
                    f"template needs {self.template.n_slots}"
                )
            node = self._node_index.get(bindings[i][k])
            # the index may be a live dict shared with a mutating source;
            # a name minted after this plan's snapshot has an id past our
            # node axis and (correctly) binds to the empty set here
            if node is not None and node < self.n_nodes:
                rows[j, node] = True
        return rows

    def fixpoint_inputs(
        self, bindings: Sequence[tuple[str, ...]]
    ) -> tuple[dualsim.Operands, jax.Array, jax.Array]:
        """``(operands, const_rows, chi0)`` of a cold solve of ``bindings``.

        What :meth:`execute` passes to :attr:`fixpoint` when no warm start
        is staged; lowering ``fixpoint`` on them shows the whole compiled
        fixpoint, kernels included, without running it.
        """
        rows = self.const_rows(bindings)
        if self._packed_chi:
            # packed engines take everything as uint32 words: constants,
            # init, warm starts — 8x less host->device traffic per request
            rows = bitops.pack_np(rows)
        # cold: chi0 is the init itself, so the AND with it is an identity
        chi0 = self.operands.init_packed if self._packed_chi else self.operands.init
        return self.operands, jnp.asarray(rows), chi0

    def execute(
        self, bindings: Sequence[tuple[str, ...]]
    ) -> tuple[np.ndarray, int]:
        """Solve the fixpoint for one batch of constant tuples.

        Returns ``(chi, sweeps)`` with ``chi`` of shape
        ``[batch * n_vars, n_nodes]``; use ``self.layout.chi_slice(i)`` to
        demux instance i.  When :meth:`patch_graph` staged a re-seeded warm
        start for exactly these constants, the solve resumes from it
        instead of the Eq.-13 init (same fixpoint, far fewer sweeps).
        """
        with TraceAnnotation("plan.inputs") as span:
            ops, rows, chi0 = self.fixpoint_inputs(bindings)
            h2d = rows.nbytes
            key = tuple(bindings)
            warm = self._warm.pop(key, None)
            if warm is not None:
                width = chi0.shape[-1]
                if warm.shape[-1] != width:  # partitioned block padding
                    warm = np.pad(warm, ((0, 0), (0, width - warm.shape[-1])))
                chi0 = jnp.asarray(warm)
                h2d += warm.nbytes
                self.metrics.warm_resumes += 1
            span.set_metadata(h2d_bytes=h2d)
        with TraceAnnotation("plan.fixpoint"):
            # waits where np.asarray below would: the copy is timed apart
            chi, sweeps = jax.block_until_ready(self.fixpoint(ops, rows, chi0))
        self.metrics.executions += 1
        with TraceAnnotation(
            "plan.copy_back", d2h_bytes=chi.nbytes + sweeps.nbytes
        ) as span:
            chi, sweeps = np.asarray(chi), int(sweeps)
            span.set_metadata(sweeps=sweeps)
        self.last_sweeps = sweeps
        if self.incremental:
            # bit-packed: 8x smaller than the bool chi it warm-starts, and
            # for the packed-chi engines it feeds straight back into the
            # solver with no unpack round trip (DESIGN.md Sect. 9)
            with TraceAnnotation("plan.memo") as span:
                packed = bitops.pack_np(chi)
                self._chi_memo[key] = packed
                span.set_metadata(bytes=packed.nbytes)
        return chi, sweeps

    def patch_graph(
        self,
        db: Graph,
        delta: GraphDelta,
        node_index: dict[str, int] | None = None,
        adj_cache: dict | None = None,
    ) -> None:
        """Adopt a shape-stable mutated snapshot without a rebuild.

        The template SOI, batch layout, and jitted fixpoint all survive;
        only the graph-dependent pieces move: the compiled SOI's Eq.-13
        init is recomputed, touched adjacency operators are patched in
        place (:func:`repro.core.dualsim.patch_operands` — untouched
        operators and therefore operand *shapes* carry over, so the
        existing trace keeps serving), and every memoized fixpoint becomes
        a staged warm start with the delta's destabilized rows re-seeded
        to ⊤ (DESIGN.md Sect. 8.2).
        """
        if not delta.shape_stable or db.n_nodes != self.n_nodes:
            raise ValueError("patch_graph needs a shape-stable delta")
        if node_index is not None:
            self._node_index = node_index
        old_mats = self.csoi.mats
        self.csoi = soi_mod.compile_soi(
            self._stripped, db, node_index=self._node_index
        )
        if self.csoi.mats != old_mats:  # dictionary change slipped through
            raise ValueError("operator list moved; delta is not resumable")
        cache = adj_cache if adj_cache is not None else self._adj_cache
        self.operands = dualsim.patch_operands(
            self.operands,
            self.csoi,
            db,
            delta.touched_labels(),
            n_blocks=self.n_blocks,
            adj_cache=cache,
        )
        if self.engine == "partitioned" and self.mesh is not None:
            self.operands = _shard_partitioned_operands(
                self.operands, self.mesh, self.chi_spec
            )
        grow = dualsim.destabilized_rows(self.csoi, delta.inserted_labels())
        self._warm = {}
        if self._packed_chi:
            # stay packed: destabilized rows re-seed to the all-ones mask
            # (trailing pad bits zero), the memo words go back verbatim
            ones = bitops.ones_mask(self.n_nodes)
            for key, packed in self._chi_memo.items():
                chi0 = packed.copy()
                chi0[grow] = ones
                self._warm[key] = chi0
        else:
            for key, packed in self._chi_memo.items():
                chi0 = bitops.unpack_np(packed, self.n_nodes)
                chi0[grow] = True
                self._warm[key] = chi0
        # superseded fixpoints are warm seeds now, not current results
        self._chi_memo.clear()
        self.metrics.patches += 1
