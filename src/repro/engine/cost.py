"""Cost-based fixpoint-engine selection (DESIGN.md 5.3 / 7.2 / 13).

Replaces the hard-coded ``--engine`` flag: given the database statistics and
the compiled SOI, estimate the per-sweep work of each batched engine in
:mod:`repro.core.dualsim` and pick the cheapest *feasible* one.  All engines
compute the same greatest fixpoint, so the choice is purely a performance
decision — which is what makes a closed-form model safe: a wrong pick is
slow, never incorrect.

Per-sweep model (V = SOI variables, n = nodes, M = distinct
(label, direction) operators, E = total edges touched by the SOI's
operators, W = devices in the mesh):

* ``dense``  — M boolean matmuls: ``V * n * n * M`` elements at matmul
  efficiency ``c_dense`` (MXU/BLAS amortization).  Infeasible when the
  stacked ``bool[M, n, n]`` adjacency exceeds ``DENSE_MAX_BYTES``.
* ``packed`` — the Pallas bitmm path: 32 bits per word cuts element count by
  32x, but on the CPU backend the kernel runs in interpret mode, which the
  model charges via ``c_packed_interpret`` (packed is an accelerator
  engine); per operator it also pays a kernel-launch overhead.
* ``packed_fused`` — the end-to-end bit-packed engine (DESIGN.md Sect. 9):
  same word count as ``packed`` at a lower per-word cost (the
  unpack → gather → AND chain between product and update is fused away, so
  chi never inflates 8x in HBM), and on CPU it lowers to the word-wise XLA
  path instead of kernel emulation — far cheaper than interpreted
  ``packed`` though still behind ``sparse`` on most CPU-sized graphs.
* ``sparse`` / ``jacobi_packed`` — the segmented-OR sweep (ISSUE 8),
  priced from BYTES MOVED: per sweep the engine streams ``E * (8 + V)``
  bytes of edge ids + gathered frontier messages, and ``3 * M * V * n/8``
  bytes of packed ``y`` words through the per-variable AND (write + read +
  chi fold), plus M per-operator dispatch overheads.  Always feasible on
  one device.  Under Gauss–Seidel every operator re-gathers the
  freshly-updated packed chi, so on a mesh it pays M packed-chi collectives
  (``M * V * n/8`` bytes) per sweep; ``jacobi_packed`` reads ONE bit-packed
  broadcast per sweep but pays a ~2x sweep-count inflation (Jacobi vs
  Gauss–Seidel, measured in ``configs/dualsim_base.py``).
* ``partitioned`` — jacobi_packed with destination-partitioned edge blocks:
  compute divides across the W shards, cross-shard traffic stays the one
  packed broadcast.  Needs a mesh (infeasible at W = 1, where it only adds
  block-padding overhead over jacobi_packed).

Communication terms enter only when ``n_devices > 1`` — on a single device
there is no collective traffic and the model must reduce to the PR-1
single-shard model exactly.

**Units and calibration (ISSUE 9).**  Every constant lives in a
:class:`CostModel`.  :data:`HAND_TUNED` carries the original folklore
constants in arbitrary units — one developer machine baked into numbers —
and remains the documented fallback.  When a measured
:class:`~repro.engine.machine.MachineSpec` is available (passed explicitly,
or discovered via :func:`repro.engine.machine.default_spec`),
:meth:`CostModel.from_spec` derives every constant from the machine's
probed ceilings instead, and the model's unit becomes *seconds*: each
engine's formula is its bytes-moved/ops count divided by the measured
throughput, plus measured per-call overheads.  No engine-selection path
reads a hand-tuned constant once a spec is present.

Feasibility is a HARD gate, not a preference: any engine whose *build*
path builds an ``[n, n]`` plane — dense itself, and the packed tier,
whose ``graph.packed_adjacency`` holds to the same budget —
is refused outright once ``n * n`` exceeds the byte budget
(``graph.DENSE_ADJ_MAX_BYTES``).  The gate depends only on graph shape,
never on calibration: no spec, however distorted, can un-refuse an engine
that cannot build its operands.
"""
from __future__ import annotations

import dataclasses

import jax

from repro.core.graph import DENSE_ADJ_MAX_BYTES, Graph
from repro.core.soi import CompiledSOI

from . import machine as machine_mod
from .machine import MachineSpec

ENGINES = (
    "dense", "packed", "packed_fused", "sparse", "jacobi_packed",
    "partitioned",
)

# hand-tuned model constants (relative cost per element, arbitrary units) —
# the documented fallback when no MachineSpec exists.  Kept as module-level
# names because DESIGN.md and the seed benches reference them; every model
# consumer goes through a CostModel instead of reading these directly.
C_DENSE = 1.0 / 8.0  # matmul elements amortize on MXU/BLAS
C_PACKED = 2.0  # per uint32 word, compiled Pallas
C_PACKED_INTERPRET = 256.0  # per word under interpret mode (CPU backend)
C_PACKED_FUSED = 1.0  # per word, fused kernel: no unpack/gather chain
C_PACKED_FUSED_CPU = 24.0  # per word, word-wise XLA lowering (no kernel)
PACKED_LAUNCH = 65536.0  # per-operator kernel launch overhead
C_SPARSE = 4.0  # per edge message (admission envelope only, see below)
C_APPLY = 0.5  # per chi element per operator (admission envelope only)
C_SEGOR_BYTE = 1.0  # per byte moved through the segmented-OR sweep
C_COMM = 8.0  # per byte of cross-shard collective traffic
JACOBI_SWEEP_FACTOR = 2.0  # Jacobi needs ~2x the sweeps of Gauss–Seidel
DENSE_MAX_BYTES = 2 << 30  # stacked bool[M, n, n] adjacency budget
PACKED_MAX_BYTES = 2 << 30
# any single [n, n] plane past this cannot be BUILT (graph.dense_adjacency
# raises MemoryError) — shared with the data layer so the model's hard gate
# and the constructor's guard can never disagree
DENSE_TIER_MAX_BYTES = DENSE_ADJ_MAX_BYTES

# resume-vs-cold model constants (DESIGN.md Sect. 8.3).  A cold rebuild
# pays SOI build + compile + operand upload + a fresh jit trace — the trace
# dominates by orders of magnitude on the serving path (the PR-1 cold/warm
# bench), which is why TRACE_COST towers over the per-sweep terms.
TRACE_COST = 5e7  # fresh jit trace + lowering of a plan's fixpoint
PATCH_COST_PER_EDGE = 16.0  # host-side rebuild of touched operators
RESUME_SWEEP_RATE = 50.0  # extra-sweep inflation per fractional delta
DEFAULT_SWEEPS = 8.0  # sweep prior when the plan never executed
RESUME_MAX_DELTA_FRACTION = 0.25  # past this, the old chi is mostly reseeded


def dense_tier_feasible(n: int) -> bool:
    """Whether any ``[n, n]`` operand plane may be materialized at all.

    Gates dense AND both packed engines: ``graph.packed_adjacency`` refuses
    the same ``n * n`` budget, so the packed tier is just as impossible past
    it even though its operand is 32x smaller.
    """
    return n * n <= DENSE_TIER_MAX_BYTES


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Every constant the engine/resume/admission models read, as one unit.

    Two provenances: :data:`HAND_TUNED` (arbitrary units, the seed's
    folklore constants) and :meth:`from_spec` (seconds, derived from a
    probed :class:`~repro.engine.machine.MachineSpec`).  The formulas in
    :func:`estimate_costs` etc. are provenance-agnostic — only the
    constants change — so the calibrated model reduces to the hand-tuned
    one structurally (same terms, same single-device reduction).
    """

    c_dense: float  # per dense boolean-matmul element
    c_packed: float  # per uint32 word, compiled kernel path
    c_packed_interpret: float  # per word, interpret-mode kernel (CPU)
    c_packed_fused: float  # per word, fused kernel path
    c_packed_fused_cpu: float  # per word, word-wise XLA lowering
    packed_launch: float  # per-operator launch overhead, packed engine
    fused_launch: float  # per-operator launch overhead, fused engine
    c_sparse: float  # per edge message (admission envelope)
    c_apply: float  # per chi element per operator (admission envelope)
    c_segor_byte: float  # per byte through the segmented-OR sweep
    c_comm: float  # per byte of cross-shard collective traffic
    c_dispatch: float  # per-operator per-sweep fixed overhead (XLA dispatch)
    trace_cost: float  # fresh jit trace + lowering of a plan's fixpoint
    patch_cost_per_edge: float  # host-side rebuild of touched operators
    source: str  # "hand-tuned" or the spec fingerprint
    unit: str  # "arb" (hand-tuned) or "s" (calibrated)

    @classmethod
    def from_spec(cls, spec: MachineSpec) -> "CostModel":
        """Derive every constant from a machine's probed ceilings (seconds).

        Derivations (DESIGN.md Sect. 13.2):

        * ``c_segor_byte = 1 / stream_bytes_per_s`` — the segmented-OR
          sweep is a pure streaming workload; its byte count divided by
          sustained bandwidth is its time.
        * ``c_dense = 1 / dense_elems_per_s`` — measured boolean-matmul
          element throughput (f32 MXU/BLAS path, as the engine runs it).
        * packed/fused per-word costs are reciprocals of the measured
          ``bitmm_apply`` word throughputs.  The probe measures the
          *shipping* lowering for the spec's backend (interpret-mode kernel
          on CPU, compiled kernel on accelerators) plus the word-wise XLA
          lowering; the constant for the lowering the spec's backend does
          not ship falls back to the XLA measurement — the closest probed
          proxy — and is only read under a backend/spec mismatch.
        * launches: the packed engine pays the measured kernel-path
          overhead per operator; the fused engine pays the same on
          accelerators but only an XLA dispatch on CPU (its words lowering
          launches no kernel).
        * ``c_sparse = 12 / stream`` (two int32 ids + a gathered message
          word share per edge) and ``c_apply = 0.375 / stream`` (three
          packed-plane passes = 3/8 byte per chi element per operator) keep
          the admission envelope's shape while pricing it in seconds;
          ``c_dispatch`` adds the measured per-op overhead the hand-tuned
          envelope ignored (zero there), which is what dominates
          millisecond-scale serving solves.
        * ``c_comm`` is the probed collective reciprocal; below 2 devices
          collectives are unprobed and fall back to ``4 / stream``
          (collectives move bytes a small factor slower than local streams).
        * ``trace_cost`` is the measured trace+compile of a representative
          packed fixpoint; ``patch_cost_per_edge = 64 / stream`` is the
          host-side operand-rebuild envelope (~64 bytes touched per edge).
        """
        stream = spec.stream_bytes_per_s
        cpu = spec.backend == "cpu"
        shipping = 1.0 / spec.packed_words_per_s
        xla = 1.0 / spec.packed_words_per_s_xla
        fused = 1.0 / spec.fused_words_per_s
        return cls(
            c_dense=1.0 / spec.dense_elems_per_s,
            c_packed=xla if cpu else shipping,
            c_packed_interpret=shipping if cpu else xla,
            c_packed_fused=fused,
            c_packed_fused_cpu=fused if cpu else xla,
            packed_launch=spec.kernel_launch_s,
            fused_launch=spec.dispatch_s if cpu else spec.kernel_launch_s,
            c_sparse=12.0 / stream,
            c_apply=0.375 / stream,
            c_segor_byte=1.0 / stream,
            c_comm=(
                1.0 / spec.collective_bytes_per_s
                if spec.collective_bytes_per_s
                else 4.0 / stream
            ),
            c_dispatch=spec.dispatch_s,
            trace_cost=spec.trace_s,
            patch_cost_per_edge=64.0 / stream,
            source=spec.fingerprint,
            unit="s",
        )


HAND_TUNED = CostModel(
    c_dense=C_DENSE,
    c_packed=C_PACKED,
    c_packed_interpret=C_PACKED_INTERPRET,
    c_packed_fused=C_PACKED_FUSED,
    c_packed_fused_cpu=C_PACKED_FUSED_CPU,
    packed_launch=PACKED_LAUNCH,
    fused_launch=PACKED_LAUNCH,
    c_sparse=C_SPARSE,
    c_apply=C_APPLY,
    c_segor_byte=C_SEGOR_BYTE,
    c_comm=C_COMM,
    c_dispatch=0.0,  # the arb-unit envelope never priced per-op overhead
    trace_cost=TRACE_COST,
    patch_cost_per_edge=PATCH_COST_PER_EDGE,
    source="hand-tuned",
    unit="arb",
)


def resolve_model(
    spec: MachineSpec | None = None,
    model: CostModel | None = None,
    backend: str | None = None,
) -> CostModel:
    """The model a cost query should price with.

    Precedence: an explicit ``model``; an explicit ``spec``; the machine's
    persisted spec (:func:`repro.engine.machine.default_spec`, governed by
    ``REPRO_MACHINE_SPEC``); the hand-tuned fallback.  This is THE spot the
    acceptance gate cares about: with a spec present, every constant the
    selection reads is spec-derived.
    """
    if model is not None:
        return model
    if spec is None:
        spec = machine_mod.default_spec(backend)
    return CostModel.from_spec(spec) if spec is not None else HAND_TUNED


def segor_sweep_cost(
    v: int, n: int, m: int, e: int, model: CostModel = HAND_TUNED
) -> float:
    """Bytes-moved model of one segmented-OR Gauss–Seidel sweep.

    ``E * (8 + V)`` bytes of edge ids (src + dst int32) and int8 frontier
    messages, plus ``3 * M * V * n/8`` bytes of packed ``y`` words (written
    by the segmented OR, read by the per-variable AND, folded into chi),
    plus M per-operator dispatch overheads (zero in the hand-tuned model).
    """
    return (
        model.c_segor_byte * (e * (8.0 + v) + 3.0 * m * v * (n / 8.0))
        + m * model.c_dispatch
    )


@dataclasses.dataclass(frozen=True)
class CostEstimate:
    """Chosen engine plus the full per-engine cost breakdown."""

    engine: str
    costs: dict[str, float]  # per-sweep model cost; float('inf') = infeasible
    reason: str


def _soi_stats(g: Graph, c: CompiledSOI) -> tuple[int, int, int]:
    """(V, M, E_total) for a compiled SOI against ``g``."""
    hist = g.label_histogram()
    e_total = int(sum(hist[la] for la, _ in c.mats))
    return c.n_vars, len(c.mats), e_total


def estimate_costs(
    g: Graph,
    c: CompiledSOI,
    *,
    backend: str | None = None,
    n_devices: int = 1,
    spec: MachineSpec | None = None,
    model: CostModel | None = None,
) -> dict[str, float]:
    """Per-sweep model cost of every engine (``inf`` when infeasible).

    ``n_devices`` is the mesh size the sharded engines would run on: it
    divides the partitioned engine's compute and switches the communication
    terms on (single-device runs have no collective traffic).  ``spec`` /
    ``model`` select the calibration (see :func:`resolve_model`); without
    either, the machine's persisted spec applies, then the hand-tuned
    fallback.
    """
    backend = backend or jax.default_backend()
    mdl = resolve_model(spec, model, backend)
    v, m, e = _soi_stats(g, c)
    n = g.n_nodes
    n_words = (n + 31) // 32
    multi = n_devices > 1

    costs: dict[str, float] = {}
    # hard gate first: past the [n, n] budget no dense-layout engine can
    # even BUILD its operands (m > 0 — an operator-free SOI builds nothing)
    tier_ok = m == 0 or dense_tier_feasible(n)
    dense_bytes = m * n * n
    costs["dense"] = (
        float("inf")
        if not tier_ok or dense_bytes > DENSE_MAX_BYTES
        else v * n * n * m * mdl.c_dense
    )
    packed_bytes = m * n * n_words * 4
    c_packed = mdl.c_packed_interpret if backend == "cpu" else mdl.c_packed
    costs["packed"] = (
        float("inf")
        if not tier_ok or packed_bytes > PACKED_MAX_BYTES
        else v * n * n_words * m * c_packed + m * mdl.packed_launch
    )
    c_fused = mdl.c_packed_fused_cpu if backend == "cpu" else mdl.c_packed_fused
    costs["packed_fused"] = (
        float("inf")
        if not tier_ok or packed_bytes > PACKED_MAX_BYTES
        else v * n * n_words * m * c_fused + m * mdl.fused_launch
    )
    sweep = segor_sweep_cost(v, n, m, e, mdl)
    # Gauss–Seidel re-gathers the packed chi per operator: M packed-chi
    # collectives (n/8 bytes each) per sweep
    sparse_comm = m * v * (n / 8.0) * mdl.c_comm if multi else 0.0
    costs["sparse"] = sweep + sparse_comm
    # Jacobi: ONE n/8-byte packed broadcast serves all M operators per sweep,
    # at ~2x the sweep count
    bcast_comm = v * (n / 8.0) * mdl.c_comm if multi else 0.0
    costs["jacobi_packed"] = JACOBI_SWEEP_FACTOR * (sweep + bcast_comm)
    costs["partitioned"] = (
        JACOBI_SWEEP_FACTOR * (sweep / n_devices + bcast_comm)
        if multi
        else float("inf")  # no mesh: pure overhead over jacobi_packed
    )
    return costs


def admission_estimate(
    g: Graph,
    q,
    *,
    spec: MachineSpec | None = None,
    model: CostModel | None = None,
) -> float:
    """Admission-control price of a parsed query (DESIGN.md Sect. 10.2).

    The serving loop must price a request *before* compiling anything —
    admission is the cheap path — so this estimates the always-feasible
    sparse engine's solve cost from the query text alone plus the graph's
    label histogram: ``DEFAULT_SWEEPS * (M*c_dispatch + V*E*c_sparse +
    V*n*M*c_apply)`` with V = distinct variables, M = 2x distinct labels
    (each label may induce a forward and a backward operator in the SOI),
    and E the total edges under the query's labels.  Labels absent from the
    graph contribute no edges (such queries prune to empty almost
    immediately, which the low price reflects).  Deliberately an
    *envelope*, not the per-engine model: all the gate needs is a monotone
    handle on "how much worse than the median template is this request".
    With a :class:`~repro.engine.machine.MachineSpec` the envelope is
    priced in seconds — per-op dispatch plus streamed bytes over measured
    bandwidth — and ``tests/test_serve.py`` asserts it stays within a
    bounded ratio of the measured per-batch solve time.
    """
    from repro.core import sparql

    mdl = resolve_model(spec, model)

    def walk(node):
        if isinstance(node, sparql.BGP):
            return list(node.triples)
        return walk(node.left) + walk(node.right)

    triples = walk(q)
    v = len(sparql.vars_of(q))
    labels = {t.p for t in triples}
    m = 2 * len(labels)
    hist = g.label_histogram()
    label_index = g.label_index() if g.label_names is not None else {}
    e = sum(int(hist[label_index[name]])
            for name in labels if name in label_index)
    return DEFAULT_SWEEPS * (
        m * mdl.c_dispatch
        + v * e * mdl.c_sparse
        + v * g.n_nodes * m * mdl.c_apply
    )


@dataclasses.dataclass(frozen=True)
class ResumeDecision:
    """Outcome of the resume-vs-cold classification for one stale plan."""

    resume: bool
    est_resume: float  # model cost of patch + warm-started sweeps
    est_cold: float  # model cost of rebuild + cold sweeps
    reason: str


def resume_decision(
    g: Graph,
    c: CompiledSOI,
    *,
    engine: str,
    delta_edges: int,
    last_sweeps: int | None = None,
    backend: str | None = None,
    n_devices: int = 1,
    spec: MachineSpec | None = None,
    model: CostModel | None = None,
) -> ResumeDecision:
    """Should a superseded (shape-stable) plan warm-resume or rebuild cold?

    Expected sweeps scale with the delta size: a warm start from the old
    fixpoint re-runs roughly ``1 + S_cold * min(1, rate * delta/E)`` sweeps
    (deletions propagate locally; insertions re-seed the destabilized
    closure), whereas a cold rebuild pays the full sweep count *plus* the
    trace.  Past :data:`RESUME_MAX_DELTA_FRACTION` of the edges changing,
    the old chi is mostly re-seeded anyway and the patch bookkeeping stops
    paying for itself — rebuild cold.  Either choice is correct (the
    resumed fixpoint is asserted identical); this is purely a latency call.
    """
    mdl = resolve_model(spec, model, backend)
    costs = estimate_costs(
        g, c, backend=backend, n_devices=n_devices, model=mdl
    )
    per_sweep = costs[engine]
    if per_sweep == float("inf"):
        # the plan exists and runs with this engine, whatever the model's
        # feasibility gate says (e.g. partitioned pinned on one device);
        # price its sweeps with the always-finite sparse estimate instead
        per_sweep = costs["sparse"]
    _, _, e = _soi_stats(g, c)
    frac = delta_edges / max(e, 1)
    s_cold = float(last_sweeps) if last_sweeps else DEFAULT_SWEEPS
    s_resume = 1.0 + s_cold * min(1.0, RESUME_SWEEP_RATE * frac)
    est_cold = mdl.trace_cost + s_cold * per_sweep
    est_resume = mdl.patch_cost_per_edge * delta_edges + s_resume * per_sweep
    resume = frac <= RESUME_MAX_DELTA_FRACTION and est_resume < est_cold
    reason = (
        f"{'resume' if resume else 'cold'}: delta {delta_edges}/{e} edges "
        f"({frac:.2%}), est resume {est_resume:.3g} vs cold {est_cold:.3g} "
        f"({engine}, ~{s_cold:.0f} sweeps cold / {s_resume:.1f} resumed)"
    )
    return ResumeDecision(
        resume=resume, est_resume=est_resume, est_cold=est_cold, reason=reason
    )


def choose_engine(
    g: Graph,
    c: CompiledSOI,
    *,
    backend: str | None = None,
    n_devices: int = 1,
    allow: tuple[str, ...] = ENGINES,
    spec: MachineSpec | None = None,
    model: CostModel | None = None,
) -> CostEstimate:
    """Pick the cheapest feasible engine for this (SOI, graph, mesh) triple."""
    mdl = resolve_model(spec, model, backend)
    costs = estimate_costs(
        g, c, backend=backend, n_devices=n_devices, model=mdl
    )
    feasible = {k: v for k, v in costs.items() if k in allow and v != float("inf")}
    if not feasible:  # sparse is always feasible unless excluded by `allow`
        raise ValueError(f"no feasible engine among {allow}")
    best = min(feasible, key=feasible.get)
    v, m, e = _soi_stats(g, c)
    reason = (
        f"{best}: cost {feasible[best]:.3g}{mdl.unit} over "
        f"{{V={v}, n={g.n_nodes}, M={m}, E={e}, W={n_devices}}} "
        f"[{mdl.source}] (candidates: "
        + ", ".join(f"{k}={costs[k]:.3g}" for k in costs)
        + ")"
    )
    return CostEstimate(engine=best, costs=costs, reason=reason)
