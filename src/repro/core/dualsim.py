"""Dual-simulation fixpoint engines (paper Sect. 3).

Four engines compute the largest solution of a compiled SOI:

* ``solve_dense``  — batched Jacobi sweep over dense boolean adjacency, one
  matmul per (label, direction) operator per sweep.  This is the MXU path:
  ``Y = chi @ A`` in ``dtype`` (bf16 on TPU) followed by ``> 0``.
* ``solve_packed`` — same sweep over bit-packed ``uint32`` adjacency via the
  Pallas ``bitmm`` kernel (64x less HBM traffic than bf16 dense); chi is
  boolean between kernel calls (the pre-ISSUE-5 baseline the fused engine
  is benchmarked against).
* ``solve_packed_fused`` — the paper's Sect.-3.2 representation end to end:
  chi stays bit-packed ``uint32 [V, nw]`` through the whole
  ``lax.while_loop`` and one fused ``bitmm_apply`` launch per operator does
  product + AND-combine + changed detection on packed words (DESIGN.md
  Sect. 9).
* ``solve_sparse`` — edge-list engine: the boolean product is a segmented
  OR over edges, i.e. message passing in the OR-AND semiring.  Since
  ISSUE 8 *both* modes carry bit-packed chi through the whole while_loop:
  the segmented-OR primitive (``kernels/segsum``) emits ``y`` already
  packed ``uint32 [V, nw]``, so no bool plane and no per-sweep
  ``bitops.pack`` exist anywhere in the loop.  ``mode="gs"`` applies
  operators sequentially (paper-faithful ordering); ``mode="jacobi_packed"``
  reads every operator's frontier bits out of ONE replicated copy of the
  packed words per sweep.
* ``solve_partitioned`` — destination-partitioned (vertex-cut) edge blocks
  over a device mesh: block-local segmented ORs emit block-local packed
  words (the block size is 32-aligned so local words concatenate into the
  global word order); the ONLY cross-shard traffic per sweep is replicating
  the n/8-byte packed words chi already lives in (DESIGN.md Sect. 7 / 9 /
  12).
* ``solve_worklist`` — the paper's own sequential strategy (Sect. 3.2 steps
  1–2 with the Sect. 3.3 heuristics); numpy, used for Table-2 parity and
  iteration-count studies.

All batched engines iterate their sweep through the single
:func:`_sweep_fixpoint` driver — they differ only in the sweep body.

All batched engines implement the same monotone operator

    chi[lhs] &= chi[rhs] ×b M        (edge inequalities, Eq. 11)
    chi[lhs] &= chi[rhs]             (copy inequalities, Eq. 15)

iterated to the (unique) greatest fixpoint; order of application does not
change the fixpoint (Knaster–Tarski on the finite powerset lattice), which is
exactly the degree of freedom the paper exploits — we spend it on batching
instead of worklist heuristics (DESIGN.md Sect. 2).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from . import bitops
from .graph import Graph
from .soi import BWD, FWD, CompiledSOI, SOI, build_soi, compile_soi

# --------------------------------------------------------------------- #
# operand construction (numpy -> pytrees)
# --------------------------------------------------------------------- #


def _pad_table(groups: list[list[int]], pad: int) -> np.ndarray:
    k = max((len(g) for g in groups), default=0)
    k = max(k, 1)
    out = np.full((len(groups), k), pad, dtype=np.int32)
    for i, g in enumerate(groups):
        out[i, : len(g)] = g
    return out


def _per_mat_tables(c: CompiledSOI) -> tuple[tuple, tuple]:
    """Per-operator inequality tables.

    For operator m: ``mat_rhs[m]`` lists the RHS variable of each inequality
    using m; ``mat_table[m]`` is the per-variable padded index list into
    those inequalities (pad = I_m, pointing at an appended all-ones row) so
    multiple inequalities on the same LHS AND-combine with gathers only.
    """
    n_mats = len(c.mats)
    rhs_by_mat: list[list[int]] = [[] for _ in range(n_mats)]
    var_by_mat: list[list[list[int]]] = [
        [[] for _ in range(c.n_vars)] for _ in range(n_mats)
    ]
    for l, r, m in zip(c.ineq_lhs, c.ineq_rhs, c.ineq_mat):
        var_by_mat[m][l].append(len(rhs_by_mat[m]))
        rhs_by_mat[m].append(r)
    mat_rhs = tuple(jnp.asarray(r, jnp.int32) for r in rhs_by_mat)
    mat_table = tuple(
        jnp.asarray(_pad_table(v, pad=len(rhs_by_mat[m])), jnp.int32)
        for m, v in enumerate(var_by_mat)
    )
    return mat_rhs, mat_table


def _mat_lhs_flags(c: CompiledSOI) -> tuple:
    """Per-operator [V, V] inequality flag matrices for the fused kernel.

    ``flags[m][l, r] = 1`` iff the SOI holds ``chi[l] <= chi[r] ×b M_m``;
    ``bitmm_apply`` turns the AND-combine into a tiny masked OR-reduce
    (``chi[l] &= ~OR_{r:F[l,r]} ~y[r]``) so no gather tables enter the
    kernel.  Semantically identical to ``mat_rhs``/``mat_table`` (duplicate
    inequalities collapse idempotently under AND).
    """
    flags = [np.zeros((c.n_vars, c.n_vars), np.uint32) for _ in c.mats]
    for l, r, m in zip(c.ineq_lhs, c.ineq_rhs, c.ineq_mat):
        flags[m][l, r] = 1
    return tuple(jnp.asarray(f) for f in flags)


def _copy_tables(c: CompiledSOI) -> tuple[jax.Array, jax.Array]:
    by_copy: list[list[int]] = [[] for _ in range(c.n_vars)]
    for i, l in enumerate(c.copy_lhs):
        by_copy[l].append(i)
    return (
        jnp.asarray(c.copy_rhs, jnp.int32),
        jnp.asarray(_pad_table(by_copy, pad=len(c.copy_lhs)), jnp.int32),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Operands:
    """Device operands shared by the batched engines.

    Adjacency comes in an engine-specific layout: dense ``bool[M, n, n]``,
    packed ``uint32[M, n, nw]``, or per-operator edge lists (sparse engine).
    Exactly one layout is populated.
    """

    init: jax.Array  # bool [V, n]
    mat_rhs: tuple  # per mat: int32 [I_m]
    mat_table: tuple  # per mat: int32 [V, K_m] (padded with I_m)
    copy_rhs: jax.Array  # int32 [C]
    var_copy: jax.Array  # int32 [V, Kc]  (padded with C)
    # packed-chi extras (ISSUE 5): host-packed init and per-mat [V, V]
    # inequality flag matrices; optional so hand-built / abstract Operands
    # stay valid (the packed engines fall back to packing init on device,
    # and only the fused engine requires the flags)
    init_packed: jax.Array | None = None  # uint32 [V, nw]
    mat_lhs_flags: tuple | None = None  # per mat: uint32 [V, V]
    adj_dense: jax.Array | None = None  # bool [M, n, n]
    adj_packed: jax.Array | None = None  # uint32 [M, n, nw]
    edge_src: tuple | None = None  # per-mat int32 [E_m] source nodes
    edge_dst: tuple | None = None  # per-mat int32 [E_m] destination nodes
    # destination-partitioned layout (mode="partitioned"): block w only
    # holds edges whose dst lies in chi block w; dst ids are block-local
    # (pad rows use dst = n_local, dropped by the segment reduce).
    edge_src_b: tuple | None = None  # per-mat int32 [W, Eb] global src
    edge_dst_b: tuple | None = None  # per-mat int32 [W, Eb] local dst
    # blocked segmented-OR layout (ISSUE 8): edges sorted and blocked by
    # destination word window for the Pallas segor kernel.  Built alongside
    # the flat edge lists in make_sparse_operands; pad rows carry the
    # sentinel destination n_pad (never a bit), see prepare_segor.
    seg_src_b: tuple | None = None  # per-mat int32 [G_m, BE] source nodes
    seg_dst_b: tuple | None = None  # per-mat int32 [G_m, BE] absolute dst
    seg_win: tuple | None = None  # per-mat int32 [G_m] dst-word window


def _base_operands(c: CompiledSOI) -> dict:
    mat_rhs, mat_table = _per_mat_tables(c)
    copy_rhs, var_copy = _copy_tables(c)
    return dict(
        init=jnp.asarray(c.init),
        # packed once on the host: the packed-chi engines start their
        # while_loop from this without ever packing on device
        init_packed=jnp.asarray(bitops.pack_np(c.init)),
        mat_rhs=mat_rhs,
        mat_table=mat_table,
        mat_lhs_flags=_mat_lhs_flags(c),
        copy_rhs=copy_rhs,
        var_copy=var_copy,
    )


def _cached_adj(adj_cache: dict | None, key, g: Graph, build):
    """Adjacency depends only on (engine, mats, graph) — never on the SOI's
    variables — so plan caches share it across templates and batch buckets.
    Entries store the graph they were built from and only hit on the *same*
    graph object: sharing one cache dict across graphs can never return
    another graph's adjacency (it just misses and rebuilds)."""
    if adj_cache is not None:
        try:
            hit_g, adj = adj_cache[key]
        except KeyError:
            pass
        else:
            if hit_g is g:
                return adj
    adj = build()
    if adj_cache is not None:
        adj_cache[key] = (g, adj)
    return adj


def make_dense_operands(
    c: CompiledSOI, g: Graph, adj_cache: dict | None = None
) -> Operands:
    def build():
        adj = np.stack(
            [g.dense_adjacency(a, backward=(d == BWD)) for (a, d) in c.mats]
        ) if c.mats else np.zeros((0, g.n_nodes, g.n_nodes), dtype=bool)
        return jnp.asarray(adj)

    adj = _cached_adj(adj_cache, ("dense", tuple(c.mats)), g, build)
    return Operands(adj_dense=adj, **_base_operands(c))


def make_packed_operands(
    c: CompiledSOI, g: Graph, adj_cache: dict | None = None
) -> Operands:
    def build():
        adj = np.stack(
            [g.packed_adjacency(a, backward=(d == BWD)) for (a, d) in c.mats]
        ) if c.mats else np.zeros((0, g.n_nodes, bitops.packed_width(g.n_nodes)), np.uint32)
        return jnp.asarray(adj)

    adj = _cached_adj(adj_cache, ("packed", tuple(c.mats)), g, build)
    return Operands(adj_packed=adj, **_base_operands(c))


# Per-operator edge lists round up to this capacity multiple; pad rows use
# the out-of-range destination id ``n`` and are dropped by the segment
# reduce.  Rounding keeps operand shapes stable under small insert/delete
# deltas, so a patched plan re-runs its existing trace instead of retracing
# (DESIGN.md Sect. 8).
EDGE_PAD = 64


def _padded_edge_list(
    s: np.ndarray, t: np.ndarray, n: int, min_cap: int = 0
) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) int32 arrays padded to an ``EDGE_PAD`` multiple >= min_cap."""
    e = len(s)
    cap = max(-(-e // EDGE_PAD) * EDGE_PAD if e else 0, min_cap)
    if cap == e:
        return np.asarray(s, np.int32), np.asarray(t, np.int32)
    ps = np.zeros(cap, np.int32)
    pt = np.full(cap, n, np.int32)  # pad dst = n -> dropped by segment reduce
    ps[:e], pt[:e] = s, t
    return ps, pt


def _oriented_edges(g: Graph, a: int, d: int) -> tuple[np.ndarray, np.ndarray]:
    e = g.edges_for_label(a)
    return (e[:, 0], e[:, 1]) if d == FWD else (e[:, 1], e[:, 0])


def _segor_mat(
    s: np.ndarray, t: np.ndarray, n: int, min_g: int = 0
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Blocked segmented-OR layout for one operator's RAW edge list.

    Feeds the Pallas segor kernel: edges sorted by destination and split
    into blocks that each touch one destination-word window.  Pad rows
    gather source 0 but carry the sentinel destination ``n_pad``, which can
    never turn on a bit (:func:`repro.kernels.segsum.kernel.prepare_segor`)
    — crucially NOT the flat layout's pad id ``n``, which would alias bit
    ``n`` whenever ``n`` lies inside a live window.
    """
    from repro.kernels.segsum import kernel as segsum_kernel

    idx_b, seg_b, win, _ = segsum_kernel.prepare_segor(t, n, min_g=min_g)
    src_b = (
        np.asarray(s, np.int32)[idx_b]
        if len(s)
        else np.zeros(idx_b.shape, np.int32)
    )
    return jnp.asarray(src_b), jnp.asarray(seg_b), jnp.asarray(win)


def make_sparse_operands(
    c: CompiledSOI, g: Graph, adj_cache: dict | None = None
) -> Operands:
    def build():
        srcs, dsts, sbs, dbs, wbs = [], [], [], [], []
        for a, d in c.mats:
            s, t = _oriented_edges(g, a, d)
            ps, pt = _padded_edge_list(s, t, g.n_nodes)
            srcs.append(jnp.asarray(ps, jnp.int32))
            dsts.append(jnp.asarray(pt, jnp.int32))
            sb, db, wb = _segor_mat(s, t, g.n_nodes)
            sbs.append(sb)
            dbs.append(db)
            wbs.append(wb)
        return tuple(srcs), tuple(dsts), tuple(sbs), tuple(dbs), tuple(wbs)

    src, dst, sb, db, wb = _cached_adj(
        adj_cache, ("sparse", tuple(c.mats)), g, build
    )
    return Operands(
        edge_src=src, edge_dst=dst,
        seg_src_b=sb, seg_dst_b=db, seg_win=wb,
        **_base_operands(c),
    )


def _partitioned_mat(
    s: np.ndarray, t: np.ndarray, n_blocks: int, n_local: int, min_eb: int = 1
) -> tuple[np.ndarray, np.ndarray]:
    """Destination-partitioned (block, local-dst) layout for one operator.

    Blocks pad to a common edge count ``>= min_eb`` (pad rows use the
    out-of-range local id ``n_local`` and are dropped by the segment
    reduce); ``min_eb`` lets an operand patch keep the superseded shape so
    the plan's trace stays valid.
    """
    blk = t // n_local
    order = np.argsort(blk, kind="stable")
    s, t, blk = s[order], t[order], blk[order]
    counts = np.bincount(blk, minlength=n_blocks)
    eb = max(int(counts.max()) if counts.size else 1, 1, min_eb)
    src_b = np.zeros((n_blocks, eb), np.int32)
    dst_b = np.full((n_blocks, eb), n_local, np.int32)  # pad -> dropped
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    for w in range(n_blocks):
        k = counts[w]
        src_b[w, :k] = s[starts[w] : starts[w] + k]
        dst_b[w, :k] = t[starts[w] : starts[w] + k] - w * n_local
    return src_b, dst_b


def padded_node_count(n: int, n_blocks: int) -> int:
    """Smallest node count splitting into ``n_blocks`` uniform blocks of
    whole 32-bit words (block size is a word multiple since ISSUE 8, so the
    blocks' packed local words concatenate directly into the global word
    order; pad columns are dead and sliced off after the solve)."""
    n_local = -(-max(-(-n // n_blocks), 1) // bitops.WORD) * bitops.WORD
    return n_local * n_blocks


def make_partitioned_operands(
    c: CompiledSOI, g: Graph, n_blocks: int, adj_cache: dict | None = None
) -> Operands:
    """Destination-partitioned (vertex-cut) edge layout: the host-side graph
    partitioner of the ``partitioned`` engine.

    The node axis is padded up to a multiple of ``n_blocks``
    (:func:`padded_node_count`) so callers never have to align the graph
    themselves — pad columns start all-False in ``init``, receive no edges,
    and stay dead through every monotone sweep; slice ``chi[:, :g.n_nodes]``
    after solving.  Blocks are padded to a common edge count (pad rows use
    the out-of-range local id ``n_local`` and are dropped by the segment
    reduce).  Like the other layouts, the edge blocks depend only on
    (mats, graph, n_blocks) and are shared across plans via ``adj_cache``.
    """
    n = g.n_nodes
    n_pad = padded_node_count(n, n_blocks)
    n_local = n_pad // n_blocks

    def build():
        srcs_b, dsts_b = [], []
        for a, d in c.mats:
            src_b, dst_b = _partitioned_mat(
                *_oriented_edges(g, a, d), n_blocks, n_local
            )
            srcs_b.append(jnp.asarray(src_b))
            dsts_b.append(jnp.asarray(dst_b))
        return tuple(srcs_b), tuple(dsts_b)

    src_b, dst_b = _cached_adj(
        adj_cache, ("partitioned", tuple(c.mats), n_blocks), g, build
    )
    base = _base_operands(c)
    if n_pad != n:
        init_np = np.pad(np.asarray(c.init, bool), ((0, 0), (0, n_pad - n)))
        base["init"] = jnp.asarray(init_np)
        base["init_packed"] = jnp.asarray(bitops.pack_np(init_np))
    return Operands(edge_src_b=src_b, edge_dst_b=dst_b, **base)


# --------------------------------------------------------------------- #
# incremental maintenance: operand patching + destabilization closure
# --------------------------------------------------------------------- #
def patch_operands(
    ops: Operands,
    c_new: CompiledSOI,
    g: Graph,
    touched_labels: set[int],
    *,
    n_blocks: int = 4,
    adj_cache: dict | None = None,
) -> Operands:
    """Patch device operands in place of a full rebuild (DESIGN.md Sect. 8).

    Precondition: the delta from the operands' snapshot to ``g`` is
    *shape-stable* (no new nodes or labels) and the SOI structure is
    unchanged, so ``c_new.mats`` matches the old operator list and all
    inequality tables stay valid.  Only operators whose label appears in
    ``touched_labels`` are rebuilt against ``g``; untouched adjacency rows
    and edge lists carry over from ``ops`` unchanged (their content is
    identical by construction).  Sparse / partitioned edge lists keep their
    superseded padded capacity whenever the new edge count still fits, so
    patched operand *shapes* — and therefore the plan's jit trace — stay
    stable.  The Eq.-13 ``init`` always refreshes (summaries shift with the
    delta).  The shared ``adj_cache`` entry is re-keyed to ``g`` so sibling
    plans (other batch buckets) pick the patched arrays up as a hit.
    """
    n = g.n_nodes
    touched = [
        m for m, (la, _) in enumerate(c_new.mats) if la in touched_labels
    ]
    init_np = np.asarray(c_new.init, bool)
    # the shared adjacency cache keys on graph identity, so a sibling plan
    # that already patched against this same snapshot is a hit and the
    # patch closure below never runs twice per (layout, mats, graph)
    kw: dict = {}
    if ops.adj_dense is not None:

        def patch_dense():
            adj = ops.adj_dense
            if touched:
                rows = np.stack(
                    [
                        g.dense_adjacency(c_new.mats[m][0],
                                          backward=(c_new.mats[m][1] == BWD))
                        for m in touched
                    ]
                )
                adj = adj.at[jnp.asarray(touched)].set(jnp.asarray(rows))
            return adj

        kw["adj_dense"] = _cached_adj(
            adj_cache, ("dense", tuple(c_new.mats)), g, patch_dense
        )
    elif ops.adj_packed is not None:

        def patch_packed():
            adj = ops.adj_packed
            if touched:
                rows = np.stack(
                    [
                        g.packed_adjacency(c_new.mats[m][0],
                                           backward=(c_new.mats[m][1] == BWD))
                        for m in touched
                    ]
                )
                adj = adj.at[jnp.asarray(touched)].set(jnp.asarray(rows))
            return adj

        kw["adj_packed"] = _cached_adj(
            adj_cache, ("packed", tuple(c_new.mats)), g, patch_packed
        )
    elif ops.edge_src_b is not None:
        n_pad = padded_node_count(n, n_blocks)
        n_local = n_pad // n_blocks
        if n_pad != n:
            init_np = np.pad(init_np, ((0, 0), (0, n_pad - n)))

        def patch_blocks():
            src_b, dst_b = list(ops.edge_src_b), list(ops.edge_dst_b)
            for m in touched:
                a, d = c_new.mats[m]
                sb, db = _partitioned_mat(
                    *_oriented_edges(g, a, d), n_blocks, n_local,
                    min_eb=int(ops.edge_src_b[m].shape[1]),
                )
                src_b[m], dst_b[m] = jnp.asarray(sb), jnp.asarray(db)
            return tuple(src_b), tuple(dst_b)

        kw["edge_src_b"], kw["edge_dst_b"] = _cached_adj(
            adj_cache, ("partitioned", tuple(c_new.mats), n_blocks), g,
            patch_blocks,
        )
    else:

        def patch_edges():
            src, dst = list(ops.edge_src), list(ops.edge_dst)
            sbs = list(ops.seg_src_b) if ops.seg_src_b is not None else None
            dbs = list(ops.seg_dst_b) if ops.seg_dst_b is not None else None
            wbs = list(ops.seg_win) if ops.seg_win is not None else None
            for m in touched:
                a, d = c_new.mats[m]
                s, t = _oriented_edges(g, a, d)
                ps, pt = _padded_edge_list(
                    s, t, n, min_cap=int(ops.edge_src[m].shape[0])
                )
                src[m], dst[m] = jnp.asarray(ps), jnp.asarray(pt)
                if sbs is not None:
                    # the blocked layout keeps its superseded block count
                    # whenever the churned edges still fit, mirroring the
                    # flat lists' EDGE_PAD capacity rule (zero retraces)
                    sbs[m], dbs[m], wbs[m] = _segor_mat(
                        s, t, n, min_g=int(ops.seg_src_b[m].shape[0])
                    )
            seg = (
                (tuple(sbs), tuple(dbs), tuple(wbs))
                if sbs is not None
                else (None, None, None)
            )
            return (tuple(src), tuple(dst)) + seg

        (
            kw["edge_src"], kw["edge_dst"],
            kw["seg_src_b"], kw["seg_dst_b"], kw["seg_win"],
        ) = _cached_adj(
            adj_cache, ("sparse", tuple(c_new.mats)), g, patch_edges
        )
    return dataclasses.replace(
        ops,
        init=jnp.asarray(init_np),
        init_packed=jnp.asarray(bitops.pack_np(init_np)),
        **kw,
    )


def destabilized_rows(c: CompiledSOI, inserted_labels: set[int]) -> np.ndarray:
    """SOI rows whose greatest solution can *grow* under an edge insertion.

    Returns a ``bool[n_vars]`` mask.  Seed: the LHS of every inequality
    whose operator carries an inserted label (their bound ``chi[rhs] x_b M``
    gains columns — the Sect.-3.3 "destabilize dependents" trigger).  The
    seed then closes transitively over the dependency direction *lhs
    depends on rhs* (edge and copy inequalities alike): a row constrained
    by a grown row can grow too.  Rows OUTSIDE the closure provably keep
    ``gfp_new[row] <= gfp_old[row]`` — their whole constraint cone uses
    untouched (or only shrunken) operators — which is the soundness
    argument for re-seeding exactly the closure to ⊤ before a warm resume
    (DESIGN.md Sect. 8.2).
    """
    touched_mats = {
        m for m, (la, _) in enumerate(c.mats) if la in inserted_labels
    }
    grow = np.zeros(c.n_vars, dtype=bool)
    if not touched_mats:
        return grow
    for lhs, m in zip(c.ineq_lhs, c.ineq_mat):
        if int(m) in touched_mats:
            grow[lhs] = True
    deps = list(zip(c.ineq_lhs, c.ineq_rhs)) + list(
        zip(c.copy_lhs, c.copy_rhs)
    )
    changed = True
    while changed:
        changed = False
        for lhs, rhs in deps:
            if grow[rhs] and not grow[lhs]:
                grow[lhs] = True
                changed = True
    return grow


# --------------------------------------------------------------------- #
# batched sweep engines (per-operator Gauss–Seidel within a sweep)
# --------------------------------------------------------------------- #


def _wsc(x: jax.Array, spec) -> jax.Array:
    """Optional sharding constraint (no-op when spec is None / no mesh)."""
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def _replicated(spec):
    """The fully-replicated counterpart of a chi sharding spec."""
    if spec is None:
        return None
    if isinstance(spec, jax.sharding.NamedSharding):
        return jax.sharding.NamedSharding(
            spec.mesh, jax.sharding.PartitionSpec()
        )
    return jax.sharding.PartitionSpec()


def _per_var_mask(y: jax.Array, m: int, ops: Operands) -> jax.Array:
    """``AND_{(l,r) in ineqs_m} y[r]`` per LHS variable l (gather-only).

    Returns bool [V, n]; rows with no operator-m inequality are all-True
    (the padded table entry points at an appended all-ones row).
    """
    n = y.shape[-1]
    vals = y[ops.mat_rhs[m]]  # [I_m, n]
    vals = jnp.concatenate([vals, jnp.ones((1, n), vals.dtype)])
    return jnp.all(vals[ops.mat_table[m]], axis=1)  # [V, n]


def _apply_mat(chi: jax.Array, y: jax.Array, m: int, ops: Operands) -> jax.Array:
    """chi[l] &= y[rhs_l] for every inequality of operator m."""
    return jnp.logical_and(chi, _per_var_mask(y, m, ops))


def _apply_copies(chi: jax.Array, ops: Operands) -> jax.Array:
    """chi[l] &= chi[r] for every copy inequality l <= r (OPTIONAL's renamed
    occurrences); its device ops run under ``jax.named_scope("copies")``."""
    if ops.copy_rhs.shape[0] == 0:
        return chi
    with jax.named_scope("copies"):
        n = chi.shape[-1]
        cvals = chi[ops.copy_rhs]
        cvals = jnp.concatenate([cvals, jnp.ones((1, n), cvals.dtype)])
        per_var = jnp.all(cvals[ops.var_copy], axis=1)
        return jnp.logical_and(chi, per_var)


# numpy scalar on purpose: a jnp constant here would initialize the JAX
# backend at import time (breaking XLA_FLAGS device-count forcing)
_ALL_ONES = np.uint32(0xFFFFFFFF)


def _apply_copies_packed(chi_p: jax.Array, ops: Operands) -> jax.Array:
    """Copy inequalities on bit-packed chi: word-wise gathers and ANDs.

    The appended pad row is all-ones *including* trailing pad bits — AND is
    its identity, and chi's own pad bits are already zero, so no pad bit can
    ever turn on (the invariant the packed convergence test relies on).  Its
    device ops run under ``jax.named_scope("copies")``.
    """
    if ops.copy_rhs.shape[0] == 0:
        return chi_p
    with jax.named_scope("copies"):
        nw = chi_p.shape[-1]
        cvals = chi_p[ops.copy_rhs]  # [C, nw]
        cvals = jnp.concatenate([cvals, jnp.full((1, nw), _ALL_ONES)])
        per_var = jax.lax.reduce(
            cvals[ops.var_copy], _ALL_ONES, jax.lax.bitwise_and, (1,)
        )  # [V, nw]
        return jnp.bitwise_and(chi_p, per_var)


def _sweep_fixpoint(
    sweep: Callable[[jax.Array], jax.Array],
    init: jax.Array,
    max_sweeps: int | None,
    chi_spec=None,
) -> tuple[jax.Array, jax.Array]:
    """The one fixpoint driver every batched engine runs on.

    Iterates ``sweep`` (any monotone shrink of chi) from ``init`` until chi
    stops changing (or ``max_sweeps``); engines differ only in the sweep
    body they plug in.  Knaster–Tarski on the finite powerset lattice makes
    this safe: every sweep order reaches the same greatest fixpoint.
    Returns (chi, n_sweeps).
    """

    def cond(state):
        _, _, changed = state
        return changed

    def body(state):
        chi, it, _ = state
        new = sweep(chi)
        changed = jnp.any(new != chi)
        if max_sweeps is not None:
            changed = jnp.logical_and(changed, it + 1 < max_sweeps)
        return new, it + 1, changed

    state = (_wsc(init, chi_spec), jnp.int32(0), jnp.bool_(True))
    chi, it, _ = jax.lax.while_loop(cond, body, state)
    return chi, it


def _replicated_frontier(chi_p: jax.Array, chi_spec=None) -> jax.Array:
    """Replicate the packed chi words across the mesh: ONE n/8-byte
    broadcast serves every operator of a Jacobi sweep (vs M chi-sized
    gathers under Gauss–Seidel).  chi already *is* packed words now, so on
    a single device (``chi_spec is None``) this is the identity — the old
    per-sweep pack→broadcast→unpack round trip is gone entirely."""
    if chi_spec is None:
        return chi_p
    return _wsc(chi_p, _replicated(chi_spec))


def _edge_bits(frontier_p: jax.Array, src: jax.Array) -> jax.Array:
    """Per-edge source bits gathered straight out of packed frontier words.

    ``int8 [V, E]``: bit ``src[e] % 32`` of word ``src[e] // 32`` — the
    gathered table is 32x smaller than a boolean frontier.  Its device ops
    run under ``jax.named_scope("edge_bits")``.
    """
    with jax.named_scope("edge_bits"):
        word = frontier_p[:, src // 32]  # [V, E] uint32
        return ((word >> (src % 32).astype(jnp.uint32)) & 1).astype(jnp.int8)


def _warm_init(ops: Operands, chi0: jax.Array | None) -> jax.Array:
    """The sweep start point: Eq.-13 init, optionally warm-started.

    ``chi0`` (a previous fixpoint, re-seeded by the caller where an
    insertion may grow the solution — :func:`destabilized_rows`) is ANDed
    into the init: every sweep only shrinks chi, so starting anywhere above
    the greatest fixpoint converges to exactly that fixpoint, in far fewer
    sweeps when ``chi0`` is already close (DESIGN.md Sect. 8.2).
    """
    if chi0 is None:
        return ops.init
    return jnp.logical_and(ops.init, chi0)


def _packed_start(ops: Operands, chi0: jax.Array | None) -> jax.Array:
    """:func:`_warm_init` for the packed-chi engines — all on uint32 words.

    ``chi0`` may be bool ``[V, n]`` or already-packed ``uint32 [V, nw]``;
    the packed form is what the plan cache's chi memo feeds back, with no
    unpack round trip anywhere between memo and while_loop.
    """
    init_p = ops.init_packed
    if init_p is None:  # hand-built Operands: pack once, outside the loop
        init_p = bitops.pack(ops.init)
    if chi0 is None:
        return init_p
    if not jnp.issubdtype(jnp.asarray(chi0).dtype, jnp.unsignedinteger):
        chi0 = bitops.pack(chi0)
    return jnp.bitwise_and(init_p, chi0)


def _per_var_mask_packed(y_p: jax.Array, m: int, ops: Operands) -> jax.Array:
    """:func:`_per_var_mask` on bit-packed ``y``: word-wise gathers + ANDs.

    ``uint32 [V, nw]``; the appended pad row is all-ones (AND identity) and
    chi's own pad bits are already zero, so no pad bit can ever turn on —
    the same argument as :func:`_apply_copies_packed`.
    """
    nw = y_p.shape[-1]
    vals = y_p[ops.mat_rhs[m]]  # [I_m, nw]
    vals = jnp.concatenate([vals, jnp.full((1, nw), _ALL_ONES)])
    return jax.lax.reduce(
        vals[ops.mat_table[m]], _ALL_ONES, jax.lax.bitwise_and, (1,)
    )  # [V, nw]


def _packed_edge_fixpoint(
    propagate: Callable[[jax.Array, int], jax.Array],
    ops: Operands,
    max_sweeps: int | None,
    chi_spec=None,
    chi0: jax.Array | None = None,
    *,
    jacobi: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Shared driver of the packed-state edge-list engines (sparse-gs,
    jacobi_packed, partitioned).  ``propagate(chi_words, m)`` is operator
    m's segmented OR and returns ``y`` already bit-packed ``uint32 [V,
    nw]`` (the ISSUE-8 primitive) — no bool plane and no ``bitops.pack``
    exist anywhere in the while body, which the ``tools.reprolint.dynamic``
    audit enforces.

    Jacobi: ONE replicate of the packed chi words serves every operator,
    all per-operator shrink masks AND together (order-free) and fold into
    chi word-wise.  Gauss–Seidel (``jacobi=False``): operators apply
    sequentially, each reading the freshly-shrunk chi — the identical
    per-operator order the bool-era GS ran, so sweep counts carry over
    verbatim (DESIGN.md Sect. 12).  Convergence is the word-level ``new !=
    chi`` of :func:`_sweep_fixpoint`.  Returns (bool chi, sweeps), unpacked
    once after the fixpoint.
    """
    n = ops.init.shape[-1]
    n_mats = len(ops.mat_rhs)

    if jacobi:

        def sweep(chi_p: jax.Array) -> jax.Array:
            frontier_p = _replicated_frontier(chi_p, chi_spec)
            shrink = None
            for m in range(n_mats):
                y_p = _wsc(propagate(frontier_p, m), chi_spec)
                pv = _per_var_mask_packed(y_p, m, ops)
                shrink = pv if shrink is None else jnp.bitwise_and(shrink, pv)
            if shrink is not None:
                chi_p = _wsc(jnp.bitwise_and(chi_p, shrink), chi_spec)
            return _apply_copies_packed(chi_p, ops)

    else:

        def sweep(chi_p: jax.Array) -> jax.Array:
            for m in range(n_mats):
                y_p = _wsc(propagate(chi_p, m), chi_spec)
                chi_p = _wsc(
                    jnp.bitwise_and(chi_p, _per_var_mask_packed(y_p, m, ops)),
                    chi_spec,
                )
            return _apply_copies_packed(chi_p, ops)

    chi_p, it = _sweep_fixpoint(
        sweep, _packed_start(ops, chi0), max_sweeps, chi_spec
    )
    return bitops.unpack(chi_p, n), it


def _fixpoint(
    propagate_m: Callable[[jax.Array, int], jax.Array],
    ops: Operands,
    max_sweeps: int | None,
    chi_spec=None,
    chi0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Gauss–Seidel sweeps: one boolean product ``y = chi x_b M_m`` per
    operator m (all variables batched), AND-updates applied immediately —
    one y tensor live at a time."""
    n_mats = len(ops.mat_rhs)

    def sweep(chi: jax.Array) -> jax.Array:
        for m in range(n_mats):
            y = propagate_m(chi, m)  # [V, n] bool
            chi = _wsc(_apply_mat(chi, y, m, ops), chi_spec)
        return _apply_copies(chi, ops)

    return _sweep_fixpoint(sweep, _warm_init(ops, chi0), max_sweeps, chi_spec)


@functools.partial(jax.jit, static_argnames=("dtype", "max_sweeps", "chi_spec"))
def solve_dense(
    ops: Operands, *, dtype=jnp.float32, max_sweeps: int | None = None,
    chi_spec=None, chi0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Sweeps with dense boolean matmuls on the MXU (OR-AND via (+,x), >0)."""

    def propagate_m(chi: jax.Array, m: int) -> jax.Array:
        x = chi.astype(dtype)
        y = x @ ops.adj_dense[m].astype(dtype)
        return y > 0

    return _fixpoint(propagate_m, ops, max_sweeps, chi_spec, chi0)


@functools.partial(
    jax.jit, static_argnames=("max_sweeps", "interpret", "chi_spec")
)
def solve_packed(
    ops: Operands, *, max_sweeps: int | None = None,
    interpret: bool | None = None, chi_spec=None,
    chi0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Sweeps over bit-packed adjacency via the Pallas bitmm kernel.

    chi itself stays boolean between kernel calls — this is the baseline
    the fused engine (:func:`solve_packed_fused`) is measured against.
    ``interpret=None`` auto-detects the backend (interpret only on CPU), so
    direct callers no longer silently interpret the kernel on accelerators.
    """
    from repro.kernels.bitmm import ops as bitmm_ops

    def propagate_m(chi: jax.Array, m: int) -> jax.Array:
        return bitmm_ops.bitmm(chi, ops.adj_packed[m], interpret=interpret)

    return _fixpoint(propagate_m, ops, max_sweeps, chi_spec, chi0)


@functools.partial(jax.jit, static_argnames=("max_sweeps", "impl", "chi_spec"))
def solve_packed_fused(
    ops: Operands, *, max_sweeps: int | None = None, impl: str | None = None,
    chi_spec=None, chi0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Bit-packed chi end to end: one fused launch per operator application.

    The ``lax.while_loop`` carries ``uint32 [V, nw]`` — 32x less state than
    the boolean engines — and every sweep is ``M`` ``bitmm_apply`` calls
    (packed product + AND-combine + changed words in one grid) plus the
    word-wise copy step.  Convergence comes from the kernels' own changed
    flags; chi is unpacked exactly once, after the fixpoint (DESIGN.md
    Sect. 9).

    ``impl``: ``"kernel"`` (compiled Pallas), ``"interpret"`` (Pallas in
    interpret mode), ``"words"`` (pure-jnp word-wise lowering), or ``None``
    for backend auto-detection — kernel on accelerators, words on CPU,
    where XLA beats kernel emulation ~9x.
    """
    from repro.kernels.bitmm import ops as bitmm_ops

    if impl is None:
        impl = "words" if jax.default_backend() == "cpu" else "kernel"
    n = ops.init.shape[-1]
    n_mats = len(ops.mat_rhs)

    def apply_m(chi_p: jax.Array, m: int) -> tuple[jax.Array, jax.Array]:
        if impl == "words":
            from repro.kernels.bitmm import ref as bitmm_ref

            return bitmm_ref.bitmm_apply_words(
                chi_p, ops.adj_packed[m], ops.mat_lhs_flags[m]
            )
        return bitmm_ops.bitmm_apply(
            chi_p, ops.adj_packed[m], ops.mat_lhs_flags[m],
            interpret=(impl == "interpret"),
        )

    def cond(state):
        return state[2]

    def body(state):
        chi_p, it, _ = state
        changed = jnp.uint32(0)
        for m in range(n_mats):
            chi_p, ch = apply_m(chi_p, m)
            chi_p = _wsc(chi_p, chi_spec)
            changed = jnp.bitwise_or(changed, jnp.uint32(ch))
        before = chi_p
        chi_p = _apply_copies_packed(chi_p, ops)
        moved = jnp.logical_or(changed != 0, jnp.any(chi_p != before))
        if max_sweeps is not None:
            moved = jnp.logical_and(moved, it + 1 < max_sweeps)
        return chi_p, it + 1, moved

    state = (
        _wsc(_packed_start(ops, chi0), chi_spec),
        jnp.int32(0),
        jnp.bool_(True),
    )
    chi_p, it, _ = jax.lax.while_loop(cond, body, state)
    return bitops.unpack(chi_p, n), it


@functools.partial(
    jax.jit,
    static_argnames=("max_sweeps", "chi_spec", "mode", "impl", "interpret"),
)
def solve_sparse(
    ops: Operands, *, max_sweeps: int | None = None, chi_spec=None,
    mode: str = "gs", impl: str | None = None,
    interpret: bool | None = None, chi0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Edge-list engine: segmented-OR message passing over bit-packed chi.

    One segmented OR per (label, direction) operator — the GNN scatter
    regime; int32-safe at billion-edge scale because segments are
    per-operator node ids.  Since ISSUE 8 chi lives bit-packed ``uint32
    [V, nw]`` through the whole while_loop in BOTH modes: frontier bits
    come straight out of the packed words (:func:`_edge_bits`) and ``y``
    comes back already packed from the segmented-OR primitive, so no
    ``[V, n]`` bool plane exists anywhere in the loop.

    ``mode``:
    * ``"gs"`` (paper-faithful ordering): operators applied sequentially
      within a sweep, each reading the freshly-shrunk chi — fewest sweeps,
      identical per-operator order (and therefore sweep counts) to the
      bool-era engine, but O(M) chi-sized collectives per sweep on a mesh.
    * ``"jacobi_packed"`` (beyond-paper, §Perf): all operators read
      frontier bits out of ONE replicated copy of the packed words per
      sweep — 32x fewer collective bytes.  Same fixpoint either way
      (monotone operator on a finite lattice).

    ``impl`` picks the segmented-OR lowering: ``"words"`` (word-wise XLA,
    the CPU path), ``"kernel"`` (the blocked Pallas kernel over the
    ``seg_*`` operand layout; ``interpret`` auto-enables on CPU only), or
    ``None`` for backend auto-detection — kernel on accelerators, words on
    CPU.  ``"kernel"`` on operands without the blocked layout raises: a
    caller that builds operands by hand asks for ``"words"`` explicitly.
    """
    from repro.kernels.segsum import kernel as segsum_kernel
    from repro.kernels.segsum import ref as segsum_ref

    n = ops.init.shape[-1]
    if impl is None:
        impl = "words" if jax.default_backend() == "cpu" else "kernel"
    if impl not in ("words", "kernel"):
        raise ValueError(f"unknown sparse impl {impl!r}")
    # trace-ok: seg_win's None-ness is pytree *structure*, static under jit
    if impl == "kernel" and ops.seg_win is None:
        raise ValueError(
            "impl='kernel' needs the blocked segmented-OR layout (seg_*); "
            "build operands with make_sparse_operands or pass impl='words'"
        )
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    def propagate(frontier_p: jax.Array, m: int) -> jax.Array:
        # device ops: the gather under "edge_bits", the OR under "segor"
        if impl == "kernel":
            bits = _edge_bits(frontier_p, ops.seg_src_b[m])  # [V, G, BE]
            with jax.named_scope("segor"):
                return segsum_kernel.segor_blocks(
                    bits.transpose(1, 2, 0), ops.seg_dst_b[m], ops.seg_win[m],
                    num_segments=n, interpret=interpret,
                )
        msgs = _edge_bits(frontier_p, ops.edge_src[m])  # int8 [V, E_m]
        with jax.named_scope("segor"):
            return segsum_ref.segor_words(msgs, ops.edge_dst[m], n)

    if mode not in ("gs", "jacobi_packed"):
        raise ValueError(f"unknown sparse mode {mode!r}")
    return _packed_edge_fixpoint(
        propagate, ops, max_sweeps, chi_spec, chi0, jacobi=(mode != "gs")
    )


@functools.partial(jax.jit, static_argnames=("max_sweeps", "chi_spec"))
def solve_partitioned(
    ops: Operands, *, max_sweeps: int | None = None, chi_spec=None,
    chi0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Vertex-cut partitioned engine (beyond-paper, EXPERIMENTS §Perf).

    Edges are pre-partitioned by destination chi-block
    (:func:`make_partitioned_operands`), so every segmented OR is
    block-local and emits block-local *packed words* directly (the block
    size is a 32-multiple by :func:`padded_node_count`, so block words
    concatenate into the global word order with a reshape); chi lives
    bit-packed through the while_loop, and the ONLY cross-shard traffic per
    sweep is replicating the n/8-byte packed words chi already is (instead
    of M chi-sized all-gathers plus scatter all-reduces — and, since
    ISSUE 8, with no bool y plane or per-sweep pack either).  Jacobi sweeps
    (all operators read the same frontier); same fixpoint as the other
    engines.
    """
    from repro.kernels.segsum import ref as segsum_ref

    v, n = ops.init.shape
    w = ops.edge_src_b[0].shape[0]
    n_local = n // w
    if n_local % bitops.WORD:
        raise ValueError(
            "partitioned operands need 32-aligned blocks "
            f"(n={n}, n_blocks={w}); build them via make_partitioned_operands"
        )
    nlw = n_local // bitops.WORD

    def propagate_blocks(frontier_p: jax.Array, m: int) -> jax.Array:
        def block(src_w, dst_w):
            msgs = _edge_bits(frontier_p, src_w)  # int8 [V, Eb]
            # pad rows (dst = n_local) dropped by the segment reduce
            return segsum_ref.segor_words(msgs, dst_w, n_local)  # [V, nlw]

        yw = jax.vmap(block)(ops.edge_src_b[m], ops.edge_dst_b[m])  # [W,V,nlw]
        return yw.transpose(1, 0, 2).reshape(v, w * nlw)  # [V, nw] block-major

    return _packed_edge_fixpoint(
        propagate_blocks, ops, max_sweeps, chi_spec, chi0, jacobi=True
    )


# --------------------------------------------------------------------- #
# the paper's sequential worklist engine (numpy reference)
# --------------------------------------------------------------------- #
def solve_worklist(
    c: CompiledSOI,
    g: Graph,
    *,
    heuristic: str = "sparse_first",
    eq13_init: bool = True,
) -> tuple[np.ndarray, int]:
    """Paper Sect. 3.2 algorithm: pick an unstable inequality, validate or
    update, destabilize dependents.  Heuristics from Sect. 3.3:

    * ``sparse_first`` — static order preferring operators with more empty
      columns (sparser matrices shrink the relation earlier);
    * ``fifo`` — arrival order;
    * row- vs column-wise evaluation of ``r`` chosen dynamically by comparing
      ``|chi(rhs)|`` with ``|chi(lhs)|``.

    Returns (chi, number of inequality evaluations).
    """
    n = g.n_nodes
    chi = (
        c.init.copy()
        if eq13_init
        else _eq12_init(c, g)
    )
    ineqs = list(zip(c.ineq_lhs, c.ineq_rhs, c.ineq_mat))
    copies = list(zip(c.copy_lhs, c.copy_rhs))

    # CSR per operator for row-wise evaluation.
    csr: list[tuple[np.ndarray, np.ndarray]] = []
    csc: list[tuple[np.ndarray, np.ndarray]] = []
    nonempty_cols: list[int] = []
    for a, d in c.mats:
        e = g.edges_for_label(a)
        s, t = (e[:, 0], e[:, 1]) if d == FWD else (e[:, 1], e[:, 0])
        csr.append(_csr(s, t, n))
        csc.append(_csr(t, s, n))
        nonempty_cols.append(len(np.unique(t)))

    if heuristic == "sparse_first":
        order = sorted(range(len(ineqs)), key=lambda i: nonempty_cols[ineqs[i][2]])
    else:
        order = list(range(len(ineqs)))

    # dependents: inequalities whose rhs is a given variable.
    dep_edge: list[list[int]] = [[] for _ in range(c.n_vars)]
    for i, (_, r, _) in enumerate(ineqs):
        dep_edge[r].append(i)
    dep_copy: list[list[int]] = [[] for _ in range(c.n_vars)]
    for i, (_, r) in enumerate(copies):
        dep_copy[r].append(i)

    unstable = set(range(len(ineqs)))
    unstable_c = set(range(len(copies)))
    evaluations = 0
    while unstable or unstable_c:
        if unstable:
            idx = next(i for i in order if i in unstable)
            unstable.discard(idx)
            l, r, m = ineqs[idx]
            evaluations += 1
            rr = _bit_product(chi[r], chi[l], csr[m], csc[m], n)
            new = chi[l] & rr
            if not np.array_equal(new, chi[l]):
                chi[l] = new
                # destabilize dependents (rhs == l); a self-loop inequality
                # (l == r) legitimately re-enters the worklist here.
                unstable.update(dep_edge[l])
                unstable_c.update(dep_copy[l])
        else:
            idx = unstable_c.pop()
            l, r = copies[idx]
            evaluations += 1
            new = chi[l] & chi[r]
            if not np.array_equal(new, chi[l]):
                chi[l] = new
                unstable.update(dep_edge[l])
                unstable_c.update(dep_copy[l])
    return chi, evaluations


def _eq12_init(c: CompiledSOI, g: Graph) -> np.ndarray:
    init = np.ones((c.n_vars, g.n_nodes), dtype=bool)
    for i, const in enumerate(c.soi.is_const):
        if const is not None:
            init[i] = c.init[i]
    # labels absent from the DB still force emptiness
    for i in range(c.n_vars):
        if not c.init[i].any():
            init[i] = False
    return init


def _csr(src: np.ndarray, dst: np.ndarray, n: int):
    order = np.argsort(src, kind="stable")
    s, t = src[order], dst[order]
    ptr = np.zeros(n + 1, dtype=np.int64)
    np.add.at(ptr, s + 1, 1)
    np.cumsum(ptr, out=ptr)
    return ptr, t.astype(np.int32)


def _bit_product(
    x: np.ndarray, lhs: np.ndarray, csr, csc, n: int
) -> np.ndarray:
    """r = x ×b A, evaluated row- or column-wise per the paper's heuristic."""
    if x.sum() <= lhs.sum():
        # row-wise: union the A-rows of set bits of x.
        ptr, idx = csr
        out = np.zeros(n, dtype=bool)
        for i in np.flatnonzero(x):
            out[idx[ptr[i] : ptr[i + 1]]] = True
        return out
    # column-wise: only decide the columns where lhs is set.
    ptr, idx = csc
    out = np.zeros(n, dtype=bool)
    for j in np.flatnonzero(lhs):
        out[j] = x[idx[ptr[j] : ptr[j + 1]]].any()
    return out


# --------------------------------------------------------------------- #
# high-level API
# --------------------------------------------------------------------- #
def pattern_graph_soi(pattern: Graph) -> SOI:
    """SOI for classic graph-to-graph dual simulation (pattern = G1)."""
    from .sparql import BGP, Triple, Var

    trs = tuple(
        Triple(Var(f"v{s}"), int(a), Var(f"v{o}"))
        for (s, a, o) in pattern.triples
    )
    return build_soi(BGP(trs))


def largest_dual_simulation(
    pattern: Graph,
    db: Graph,
    *,
    engine: str = "dense",
    dtype=jnp.float32,
    n_blocks: int = 4,
) -> tuple[np.ndarray, int]:
    """Largest dual simulation between ``pattern`` and ``db`` (Prop. 1).

    Returns ``(S, sweeps)`` with ``S`` a bool matrix of shape
    ``(pattern.n_nodes, db.n_nodes)``: ``S[v, x]`` iff x dual-simulates v.
    """
    soi = pattern_graph_soi(pattern)
    # map var ids back to pattern node order: vars are created in triple
    # order, so build the permutation explicitly.  Isolated pattern nodes
    # (no incident edges) are unconstrained: simulated by every db node.
    c = compile_soi(soi, db)
    seen = {b: i for i, b in enumerate(soi.base)}
    isolated = [n for n in range(pattern.n_nodes) if f"v{n}" not in seen]

    def reorder(chi: np.ndarray) -> np.ndarray:
        out = np.ones((pattern.n_nodes, db.n_nodes), dtype=bool)
        for node in range(pattern.n_nodes):
            if node not in isolated:
                out[node] = chi[seen[f"v{node}"]]
        return out

    if engine == "worklist":
        chi, it = solve_worklist(c, db)
        return reorder(np.asarray(chi)), int(it)
    chi, it = solve_compiled(c, db, engine=engine, dtype=dtype, n_blocks=n_blocks)
    return reorder(chi), it


def solve_compiled(
    c: CompiledSOI,
    g: Graph,
    *,
    engine: str = "dense",
    dtype=jnp.float32,
    n_blocks: int = 4,
    chi0: np.ndarray | None = None,
) -> tuple[np.ndarray, int]:
    """Solve a compiled SOI with the chosen engine; returns (chi, iters).

    Engines: ``dense``, ``packed``, ``packed_fused`` (bit-packed chi end to
    end through the fused ``bitmm_apply`` kernel), ``sparse``
    (Gauss–Seidel), ``jacobi_packed`` (edge lists over a bit-packed chi
    state, one packed frontier replicate per sweep), ``partitioned``
    (destination-partitioned edge blocks over packed chi; ``n_blocks``
    shards, node axis auto-padded), ``worklist`` (numpy reference).

    ``chi0`` warm-starts any batched engine from a previous fixpoint
    (callers are responsible for the re-seeding rule — use
    :func:`resume_fixpoint` for the safe high-level path).
    """
    if chi0 is not None:
        if engine == "worklist":
            raise ValueError("the worklist engine does not take a warm start")
        chi0 = jnp.asarray(chi0, dtype=bool)
    if engine == "dense":
        chi, it = solve_dense(make_dense_operands(c, g), dtype=dtype, chi0=chi0)
    elif engine == "packed":
        chi, it = solve_packed(make_packed_operands(c, g), chi0=chi0)
    elif engine == "packed_fused":
        chi, it = solve_packed_fused(make_packed_operands(c, g), chi0=chi0)
    elif engine == "sparse":
        chi, it = solve_sparse(make_sparse_operands(c, g), chi0=chi0)
    elif engine == "jacobi_packed":
        chi, it = solve_sparse(
            make_sparse_operands(c, g), mode="jacobi_packed", chi0=chi0
        )
    elif engine == "partitioned":
        ops = make_partitioned_operands(c, g, n_blocks)
        if chi0 is not None and chi0.shape[-1] != ops.init.shape[-1]:
            chi0 = jnp.pad(
                chi0, ((0, 0), (0, ops.init.shape[-1] - chi0.shape[-1]))
            )
        chi, it = solve_partitioned(ops, chi0=chi0)
        chi = chi[:, : g.n_nodes]  # drop block-padding columns
    elif engine == "worklist":
        return solve_worklist(c, g)
    else:
        raise ValueError(f"unknown engine {engine!r}")
    return np.asarray(chi), int(it)


def resume_fixpoint(
    c: CompiledSOI,
    g: Graph,
    chi0: np.ndarray,
    *,
    inserted_labels: set[int] | frozenset[int] = frozenset(),
    engine: str = "dense",
    dtype=jnp.float32,
    n_blocks: int = 4,
) -> tuple[np.ndarray, int]:
    """Warm-started fixpoint: resume from a previous snapshot's solution.

    ``chi0`` is the greatest solution computed against the *previous* graph
    snapshot; ``c`` is the SOI re-compiled against the mutated graph ``g``
    (same SOI structure, new Eq.-13 init).  Correctness (DESIGN.md 8.2):

    * **deletions only** — the greatest solution can only shrink, and every
      sweep is monotone-decreasing, so resuming from ``chi0 ∧ init_new``
      converges to exactly the new greatest fixpoint;
    * **insertions** — rows in the :func:`destabilized_rows` closure of the
      inserted labels are re-seeded to ⊤ (their fresh Eq.-13 init) first;
      rows outside the closure provably cannot grow, so the re-seeded start
      still dominates the new fixpoint.

    Returns ``(chi, sweeps)`` bit-identical to a cold solve on ``g``.
    """
    chi0 = np.array(chi0, dtype=bool, copy=True)
    if inserted_labels:
        chi0[destabilized_rows(c, set(inserted_labels))] = True
    return solve_compiled(
        c, g, engine=engine, dtype=dtype, n_blocks=n_blocks, chi0=chi0
    )
