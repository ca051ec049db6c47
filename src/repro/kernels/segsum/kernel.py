"""Pallas TPU kernels: windowed segment-sum and segmented-OR over sorted ids.

The scatter hot spot of the GNN zoo and the sparse dual-simulation engine:
``out[s] += sum_{i: seg[i]=s} vals[i]`` with ``seg`` sorted.  The TPU has no
scatter unit, so the reduce is reformulated as a one-hot matmul per edge
block — the MXU does the scatter (kernel_taxonomy §GNN, GE-SpMM style).

Tiling: grid over edge blocks.  A host-precomputed, scalar-prefetched map
``win[i]`` gives the segment-window block each edge block writes
(``BlockSpec`` index map reads it), valid because sorted ids make windows
monotone non-decreasing; the host layout guarantees each edge block touches
at most one window (`prepare`: blocks are split at window boundaries).
Revisited windows accumulate in VMEM; first visit initializes.

``segor_blocks`` generalizes the same layout to the segmented OR the
edge-list dual-simulation engines run every sweep (DESIGN.md Sect. 12):
edges are blocked by destination *word* window, each block one-hot-matmuls
its gathered frontier bits into per-destination counts, and an exact f32
two-matmul bit-pack turns the ``block_n`` destination rows of a window into
``block_n / 32`` output words — OR-accumulated in VMEM, so ``y`` leaves the
kernel already packed ``uint32`` and the engines never touch an ``[n]``-wide
bool plane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def prepare(
    vals: np.ndarray, seg_ids: np.ndarray, num_segments: int,
    block_e: int = 256, block_n: int = 256,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side layout: split/pad edge blocks so each touches ONE segment
    window of ``block_n``.  Returns (vals_p, seg_p, win, n_pad).
    Padding rows carry segment id = window_start (sums zeros — vals are 0).
    """
    e = len(seg_ids)
    order = np.argsort(seg_ids, kind="stable")
    seg_s, vals_s = seg_ids[order], vals[order]
    blocks_v, blocks_s, win = [], [], []
    i = 0
    while i < e:
        w = int(seg_s[i]) // block_n
        j = i
        while j < e and j - i < block_e and int(seg_s[j]) // block_n == w:
            j += 1
        bs = np.full(block_e, w * block_n, np.int32)
        bv = np.zeros((block_e,) + vals.shape[1:], vals.dtype)
        bs[: j - i] = seg_s[i:j]
        bv[: j - i] = vals_s[i:j]
        blocks_s.append(bs)
        blocks_v.append(bv)
        win.append(w)
        i = j
    n_pad = -(-num_segments // block_n) * block_n
    n_win = n_pad // block_n
    # every output window must be visited at least once (unvisited pallas
    # output blocks are undefined): insert zero blocks for uncovered windows
    covered = set(win)
    merged_v, merged_s, merged_w = [], [], []
    k = 0
    for w in range(n_win):
        if w in covered:
            while k < len(win) and win[k] == w:
                merged_v.append(blocks_v[k]); merged_s.append(blocks_s[k])
                merged_w.append(w); k += 1
        else:
            merged_v.append(np.zeros((block_e,) + vals.shape[1:], vals.dtype))
            merged_s.append(np.full(block_e, w * block_n, np.int32))
            merged_w.append(w)
    blocks_v, blocks_s, win = merged_v, merged_s, merged_w
    return (
        np.concatenate(blocks_v).reshape(len(win), block_e, *vals.shape[1:]),
        np.stack(blocks_s),
        np.asarray(win, np.int32),
        n_pad,
    )


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_n", "interpret")
)
def segsum_blocks(
    vals_b: jax.Array,  # [G, BE, D]
    seg_b: jax.Array,  # [G, BE] absolute sorted ids
    win: jax.Array,  # [G] window block per edge block
    *,
    num_segments: int,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    g, be, d = vals_b.shape
    n_pad = -(-num_segments // block_n) * block_n
    dp = -(-d // 128) * 128
    vals_p = jnp.zeros((g, be, dp), vals_b.dtype).at[:, :, :d].set(vals_b)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, be), lambda i, win: (i, 0)),
            pl.BlockSpec((1, be, dp), lambda i, win: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, dp), lambda i, win: (win[i], 0)),
    )

    def kern(win_ref, seg_ref, val_ref, out_ref):
        i = pl.program_id(0)

        @pl.when((i == 0) | (win_ref[i] != win_ref[jnp.maximum(i - 1, 0)]))
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        base = win_ref[i] * block_n
        local = seg_ref[0] - base  # [BE]
        onehot = (
            local[None, :] == jax.lax.iota(jnp.int32, block_n)[:, None]
        ).astype(val_ref.dtype)
        out_ref[...] += jnp.dot(
            onehot, val_ref[0], preferred_element_type=out_ref.dtype
        )

    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, dp), vals_b.dtype),
        interpret=interpret,
        name="segsum_blocks",
    )(win, seg_b, vals_p)
    return out[:num_segments, :d]


# Edge-block counts are rounded up to this multiple so modest edge churn
# under ``patch_operands`` lands in existing pad blocks instead of changing
# the blocked-layout shapes (zero retraces on warm resume, DESIGN.md 12).
SEG_G_PAD = 8


def prepare_segor(
    seg_ids: np.ndarray, num_segments: int,
    block_e: int = 256, block_n: int = 256, min_g: int = 0,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Host-side blocked layout for the segmented-OR kernel.

    Sorts edges by destination id, splits them into blocks of ``block_e``
    that each touch one destination window of ``block_n`` ids, and returns
    ``(idx_b, seg_b, win, n_pad)``: ``idx_b [G, BE]`` int32 gather indices
    into the original edge axis, ``seg_b [G, BE]`` absolute destination
    ids, ``win [G]`` the window each block writes, and the padded node
    count ``n_pad``.

    Pad entries carry gather index 0 and the sentinel id ``n_pad`` — the
    sentinel lies outside every window (its one-hot column is all-zero) and
    is ``>= num_segments`` (a segment reduce drops it), so a pad row can
    never turn on a bit regardless of what index 0 gathers.  Callers must
    pass RAW destination ids (< num_segments): an EDGE_PAD-style pad id of
    ``n`` would alias bit ``n`` whenever ``n`` falls inside a live window.
    """
    if block_n % 32:
        raise ValueError("block_n must be a multiple of 32")
    seg_ids = np.asarray(seg_ids, np.int32)
    e = len(seg_ids)
    order = np.argsort(seg_ids, kind="stable").astype(np.int32)
    seg_s = seg_ids[order]
    if e and int(seg_s[-1]) >= num_segments:
        raise ValueError(
            "seg_ids must be < num_segments (pass raw, unpadded edges)"
        )
    n_pad = max(-(-num_segments // block_n), 1) * block_n
    n_win = n_pad // block_n
    blocks_i, blocks_s, win = [], [], []
    i = 0
    while i < e:
        w = int(seg_s[i]) // block_n
        j = i
        while j < e and j - i < block_e and int(seg_s[j]) // block_n == w:
            j += 1
        bi = np.zeros(block_e, np.int32)
        bs = np.full(block_e, n_pad, np.int32)
        bi[: j - i] = order[i:j]
        bs[: j - i] = seg_s[i:j]
        blocks_i.append(bi)
        blocks_s.append(bs)
        win.append(w)
        i = j
    # every output window must be visited at least once (unvisited pallas
    # output blocks are undefined): insert all-pad blocks where uncovered
    covered = set(win)
    merged_i, merged_s, merged_w = [], [], []
    k = 0
    for w in range(n_win):
        if w in covered:
            while k < len(win) and win[k] == w:
                merged_i.append(blocks_i[k])
                merged_s.append(blocks_s[k])
                merged_w.append(w)
                k += 1
        else:
            merged_i.append(np.zeros(block_e, np.int32))
            merged_s.append(np.full(block_e, n_pad, np.int32))
            merged_w.append(w)
    g = -(-max(len(merged_w), min_g, 1) // SEG_G_PAD) * SEG_G_PAD
    while len(merged_w) < g:  # trailing pad blocks keep win monotone
        merged_i.append(np.zeros(block_e, np.int32))
        merged_s.append(np.full(block_e, n_pad, np.int32))
        merged_w.append(n_win - 1)
    return (
        np.stack(merged_i),
        np.stack(merged_s),
        np.asarray(merged_w, np.int32),
        n_pad,
    )


@functools.partial(
    jax.jit, static_argnames=("num_segments", "block_n", "interpret")
)
def segor_blocks(
    vals_b: jax.Array,  # [G, BE, V] 0/1 frontier bits per blocked edge
    seg_b: jax.Array,  # [G, BE] absolute destination ids (pads = n_pad)
    win: jax.Array,  # [G] destination-word window per edge block
    *,
    num_segments: int,
    block_n: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Segmented OR over the ``prepare_segor`` layout -> ``uint32 [V, nw]``.

    Per block: one-hot matmul scatters the 0/1 frontier bits into
    per-destination counts, then an exact f32 two-matmul bit-pack (16 low +
    16 high bit planes; every partial sum < 2**16 is exactly representable)
    collapses the ``block_n`` destination rows to ``block_n / 32`` words,
    OR-accumulated into the revisited VMEM output window.  VMEM per step:
    one ``[block_n, VP]`` f32 counts tile + the ``[block_n/32, VP]`` uint32
    output window — ~¼ MB at the defaults, far under the ~16 MB budget.
    """
    g, be, v = vals_b.shape
    n_pad = max(-(-num_segments // block_n), 1) * block_n
    block_w = block_n // 32
    nw = -(-num_segments // 32)
    vp = -(-v // 128) * 128
    vals_p = (
        jnp.zeros((g, be, vp), jnp.float32)
        .at[:, :, :v]
        .set(vals_b.astype(jnp.float32))
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(g,),
        in_specs=[
            pl.BlockSpec((None, 1, be), lambda i, win: (i, 0, 0)),
            pl.BlockSpec((None, be, vp), lambda i, win: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_w, vp), lambda i, win: (win[i], 0)),
    )

    def kern(win_ref, seg_ref, val_ref, out_ref):
        i = pl.program_id(0)

        @pl.when((i == 0) | (win_ref[i] != win_ref[jnp.maximum(i - 1, 0)]))
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        base = win_ref[i] * block_n
        local = seg_ref[...] - base  # [1, BE]; pad sentinels land >= block_n
        onehot = (
            local == jax.lax.broadcasted_iota(jnp.int32, (block_n, be), 0)
        ).astype(jnp.float32)
        counts = jnp.dot(
            onehot, val_ref[...], preferred_element_type=jnp.float32
        )  # [block_n, VP]
        bits = (counts > 0).astype(jnp.float32)
        # exact f32 bit-pack: words[w] = sum_s 2^s * bits[32w + s], split
        # into 16-bit halves so every weight and partial sum stays exact
        w_ids = jax.lax.broadcasted_iota(jnp.int32, (block_w, block_n), 0)
        j_ids = jax.lax.broadcasted_iota(jnp.int32, (block_w, block_n), 1)
        s = j_ids - w_ids * 32
        # integer shifts, not exp2: exp2 lowers through exp(x * ln 2) and
        # can return 32767.998 for 2^15, which truncates to the wrong word
        pow2 = jnp.int32(1) << jnp.clip(s % 16, 0, 15)
        lo_w = jnp.where(
            (s >= 0) & (s < 16), pow2.astype(jnp.float32), 0.0
        )
        hi_w = jnp.where(
            (s >= 16) & (s < 32), pow2.astype(jnp.float32), 0.0
        )
        lo = jnp.dot(lo_w, bits, preferred_element_type=jnp.float32)
        hi = jnp.dot(hi_w, bits, preferred_element_type=jnp.float32)
        # f32 -> int32 is exact (every half is < 2**16); the high half's
        # shift into the sign bit is fine, the caller reinterprets as uint32
        words = lo.astype(jnp.int32) | (hi.astype(jnp.int32) << 16)
        out_ref[...] = out_ref[...] | words

    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad // 32, vp), jnp.int32),
        interpret=interpret,
        name="segor_blocks",
    )(win, seg_b.reshape(g, 1, be), vals_p)
    return jax.lax.bitcast_convert_type(out[:nw, :v].T, jnp.uint32)
