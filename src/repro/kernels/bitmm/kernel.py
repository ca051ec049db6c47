"""Pallas TPU kernel: bit-packed boolean vector-batch x matrix product.

Computes ``out[q, jw] = OR_{i : x[q,i]=1} A[i, jw]`` over ``uint32`` words,
i.e. the paper's ``×b`` with the adjacency matrix resident in HBM/VMEM at
**1 bit per edge** (64x denser than bf16, 32x than int8).  The OR-AND
semiring runs on the VPU: a masked select of packed rows followed by an
OR-reduction over the contraction block.

Tiling: grid = (J, I) with the contraction dimension I innermost so each
``out`` tile is revisited sequentially and OR-accumulated in VMEM.

    x block   (V,  BI)   at (0, i)      — the query-variable frontier bits
    A block   (BI, BJW)  at (i, j)      — packed adjacency tile
    out block (V,  BJW)  at (0, j)      — packed result tile (accumulated)

VMEM per step = V*BI*4 + BI*BJW*4 + V*BJW*4 bytes plus the [V, BI, BJW]
select intermediate in VREGs; defaults (V<=8, BI=256, BJW=128) stay well
under the ~16 MiB VMEM budget.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _or_select(acc, bit, row):
    """``acc | (row if bit else 0)`` with ``bit`` a 0/1 ``[V, 1]`` column.

    ``0 - bit`` turns the bit into an all-ones or all-zero word mask, so the
    select is one AND per word and the OR-reduction over the contraction
    axis becomes a chain of elementwise ORs — no ``lax.reduce`` with a
    custom monoid, which Mosaic does not lower.
    """
    return acc | ((jnp.uint32(0) - bit) & row)


def _bitmm_kernel(x_ref, a_ref, o_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]  # [V, BI] uint32 (0/1 flags)
    a = a_ref[...]  # [BI, BJW] uint32 packed words
    # rows of A where the frontier bit is set, OR-folded over the block
    acc = o_ref[...]  # [V, BJW]
    for r in range(x.shape[1]):
        acc = _or_select(acc, x[:, r:r + 1], a[r:r + 1, :])
    o_ref[...] = acc


def _bitmm_apply_kernel(xc_ref, a_ref, f_ref, xe_ref, o_ref, chg_ref):
    """Fused sweep step: packed product, AND-combine, changed accumulation.

    Grid (J, I), I innermost.  ``o_ref`` doubles as the y accumulator: for
    i < I-1 it holds the partial packed product; the last contraction step
    turns it into the updated chi tile in place and ORs the moved words
    into ``chg_ref`` — one revisited output tile, no scratch buffer.
    ``chg_ref`` is a ``[V, BJW]`` word tile revisited by every grid step;
    the caller ORs it down to the scalar changed flag.
    """
    j, i = pl.program_id(0), pl.program_id(1)
    ni = pl.num_programs(1)

    @pl.when((j == 0) & (i == 0))
    def _init_changed():
        chg_ref[...] = jnp.zeros_like(chg_ref)

    @pl.when(i == 0)
    def _init_acc():
        o_ref[...] = jnp.zeros_like(o_ref)

    xw = xc_ref[...]  # [V, BIW] packed chi words of the contraction block
    # frontier bits of the block, extracted word-wise on the VPU: bit s of
    # word w is contraction row 32*w + s, matching a's host-side reshape
    acc = o_ref[...]
    for w in range(xw.shape[1]):
        col = xw[:, w:w + 1]  # [V, 1]
        a_w = a_ref[w]  # [32, BJW] packed adjacency rows 32*w .. 32*w+31
        for s in range(32):
            acc = _or_select(acc, (col >> s) & jnp.uint32(1), a_w[s:s + 1, :])
    o_ref[...] = acc

    @pl.when(i == ni - 1)
    def _combine():
        y = o_ref[...]  # [V, BJW] finished packed product chi ×b A
        f = f_ref[...]  # [V, V] lhs-rhs inequality flags
        # chi[l] &= AND_{r: f[l,r]} y[r]  ==  chi[l] &= ~OR_{r: f[l,r]} ~y[r]
        not_y = jnp.bitwise_not(y)
        bad = jnp.zeros_like(y)
        for r in range(f.shape[1]):
            bad = _or_select(bad, f[:, r:r + 1], not_y[r:r + 1, :])
        old = xe_ref[...]  # [V, BJW] chi tile being updated
        new = jnp.bitwise_and(old, jnp.bitwise_not(bad))
        o_ref[...] = new
        chg_ref[...] = chg_ref[...] | jnp.bitwise_xor(new, old)


@functools.partial(
    jax.jit, static_argnames=("block_i", "block_jw", "interpret")
)
def bitmm_apply_packed(
    chi_packed: jax.Array,  # uint32 [V, nw] packed chi rows
    a_packed: jax.Array,  # uint32 [n, nw] packed adjacency
    lhs_flags: jax.Array,  # uint32 [V, V] 0/1; [l, r] set iff ineq chi[l] <= chi[r] xb A
    *,
    block_i: int = 256,
    block_jw: int = 128,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One fused operator application on bit-packed chi.

    Computes ``y = chi ×b A`` and ``chi'[l] = chi[l] & AND_{r: F[l,r]} y[r]``
    in a single Pallas grid; returns ``(chi', changed)`` with ``changed`` a
    uint32 scalar that is nonzero iff any chi word moved.  Everything stays
    packed: HBM traffic is 1 bit per node end-to-end, and the former
    bitmm → unpack → gather → ``jnp.all`` → AND chain is one kernel launch.
    """
    assert block_i % 32 == 0, block_i
    v, nw = chi_packed.shape
    n, nw_a = a_packed.shape
    assert nw_a == nw, (chi_packed.shape, a_packed.shape)
    assert lhs_flags.shape == (v, v), (lhs_flags.shape, v)

    vp = -(-v // 8) * 8
    np_ = -(-n // block_i) * block_i
    nwp = -(-nw // block_jw) * block_jw
    biw = block_i // 32
    nbi = np_ // block_i
    # chi plays two roles: contraction input (its bits select A rows, so its
    # word axis pads to np_/32) and elementwise input (tiles like the
    # output, padding to nwp).  Zero padding is the OR/AND identity in both.
    # The contraction words go block-major, [I, V, BIW]: each grid step
    # reads one whole (V, BIW) trailing slab, which the TPU tiling accepts
    # for any BIW (a (V, BIW) window of a wider word axis would need BIW to
    # be a multiple of 128 lanes).
    xc = (
        jnp.zeros((vp, np_ // 32), jnp.uint32).at[:v, :nw].set(chi_packed)
        .reshape(vp, nbi, biw).transpose(1, 0, 2)
    )
    xe = jnp.zeros((vp, nwp), jnp.uint32).at[:v, :nw].set(chi_packed)
    a_p = jnp.zeros((np_, nwp), jnp.uint32).at[:n, :nw].set(a_packed)
    # row 32*w + s of block b lands at [b, w, s, :]: the kernel's bit
    # extraction indexes words, never reshapes inside the kernel
    a4 = a_p.reshape(nbi, biw, 32, nwp)
    f_p = jnp.zeros((vp, vp), jnp.uint32).at[:v, :v].set(lhs_flags)

    grid = (nwp // block_jw, nbi)
    chi_new, changed = pl.pallas_call(
        _bitmm_apply_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, vp, biw), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((None, biw, 32, block_jw), lambda j, i: (i, 0, 0, j)),
            pl.BlockSpec((vp, vp), lambda j, i: (0, 0)),
            pl.BlockSpec((vp, block_jw), lambda j, i: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((vp, block_jw), lambda j, i: (0, j)),
            pl.BlockSpec((vp, block_jw), lambda j, i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((vp, nwp), jnp.uint32),
            jax.ShapeDtypeStruct((vp, block_jw), jnp.uint32),
        ],
        interpret=interpret,
    )(xc, a4, f_p, xe)
    return chi_new[:v, :nw], jnp.any(changed != 0).astype(jnp.uint32)


@functools.partial(
    jax.jit, static_argnames=("block_i", "block_jw", "interpret")
)
def bitmm_packed(
    x_flags: jax.Array,  # uint32 [V, n] 0/1 per node
    a_packed: jax.Array,  # uint32 [n, nw]
    *,
    block_i: int = 256,
    block_jw: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Packed boolean product; returns uint32 [V, nw]."""
    v, n = x_flags.shape
    n_a, nw = a_packed.shape
    assert n == n_a, (x_flags.shape, a_packed.shape)

    # pad every dimension to its block multiple (zeros are OR-identities)
    vp = -(-v // 8) * 8
    np_ = -(-n // block_i) * block_i
    nwp = -(-nw // block_jw) * block_jw
    x_p = jnp.zeros((vp, np_), jnp.uint32).at[:v, :n].set(x_flags)
    a_p = jnp.zeros((np_, nwp), jnp.uint32).at[:n, :nw].set(a_packed)

    grid = (nwp // block_jw, np_ // block_i)
    out = pl.pallas_call(
        _bitmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((vp, block_i), lambda j, i: (0, i)),
            pl.BlockSpec((block_i, block_jw), lambda j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((vp, block_jw), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((vp, nwp), jnp.uint32),
        interpret=interpret,
    )(x_p, a_p)
    return out[:v, :nw]
