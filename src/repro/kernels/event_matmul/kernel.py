"""Block-sparse event-driven matmul — Pallas TPU kernel.

TPU adaptation of the paper's synop accumulation (DESIGN.md §3): activation
tiles with no events (all |x| <= threshold) are compacted away on the host
side; the kernel's grid walks only a compacted index list delivered through
scalar prefetch, so inactive (m, k) tiles drive **no weight-tile DMA and no
MXU issue** — the TPU analog of "a message is only sent for a nonzero
activation, and only its weights are fetched".

Grid: (M/bm, N/bn, K/bk), k innermost.  For grid step (m, n, k):

* x tile   <- x[m*bm:(m+1)*bm, idx[m,k]*bk:...]   (compacted k index)
* w tile   <- w[idx[m,k]*bk:..., n*bn:(n+1)*bn]
* guarded accumulate into a VMEM f32 scratch when k < n_active[m]; the
  compacted index map pins padding steps to the last active tile so Mosaic's
  revisit detection elides their copies.
* the accumulator is written to the output tile on the final k step.

Block shapes default to MXU-native 128x128x128 and must keep the last axis a
multiple of 128 and the second-to-last a multiple of 8 (f32) for VMEM tiling.

``precision`` defaults to HIGHEST: on the TPU the MXU's default single
bf16 pass rounds f32 operands to 8 mantissa bits, which moves values by
~3e-3 relative.  0/1 counter operands are exact in one pass and may pass
``Precision.DEFAULT``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HIGHEST = jax.lax.Precision.HIGHEST


def _event_matmul_kernel(idx_ref, cnt_ref, x_ref, w_ref, o_ref, acc_ref, *,
                         n_k_blocks: int, out_dtype, precision):
    m = pl.program_id(0)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < cnt_ref[m])
    def _accumulate():                      # skipped for event-free tiles
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...], precision=precision,
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_k_blocks - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def _event_matmul2_kernel(idx_ref, cnt_ref, x_ref, w_ref, o_ref, acc_ref, *,
                          n_k_blocks: int, out_dtype, precision):
    """2-D (activation x weight tile) sparsity: the compacted k list is per
    (m, n) block pair, so a grid step is skipped when EITHER the activation
    tile is event-free OR the weight tile is all-zero."""
    m = pl.program_id(0)
    n = pl.program_id(1)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k < cnt_ref[m, n])
    def _accumulate():                      # skipped: no events or no weights
        acc_ref[...] += jnp.dot(x_ref[...], w_ref[...], precision=precision,
                                preferred_element_type=jnp.float32)

    @pl.when(k == n_k_blocks - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def event_matmul2_pallas(x: jax.Array, w: jax.Array, idx: jax.Array,
                         cnt: jax.Array, *, bm: int, bk: int, bn: int,
                         out_dtype=None, interpret: bool = False,
                         precision=HIGHEST) -> jax.Array:
    """Joint-sparsity launch.  ``idx`` (Mb, Nb, Kb) int32 holds, per (m, n)
    block pair, the compacted k-block indices live in BOTH the activation
    row (tile has an event) and the weight column (tile has a nonzero
    weight); ``cnt`` (Mb, Nb) int32 holds the live counts.  Padding entries
    repeat the last live index so Mosaic's revisit detection elides their
    copies, exactly like the 1-D kernel."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    mb, kb, nb = M // bm, K // bk, N // bn
    assert idx.shape == (mb, nb, kb) and cnt.shape == (mb, nb)
    out_dtype = out_dtype or x.dtype

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(mb, nb, kb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k, idx, cnt: (m, idx[m, n, k])),
            pl.BlockSpec((bk, bn), lambda m, n, k, idx, cnt: (idx[m, n, k], n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k, idx, cnt: (m, n)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(_event_matmul2_kernel, n_k_blocks=kb,
                               out_dtype=out_dtype, precision=precision)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
        name="event_matmul2",
    )(idx, cnt, x, w)


def event_matmul_pallas(x: jax.Array, w: jax.Array, idx: jax.Array,
                        cnt: jax.Array, *, bm: int, bk: int, bn: int,
                        out_dtype=None, interpret: bool = False,
                        precision=HIGHEST) -> jax.Array:
    """Launch the kernel.  ``idx`` (Mb, Kb) int32 holds, per m-block, the
    compacted active k-block indices (padding entries repeat the last active
    index); ``cnt`` (Mb,) int32 holds the active counts.  All of M, K, N must
    already be padded to multiples of (bm, bk, bn)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2, (x.shape, w.shape)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0
    mb, kb, nb = M // bm, K // bk, N // bn
    assert idx.shape == (mb, kb) and cnt.shape == (mb,)
    out_dtype = out_dtype or x.dtype

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(mb, nb, kb),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k, idx, cnt: (m, idx[m, k])),
            pl.BlockSpec((bk, bn), lambda m, n, k, idx, cnt: (idx[m, k], n)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k, idx, cnt: (m, n)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(_event_matmul_kernel, n_k_blocks=kb,
                               out_dtype=out_dtype, precision=precision)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        interpret=interpret,
        name="event_matmul",
    )(idx, cnt, x, w)
