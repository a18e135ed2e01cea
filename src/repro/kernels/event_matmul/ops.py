"""Public jit'd wrapper for the block-sparse event-driven matmul."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.event_matmul.kernel import (event_matmul2_pallas,
                                               event_matmul_pallas)
from repro.kernels.event_matmul.ref import block_activity_ref


def _pad_to(a: jax.Array, mult: tuple[int, ...]) -> jax.Array:
    pads = [(0, (-s) % m) for s, m in zip(a.shape, mult)]
    if any(p[1] for p in pads):
        a = jnp.pad(a, pads)
    return a


def block_activity(x: jax.Array, threshold: float, bm: int = 128,
                   bk: int = 128) -> jax.Array:
    """(Mb, Kb) bool activity map.

    Accepts either raw or already tile-aligned ``x``: ``_pad_to`` is a no-op
    on aligned inputs, so callers that pad for the kernel share one pad with
    this helper instead of paying a second copy.
    """
    x = _pad_to(x, (bm, bk))
    return block_activity_ref(x, threshold, bm, bk)


def pad_compact(x: jax.Array, threshold: float, bm: int = 128,
                bk: int = 128) -> tuple[jax.Array, jax.Array, jax.Array,
                                        jax.Array]:
    """One pad, one activity map, one compaction — shared by every consumer.

    Returns ``(xp, active, idx, cnt)``: the (bm, bk)-aligned operand, its
    (Mb, Kb) bool activity map, and the compacted per-m-block active
    k-tile indices + counts the kernel's scalar prefetch consumes.  This is
    the single entry point through which :func:`block_activity` and
    :func:`event_matmul` (and the simulator's event compute backend) derive
    their tile structures, so no caller ever pays a second pad.
    """
    xp = _pad_to(x, (bm, bk))
    active = block_activity_ref(xp, threshold, bm, bk)
    idx, cnt = _compact_indices(active)
    return xp, active, idx, cnt


def weight_block_occupancy(w: jax.Array, bk: int = 128,
                           bn: int = 128) -> jax.Array:
    """(Kb, Nb) bool block-CSR occupancy map: tile holds >= 1 nonzero weight.

    The host-side half of 2-D (activation x weight) sparsity: computed once
    per layer from the immutable weight mask, padded to the kernel's tile
    grid (padding tiles are all-zero, hence unoccupied), and intersected
    with the per-m-block activity lists by :func:`event_matmul` /
    :func:`event_matmul_pair` so all-zero weight tiles drive no DMA and no
    MXU issue.  Accepts the weights themselves or a 0/1 mask — occupancy is
    ``any(w != 0)`` either way.
    """
    wp = _pad_to(jnp.asarray(w), (bk, bn))
    K, N = wp.shape
    tiles = (wp != 0).reshape(K // bk, bk, N // bn, bn)
    return tiles.any(axis=(1, 3))


def _compact_indices_joint(active: jax.Array,
                           w_occ: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Intersect per-m-block activity with weight-tile occupancy.

    ``active`` (Mb, Kb) bool, ``w_occ`` (Kb, Nb) bool.  Returns the 2-D
    kernel's scalar-prefetch structure: ``idx`` (Mb, Nb, Kb) int32 compacted
    k lists per (m, n) block pair and ``cnt`` (Mb, Nb) int32 live counts —
    a k step survives only when the activation tile has an event AND the
    weight tile has a nonzero.  Reuses the stable cumsum compaction of
    :func:`_compact_indices` over the flattened (Mb * Nb) leading axis.
    """
    mb, kb = active.shape
    kb2, nb = w_occ.shape
    assert kb == kb2, (active.shape, w_occ.shape)
    joint = active[:, None, :] & w_occ.T[None, :, :]      # (Mb, Nb, Kb)
    idx, cnt = _compact_indices(joint.reshape(mb * nb, kb))
    return idx.reshape(mb, nb, kb), cnt.reshape(mb, nb)


def _compact_indices(active: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per m-block, compact active k-block indices to the front.

    Returns (idx (Mb, Kb) int32, cnt (Mb,) int32).  Padding entries repeat
    the last active index (or 0 when a row is fully inactive) so the kernel's
    index map revisits an already-resident tile instead of DMA'ing a new one.

    Stable cumsum compaction: each active column's destination slot is its
    running count minus one (O(Mb*Kb) scatter instead of an O(Kb log Kb)
    per-row argsort).
    """
    mb, kb = active.shape
    cum = jnp.cumsum(active, axis=1)
    cnt = cum[:, -1].astype(jnp.int32)
    # inactive columns scatter into an overflow slot that is sliced away
    dest = jnp.where(active, cum - 1, kb)
    rows = jnp.broadcast_to(jnp.arange(mb)[:, None], (mb, kb))
    cols = jnp.broadcast_to(jnp.arange(kb)[None, :], (mb, kb))
    idx = (jnp.zeros((mb, kb + 1), jnp.int32)
           .at[rows, dest].set(cols.astype(jnp.int32))[:, :kb])
    pos = jnp.arange(kb)[None, :]
    last = jnp.take_along_axis(idx, jnp.maximum(cnt - 1, 0)[:, None], axis=1)
    idx = jnp.where(pos < cnt[:, None], idx, last)
    return idx, cnt


@functools.partial(jax.jit, static_argnames=("threshold", "bm", "bk", "bn",
                                             "interpret"))
def event_matmul(x: jax.Array, w: jax.Array, w_occ: jax.Array | None = None,
                 *, threshold: float = 0.0,
                 bm: int = 128, bk: int = 128, bn: int = 128,
                 interpret: bool | None = None) -> jax.Array:
    """``y = x @ w`` skipping event-free (bm, bk) activation tiles.

    The paper's synop accumulation adapted to the TPU memory hierarchy:
    weight tiles for event-free activation tiles are never DMA'd into VMEM
    and never touch the MXU.  Unstructured *element* sparsity inside an
    active tile is not exploited (matching the paper's CNN dense-format
    finding — structure is required for real fetch savings; on TPU the
    structure is the 128-tile).

    With ``w_occ`` (the (Kb, Nb) block-CSR occupancy from
    :func:`weight_block_occupancy`), sparsity goes 2-D: a (k, n) weight
    tile that is all-zero is skipped even when the activation tile is
    active, so work scales with ``act_density x weight_block_density``.
    Skipping an all-zero tile is exact — its contribution is an exact zero.

    Args:
      x: (M, K) activations (any float dtype).
      w: (K, N) weights.
      w_occ: optional (Kb, Nb) bool weight-tile occupancy (padded grid).
      threshold: |x| <= threshold counts as "no event".
      bm/bk/bn: VMEM tile sizes; MXU-aligned 128s by default.
      interpret: force Pallas interpret mode (auto: on for CPU backends).

    Returns: (M, N) in x.dtype.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    M, K = x.shape
    K2, N = w.shape
    if K != K2:
        raise ValueError(f"contraction mismatch: {x.shape} @ {w.shape}")
    xp, active, idx, cnt = pad_compact(x, threshold, bm, bk)
    wp = _pad_to(w, (bk, bn))
    if w_occ is None:
        out = event_matmul_pallas(xp, wp, idx, cnt, bm=bm, bk=bk, bn=bn,
                                  out_dtype=x.dtype, interpret=interpret)
    else:
        idx2, cnt2 = _compact_indices_joint(active, w_occ)
        out = event_matmul2_pallas(xp, wp, idx2, cnt2, bm=bm, bk=bk, bn=bn,
                                   out_dtype=x.dtype, interpret=interpret)
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("threshold", "bm", "bk", "bn",
                                             "interpret"))
def event_matmul_pair(x: jax.Array, m: jax.Array, w: jax.Array,
                      wm: jax.Array, w_occ: jax.Array | None = None,
                      *, threshold: float = 0.0,
                      bm: int = 128, bk: int = 128, bn: int = 128,
                      interpret: bool | None = None
                      ) -> tuple[jax.Array, jax.Array]:
    """Batched (T, ·) entry point for the simulator's event backend: the
    value matmul ``x @ w`` and the counter matmul ``m @ wm`` as ONE jitted
    program, each skipping its own event-free (bm, bk) tiles.

    ``x`` is the effective activation block (pre-activation GEMM input) and
    ``m`` its 0/1 wire-event mask; the two share a sparsity pattern only
    when no delta reconstruction is in play, so each operand gets its own
    :func:`pad_compact` — but both kernel launches, both pads and both
    compactions fuse into a single compiled program (one dispatch per
    simulated layer instead of two).

    With ``w_occ``, BOTH matmuls run through the 2-D joint-sparsity kernel
    with the same weight-tile occupancy: ``wm`` is the nnz mask of ``w``,
    so a tile that is all-zero in one is all-zero in the other — the value
    and counter contractions skip exactly the same (k, n) tiles, which is
    what keeps the event counters bit-identical to the dense reference.

    Returns ``(y, macs)`` cropped to ``(x.shape[0], w.shape[1])``.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    M, K = x.shape
    K2, N = w.shape
    if K != K2 or m.shape != x.shape or wm.shape != w.shape:
        raise ValueError(f"shape mismatch: {x.shape}/{m.shape} @ "
                         f"{w.shape}/{wm.shape}")
    xp, xa, xi, xc = pad_compact(x, threshold, bm, bk)
    mp, ma, mi, mc = pad_compact(m, 0.0, bm, bk)
    wp = _pad_to(w, (bk, bn))
    wmp = _pad_to(wm, (bk, bn))
    # the counter operands are 0/1: exact in the MXU's single bf16 pass
    one_pass = jax.lax.Precision.DEFAULT
    if w_occ is None:
        y = event_matmul_pallas(xp, wp, xi, xc, bm=bm, bk=bk, bn=bn,
                                out_dtype=x.dtype, interpret=interpret)
        macs = event_matmul_pallas(mp, wmp, mi, mc, bm=bm, bk=bk, bn=bn,
                                   out_dtype=m.dtype, interpret=interpret,
                                   precision=one_pass)
    else:
        xi2, xc2 = _compact_indices_joint(xa, w_occ)
        mi2, mc2 = _compact_indices_joint(ma, w_occ)
        y = event_matmul2_pallas(xp, wp, xi2, xc2, bm=bm, bk=bk, bn=bn,
                                 out_dtype=x.dtype, interpret=interpret)
        macs = event_matmul2_pallas(mp, wmp, mi2, mc2, bm=bm, bk=bk, bn=bn,
                                    out_dtype=m.dtype, interpret=interpret,
                                    precision=one_pass)
    return y[:M, :N], macs[:M, :N]
