"""Distributed collectives: island-migration primitives for the sharded
search plus the int8-compressed gradient all-reduce with error feedback.

**Island migration** (``engine="sharded"`` in ``repro.core.search``): the
population's K axis is sharded over a 1-D ``("island",)`` mesh, and every
``migrate_every`` generations each island rotates its elite block to the
next island on a ring — :func:`ring_shift` is that ``jax.lax.ppermute``,
applied leaf-wise to the whole survivor-state pytree so genomes travel with
their cached objectives.  A ring *rotation* (not a copy) preserves the
global genome multiset exactly: every row changes island, no row is
duplicated or dropped (tests/test_sharded_search.py asserts the multiset).
:func:`gather_islands` is the matching ``all_gather`` used to assemble
global Pareto/GenStats values inside the sharded step.

**Compressed gradient reduction** (original module contents):
int8-compressed gradient all-reduce with error feedback.

The DP gradient reduction moves |params| bytes per step across the `data`
(and `pod` / DCI) links — at 1T params that IS the collective term.  The
standard mitigation is quantized reduction with error feedback (1-bit Adam /
PowerSGD family):

    q      = quantize_int8(g + err)      # per-leaf scale = max|.| / 127
    g_hat  = psum(q) * scale / n
    err'   = (g + err) - dequant(q)      # local residual, re-injected next step

Error feedback keeps the *accumulated* quantization error bounded, so SGD/
Adam convergence is preserved (verified by tests/test_collectives.py: an
int8-compressed run matches the exact run's loss curve within tolerance).

Usage: inside a ``shard_map`` over the DP axes (see train/loop.py's
``dp_compressed`` mode); the wire payload is 1/4 of bf16, 1/8 of f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def ring_shift(tree, *, size: int, axis_name: str = "island",
               shift: int = 1):
    """Rotate every leaf's shard ``shift`` positions around the mesh ring:
    island ``i`` sends its block to island ``(i + shift) % size`` and
    receives island ``(i - shift) % size``'s.  ``size`` is the static mesh
    axis size (``ppermute`` permutations must be python data, which a
    traced axis size cannot build).  Only
    valid inside a ``shard_map`` over ``axis_name``."""
    size = int(size)
    if size < 1:
        raise ValueError(f"ring over {size} islands")
    perm = [(i, (i + shift) % size) for i in range(size)]
    return jax.tree.map(
        lambda v: jax.lax.ppermute(v, axis_name, perm), tree)


def gather_islands(tree, *, axis_name: str = "island", axis: int = 0,
                   tiled: bool = False):
    """Leaf-wise ``jax.lax.all_gather`` over the island axis: every island
    ends up holding the stacked (``tiled=False``, new leading axis) or
    concatenated (``tiled=True``) per-island values — the assembly step for
    global fronts/stats inside the sharded search."""
    return jax.tree.map(
        lambda v: jax.lax.all_gather(v, axis_name, axis=axis, tiled=tiled),
        tree)


def quantize_int8(x: jax.Array):
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum_mean(x: jax.Array, err: jax.Array, axis_names):
    """One leaf: error-feedback int8 mean-reduction over ``axis_names``.
    Returns (mean_estimate f32, new_err)."""
    xf = x.astype(jnp.float32) + err
    q, scale = quantize_int8(xf)
    local_dq = dequantize_int8(q, scale)
    new_err = xf - local_dq
    # int8 payloads psum; scales are per-shard -> reduce the dequantized
    # value but transmit int8: sum_i dq_i = sum_i q_i*scale_i.  With a
    # shared (max) scale the wire format is exactly int8 + one f32.
    gmax = jax.lax.pmax(scale, axis_names)
    q2 = jnp.clip(jnp.round(xf / gmax), -127, 127).astype(jnp.int8)
    new_err = xf - q2.astype(jnp.float32) * gmax
    total = jax.lax.psum(q2.astype(jnp.int32), axis_names)
    n = 1
    for a in (axis_names if isinstance(axis_names, (tuple, list))
              else (axis_names,)):
        n *= jax.lax.axis_size(a)
    return total.astype(jnp.float32) * gmax / n, new_err


def compressed_grad_mean(grads, err_tree, axis_names):
    """Tree version. Returns (mean_grads f32, new_err_tree)."""
    fn = functools.partial(compressed_psum_mean, axis_names=axis_names)
    out = jax.tree.map(lambda g, e: fn(g, e), grads, err_tree)
    g = jax.tree.map(lambda o: o[0], out,
                     is_leaf=lambda o: isinstance(o, tuple))
    e = jax.tree.map(lambda o: o[1], out,
                     is_leaf=lambda o: isinstance(o, tuple))
    return g, e


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
