"""Fused sigma-delta encoder — Pallas TPU kernel.

One VMEM pass produces the sparse delta-message stream and the updated
reconstruction state (paper workloads PilotNet [46]; sigma-delta networks
[34]).  Unfused, this is 4 HBM round-trips (delta, mask, quantize, state
add); fused it is a single elementwise tile walk:

    delta = a - s
    q     = round(delta / theta) * theta     where |delta| >= theta, else 0
    s'    = s + q

Emitting q (the message) and s' (the state) from one kernel halves HBM
traffic for the encoder — on a chip where the encoder runs every timestep
over every activation map, that is the memory-bound term of the floorline.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sigma_delta_kernel(a_ref, s_ref, q_ref, s_out_ref, *, theta: float):
    a = a_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    delta = a - s
    q = jnp.where(jnp.abs(delta) >= theta,
                  jnp.round(delta / theta) * theta, 0.0)
    q_ref[...] = q.astype(q_ref.dtype)
    s_out_ref[...] = (s + q).astype(s_out_ref.dtype)


def _window_cumsum_kernel(live_ref, x_ref, o_ref):
    """Within-window cumulative sum over the time axis of one (W, bd) tile.

    The cumsum is an MXU-friendly lower-triangular ones matmul (in-kernel
    ``jnp.cumsum`` does not lower well on TPU); quiet windows — flagged by
    the scalar-prefetched ``live`` vector — skip the matmul entirely and
    write zeros, the temporal analog of the event matmul's tile skip.
    """
    i = pl.program_id(0)

    @pl.when(live_ref[i] > 0)
    def _run():
        x = x_ref[...].astype(jnp.float32)
        W = x.shape[0]
        r = jax.lax.broadcasted_iota(jnp.int32, (W, W), 0)
        c = jax.lax.broadcasted_iota(jnp.int32, (W, W), 1)
        tri = (r >= c).astype(jnp.float32)
        # HIGHEST: the MXU's default single bf16 pass would round the
        # deltas to 8 mantissa bits
        o_ref[...] = jnp.dot(tri, x, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)

    @pl.when(live_ref[i] == 0)
    def _quiet():
        o_ref[...] = jnp.zeros_like(o_ref)


def window_cumsum_pallas(x: jax.Array, live: jax.Array, *, window: int,
                         bd: int = 512,
                         interpret: bool = False) -> jax.Array:
    """(T, D) -> per-window cumulative sums along time.  ``T`` must be a
    multiple of ``window`` (a multiple of 8 for f32 sublane tiling), ``D``
    a multiple of ``bd``; ``live`` is the (T/window,) int32 quiet-window
    flag vector (0 -> the window's output rows are exact zeros)."""
    T, D = x.shape
    assert T % window == 0 and D % bd == 0, (x.shape, window, bd)
    assert live.shape == (T // window,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(T // window, D // bd),
        in_specs=[pl.BlockSpec((window, bd), lambda i, j, live: (i, j))],
        out_specs=pl.BlockSpec((window, bd), lambda i, j, live: (i, j)),
    )
    return pl.pallas_call(
        _window_cumsum_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, D), x.dtype),
        interpret=interpret,
        name="window_cumsum",
    )(live, x)


def sigma_delta_pallas(a: jax.Array, s: jax.Array, *, theta: float,
                       bm: int = 256, bd: int = 512,
                       interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """(M, D) activations + state -> (messages q, new state).  M, D must be
    padded to (bm, bd) multiples."""
    M, D = a.shape
    assert s.shape == (M, D)
    assert M % bm == 0 and D % bd == 0
    grid = (M // bm, D // bd)
    spec = pl.BlockSpec((bm, bd), lambda i, j: (i, j))
    kernel = functools.partial(_sigma_delta_kernel, theta=theta)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=(spec, spec),
        out_shape=(jax.ShapeDtypeStruct((M, D), a.dtype),
                   jax.ShapeDtypeStruct((M, D), s.dtype)),
        interpret=interpret,
        name="sigma_delta_encode",
    )(a, s)
