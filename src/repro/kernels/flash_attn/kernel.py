"""Flash-attention Pallas TPU kernel (lazy-softmax, GQA-aware).

The §Roofline memory terms count attention-score tensors as VMEM-resident
— this kernel is what makes that true on the TPU target: the (Sq x Skv)
score block never leaves VMEM; HBM traffic is exactly q/k/v reads + o
writes.

Grid: (batch*kv_head, Sq/BQ, Skv/BK) with the KV axis innermost ("arbitrary"
sequential on TPU) so the running (m, l, acc) state persists in VMEM across
KV steps.  Block shapes are MXU-aligned (BQ x BK = 128k x 128k tiles; head
dim is a full lane dimension).  Causal masking with an optional local
window; softcap for gemma-2.  Validated against ref.py in interpret mode
(CPU) over shape/dtype sweeps (tests/test_kernels_flash.py).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, n_kv_steps: int, causal: bool,
                  window: int | None, softcap: float | None, scale: float,
                  kv_len: int | None):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    run = True
    if causal:
        # skip fully-masked KV blocks
        run = (ki * bk) <= (qi * bq + bq - 1)

    @pl.when(run)
    def _step():
        q = q_ref[0].astype(jnp.float32) * scale     # (bq, G*hd) -> per-head
        k = k_ref[0].astype(jnp.float32)             # (bk, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok &= q_pos >= k_pos
        if window is not None:
            ok &= (q_pos - k_pos) < window
        if kv_len is not None:
            ok &= k_pos < kv_len        # sequence padding (non-causal too)
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_kv_steps - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int | None = None,
                           softcap: float | None = None,
                           bq: int = 128, bk: int = 128,
                           kv_len: int | None = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Sq, H, hd); k/v: (B, Skv, K, hd); H % K == 0.

    Query heads are grouped with their KV head: grid axis 0 iterates
    (B * K * G) query-head panels against that KV head's sequence."""
    B, Sq, H, hd = q.shape
    Skv, K = k.shape[1], k.shape[2]
    G = H // K
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, Skv, bq, bk)
    scale = 1.0 / math.sqrt(hd)
    n_kv = Skv // bk

    # (B, S, H, hd) -> (B*H, S, hd) query panels; KV indexed by head group
    qp = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, hd)
    kp = k.transpose(0, 2, 1, 3).reshape(B * K, Skv, hd)
    vp = v.transpose(0, 2, 1, 3).reshape(B * K, Skv, hd)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv_steps=n_kv, causal=causal,
        window=window, softcap=softcap, scale=scale, kv_len=kv_len)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, Sq // bq, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h // G, j, 0)),
            pl.BlockSpec((1, bk, hd), lambda h, i, j: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),      # running max m
            pltpu.VMEM((bq, 1), jnp.float32),      # running denom l
            pltpu.VMEM((bq, hd), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return out.reshape(B, H, Sq, hd).transpose(0, 2, 1, 3)
