"""Jitted wrapper for the flash-attention kernel with shape padding."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attn.kernel import flash_attention_pallas


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, softcap: float | None = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool | None = None) -> jax.Array:
    """Pads Sq/Skv to block multiples, launches the kernel, slices back.
    Pad queries produce garbage rows that are sliced off; pad KV rows are
    masked inside the kernel via ``kv_len`` (the real key count), which
    keeps non-causal attention — encoder/cross blocks lowered by the
    model-zoo frontend — exact too.  ``interpret`` forces Pallas interpret
    mode (auto: on for CPU backends)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    pq = (-Sq) % bq
    pk = (-Skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 softcap=softcap, bq=bq, bk=bk,
                                 kv_len=Skv if pk else None,
                                 interpret=interpret)
    return out[:, :Sq]
