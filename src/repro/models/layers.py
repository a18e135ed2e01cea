"""Composable JAX layers for the assigned architectures.

Sharding design (mesh axes ``("pod","data","model")`` or ``("data","model")``):

* batch / tokens shard over the DP axes (``pod`` x ``data``);
* ``model`` carries TP: column/row-parallel projections (heads when the head
  count divides the axis, otherwise head_dim + context-parallel attention),
  MLP ff dim, MoE expert-FF dim, SSD/RG-LRU channel dims;
* MoE experts shard over the DP axes (EP) with capacity-based all_to_all
  dispatch inside ``shard_map`` (see moe.py);
* decode uses a sequence-sharded KV cache ("flash-decoding": per-shard partial
  attention, GSPMD merges the softmax statistics with tiny all-reduces).

Everything is written against *global* semantics with
``with_sharding_constraint`` hints; the same code runs unsharded on one CPU
device (``ShardCtx(mesh=None)`` turns every hint into a no-op).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import BlockCfg, ModelCfg, RGLRUCfg, SSDCfg

# --------------------------------------------------------------------------
# Sharding context
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PerfFlags:
    """Hillclimb knobs (EXPERIMENTS.md §Perf).  Defaults = paper-faithful
    baseline; each flag is one candidate move in the floorline-style
    backtracking optimization (distributed/autoshard.py)."""

    moe_sp_dispatch: bool = False   # slice MoE a2a payload over `model`
    sp_residual: bool = False       # Megatron-SP: residual stream sequence-
                                    # sharded over `model` (ag/rs per block)


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh + axis roles threaded through every layer.

    ``mesh=None`` disables all constraints (single-device smoke tests).
    """

    mesh: Optional[Mesh] = None
    dp: tuple[str, ...] = ("data",)     # batch axes (("pod","data") multi-pod)
    tp: Optional[str] = "model"
    batch_sharded: bool = True          # False when B < |dp| (e.g. long_500k)
    flags: PerfFlags = PerfFlags()

    @property
    def tp_size(self) -> int:
        if self.mesh is None or self.tp is None:
            return 1
        return self.mesh.shape[self.tp]

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.dp]))

    @property
    def dp_spec(self):
        return self.dp if self.batch_sharded else None

    def cs(self, x: jax.Array, *dims) -> jax.Array:
        """with_sharding_constraint helper; dims are PartitionSpec entries."""
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*dims)))

    def cs_res(self, y: jax.Array) -> jax.Array:
        """Residual-stream constraint for (B, S, d) tensors: sequence-
        sharded over `model` when flags.sp_residual (Megatron-SP), else
        replicated over `model`."""
        if self.mesh is None:
            return y
        sp = self.tp if (self.flags.sp_residual
                         and y.shape[1] % max(self.tp_size, 1) == 0) else None
        return self.cs(y, self.dp_spec, sp, None)

    def can_shard(self, dim_size: int) -> bool:
        return self.tp is not None and dim_size % max(self.tp_size, 1) == 0


def single_device_mesh() -> Mesh:
    """1-device mesh with the production axis names (for smoke tests)."""
    return jax.make_mesh((1, 1), ("data", "model"), (AxisType.Auto,) * 2,
                         devices=np.array(jax.devices()[:1]))


# --------------------------------------------------------------------------
# dtype / init helpers
# --------------------------------------------------------------------------

def dt(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


def _init(key, shape, fan_in, dtype):
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


class KeyGen:
    """Deterministic per-leaf key derivation."""

    def __init__(self, key):
        self.key = key
        self.n = 0

    def __call__(self):
        self.n += 1
        return jax.random.fold_in(self.key, self.n)


# --------------------------------------------------------------------------
# Norms and positional embeddings
# --------------------------------------------------------------------------

def rms_norm(x: jax.Array, gamma: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + gamma.astype(jnp.float32))
    return out.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding. x: (..., S, H, hd); positions: (S,) or (B, S)."""
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd // 2, dtype=jnp.float32) / (hd // 2))
    angles = positions[..., None].astype(jnp.float32) * freqs    # (..., S, hd/2)
    angles = angles[..., None, :]                                # head axis
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


ACTS: dict[str, Callable] = {
    "silu": jax.nn.silu, "gelu": functools.partial(jax.nn.gelu, approximate=True),
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),   # squared ReLU
}


# --------------------------------------------------------------------------
# Attention
# --------------------------------------------------------------------------

def attn_params(kg: KeyGen, cfg: ModelCfg, dtype) -> dict:
    d = cfg.d_model
    p = {
        "wq": _init(kg(), (d, cfg.n_heads, cfg.head_dim), d, dtype),
        "wk": _init(kg(), (d, cfg.n_kv_heads, cfg.head_dim), d, dtype),
        "wv": _init(kg(), (d, cfg.n_kv_heads, cfg.head_dim), d, dtype),
        "wo": _init(kg(), (cfg.n_heads, cfg.head_dim, d), cfg.q_dim, dtype),
    }
    if cfg.qk_norm:
        p["q_gamma"] = jnp.zeros((cfg.head_dim,), dtype)
        p["k_gamma"] = jnp.zeros((cfg.head_dim,), dtype)
    return p


def _mask_bias(q_pos: jax.Array, kv_pos: jax.Array,
               window: Optional[int], *, causal: bool = True) -> jax.Array:
    """(..., Sq, Skv) additive mask bias in f32."""
    d = q_pos[..., :, None] - kv_pos[..., None, :]
    ok = (d >= 0) if causal else jnp.ones_like(d, dtype=bool)
    if window is not None:
        ok &= d < window
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _sdpa(q, k, v, bias, cfg: ModelCfg):
    """Grouped-query attention core. q:(B,Sq,H,hd) k/v:(B,Skv,K,hd)."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(hd)
    scores = softcap(scores, cfg.attn_softcap)
    scores = scores + bias[..., None, None, :, :] if bias.ndim == 2 else scores + bias
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v)
    return out.reshape(B, Sq, H, hd)


def _chunked_sdpa(q, k, v, q_pos, kv_pos, window, cfg: ModelCfg,
                  kv_chunk: int = 1024, causal: bool = True):
    """Lazy-softmax (flash-style) attention: scan over KV chunks carrying
    running (max, denom, acc). Keeps the score matrix at
    (B,K,G,Sq,kv_chunk) instead of (..., Skv) — required for 32k prefill."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    Skv = k.shape[1]
    n_chunks = Skv // kv_chunk
    qg = (q.reshape(B, Sq, K, G, hd).astype(jnp.float32)
          / math.sqrt(hd))
    kc = k.reshape(B, n_chunks, kv_chunk, K, hd)
    vc = v.reshape(B, n_chunks, kv_chunk, K, hd)
    pc = kv_pos.reshape(n_chunks, kv_chunk)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs
        s = jnp.einsum("bqkgh,bskh->bkgqs", qg, kb.astype(jnp.float32))
        s = softcap(s, cfg.attn_softcap)
        s = s + _mask_bias(q_pos, pb, window, causal=causal)[None, None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        scale = jnp.exp(m - m_new)
        l_new = l * scale + jnp.sum(p, axis=-1)
        acc_new = acc * scale[..., None] + jnp.einsum(
            "bkgqs,bskh->bkgqh", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, K, G, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, K, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, K, G, Sq, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, (1, 2), (2, 3))          # (B,Sq,K,G,hd)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def attention(x: jax.Array, p: dict, blk: BlockCfg, cfg: ModelCfg,
              ctx: ShardCtx, *, positions: jax.Array,
              causal: bool = True, xkv: jax.Array | None = None,
              return_kv: bool = False):
    """Full-sequence attention (training / prefill).

    TP mode: "head" (H % tp == 0) shards Q heads; otherwise context-parallel:
    Q is sequence-sharded and KV gathered — no duplicated FLOPs either way.
    ``xkv`` switches to cross-attention (whisper decoder).
    ``return_kv`` additionally returns the rotary-embedded (k, v) for
    prefill cache construction (window blocks: last ``window`` positions).
    """
    B, S, dmod = x.shape
    head_tp = ctx.can_shard(cfg.n_heads)
    kv_src = x if xkv is None else xkv

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", kv_src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", kv_src, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_gamma"], cfg.norm_eps)
        k = rms_norm(k, p["k_gamma"], cfg.norm_eps)
    kv_pos = positions if xkv is None else jnp.arange(kv_src.shape[1])
    if blk.kind == "attn" and xkv is None:
        # cross-attention is content-based (no rope), matching decode
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, kv_pos, cfg.rope_theta)

    dp = ctx.dp_spec
    if head_tp:
        kv_tp = ctx.can_shard(cfg.n_kv_heads)
        q = ctx.cs(q, dp, None, ctx.tp, None)
        # kv heads that don't divide tp are replicated (GQA kv is small);
        # the weights stay head_dim-sharded for memory — GSPMD emits one
        # small all-gather after the projection.
        k = ctx.cs(k, dp, None, ctx.tp if kv_tp else None, None)
        v = ctx.cs(v, dp, None, ctx.tp if kv_tp else None, None)
    else:
        # context parallel: shard sequence of Q; KV gathered (small for GQA)
        q = ctx.cs(q, dp, ctx.tp, None, None)
        k = ctx.cs(k, dp, None, None, None)
        v = ctx.cs(v, dp, None, None, None)

    Skv = k.shape[1]
    if Skv > 4096 and Skv % 1024 == 0:
        out = _chunked_sdpa(q, k, v, positions, kv_pos, blk.window, cfg,
                            causal=causal)
    else:
        bias = _mask_bias(positions, kv_pos, blk.window, causal=causal)
        out = _sdpa(q, k, v, bias, cfg)

    if head_tp:
        out = ctx.cs(out, dp, None, ctx.tp, None)
    else:
        out = ctx.cs(out, dp, ctx.tp, None, None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    y = ctx.cs_res(y)
    if return_kv:
        if blk.window is not None and k.shape[1] > blk.window:
            k, v = k[:, -blk.window:], v[:, -blk.window:]
        return y, (k, v)
    return y


def attention_decode(x: jax.Array, p: dict, blk: BlockCfg, cfg: ModelCfg,
                     ctx: ShardCtx, *, cache_k: jax.Array, cache_v: jax.Array,
                     pos: jax.Array, cross: bool = False):
    """Single-token decode against a sequence-sharded KV cache
    ("flash-decoding": cache S over `model`; partial softmax merged by GSPMD).

    Projections are row-parallel over head_dim (divisible by 16 for every
    assigned arch) so no FLOPs are duplicated regardless of head count.
    Returns (y, new_cache_k, new_cache_v).  x: (B, 1, d).
    """
    dp = ctx.dp_spec
    W = cache_k.shape[1]

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_gamma"], cfg.norm_eps)
    if not cross:
        k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if cfg.qk_norm:
            k_new = rms_norm(k_new, p["k_gamma"], cfg.norm_eps)
        q = rope(q, pos[None], cfg.rope_theta) if blk.kind == "attn" else q
        if blk.kind == "attn":
            k_new = rope(k_new, pos[None], cfg.rope_theta)
        slot = pos % W if blk.window is not None else pos   # ring buffer
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k_new.astype(cache_k.dtype), slot, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v_new.astype(cache_v.dtype), slot, axis=1)
    else:
        q = q  # cross-attention: cache is the precomputed encoder K/V

    cache_k = ctx.cs(cache_k, dp, ctx.tp, None, None)
    cache_v = ctx.cs(cache_v, dp, ctx.tp, None, None)
    q = ctx.cs(q, dp, None, None, None)

    # valid-slot mask
    idx = jnp.arange(W)
    if cross:
        valid = jnp.ones((W,), bool)
        kv_pos = idx
    elif blk.window is not None:
        # ring buffer holds positions (pos-W, pos]; slot s holds the largest
        # p <= pos with p % W == s.
        kv_pos = pos - ((pos - idx) % W)
        valid = kv_pos >= 0
    else:
        kv_pos = idx
        valid = idx <= pos

    B, _, H, hd = q.shape
    K = cache_k.shape[2]
    G = H // K
    qg = q.reshape(B, K, G, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qg, cache_k,
                   preferred_element_type=jnp.float32) / math.sqrt(hd)
    s = softcap(s, cfg.attn_softcap)
    s = jnp.where(valid[None, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w.astype(cache_v.dtype), cache_v)
    out = out.reshape(B, 1, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return ctx.cs(y, dp, None, None), cache_k, cache_v


# --------------------------------------------------------------------------
# Dense MLP (SwiGLU family)
# --------------------------------------------------------------------------

def mlp_params(kg: KeyGen, d: int, d_ff: int, dtype) -> dict:
    return {
        "wi": _init(kg(), (d, d_ff), d, dtype),
        "wg": _init(kg(), (d, d_ff), d, dtype),
        "wo": _init(kg(), (d_ff, d), d_ff, dtype),
    }


def mlp(x: jax.Array, p: dict, cfg: ModelCfg, ctx: ShardCtx) -> jax.Array:
    """Gated MLP, column->row parallel over `model` (one psum per block)."""
    dp = ctx.dp_spec
    act = ACTS[cfg.act_fn]
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    g = jnp.einsum("bsd,df->bsf", x, p["wg"])
    h = ctx.cs(h, dp, None, ctx.tp)
    g = ctx.cs(g, dp, None, ctx.tp)
    y = jnp.einsum("bsf,fd->bsd", act(g) * h, p["wo"])
    return ctx.cs_res(y)


# --------------------------------------------------------------------------
# Mamba-2 SSD mixer (chunked, matmul-dominant — MXU friendly)
# --------------------------------------------------------------------------

def ssd_params(kg: KeyGen, cfg: ModelCfg, s: SSDCfg, dtype) -> dict:
    d = cfg.d_model
    H = s.d_inner // s.head_dim
    conv_ch = s.d_inner + 2 * s.n_groups * s.d_state
    return {
        "in_xz": _init(kg(), (d, 2 * s.d_inner), d, dtype),
        "in_bc": _init(kg(), (d, 2 * s.n_groups * s.d_state), d, dtype),
        "in_dt": _init(kg(), (d, H), d, dtype),
        "conv_w": _init(kg(), (s.d_conv, conv_ch), s.d_conv, dtype),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.full((H,), math.log(math.e - 1), jnp.float32),
        "norm_g": jnp.zeros((s.d_inner,), dtype),
        "out": _init(kg(), (s.d_inner, d), s.d_inner, dtype),
    }


def _ssd_chunk_scan(xh, a_log_dt, Bm, Cm, chunk: int, init_state=None):
    """SSD (state-space duality) chunked scan.

    xh: (B,S,H,P) inputs (already dt-scaled), a_log_dt: (B,S,H) log decay,
    Bm/Cm: (B,S,G,N) input/output maps. Returns (y (B,S,H,P), final_state
    (B,H,P,N)). Intra-chunk handled with dense matmuls; inter-chunk carried
    by a lax.scan over S/chunk steps.
    """
    Bsz, S, H, Pd = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    nc = S // chunk
    rep = H // G
    xc = xh.reshape(Bsz, nc, chunk, H, Pd)
    ac = a_log_dt.reshape(Bsz, nc, chunk, H)
    Bc = jnp.repeat(Bm.reshape(Bsz, nc, chunk, G, N), rep, axis=3)
    Cc = jnp.repeat(Cm.reshape(Bsz, nc, chunk, G, N), rep, axis=3)

    cum = jnp.cumsum(ac, axis=2)                         # (B,nc,L,H)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Lq,Lk,H)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(seg), 0.0)

    # intra-chunk (diag block): y_intra = (C B^T * L) @ x
    cb = jnp.einsum("bnqhs,bnkhs->bnqkh", Cc, Bc)
    y_intra = jnp.einsum("bnqkh,bnqkh,bnkhp->bnqhp", cb, L, xc)

    # chunk-local state contribution: sum_k exp(cum_end - cum_k) B_k x_k
    decay_to_end = jnp.exp(cum[:, :, -1:, :] - cum)      # (B,nc,L,H)
    chunk_states = jnp.einsum("bnkhs,bnkh,bnkhp->bnhps",
                              Bc, decay_to_end, xc)      # (B,nc,H,P,N)
    chunk_decay = jnp.exp(cum[:, :, -1, :])              # (B,nc,H)

    def body(state, xs):
        cs_, cd_, cum_ = xs                              # per-chunk
        new_state = state * cd_[..., None, None] + cs_
        return new_state, state                          # emit state *before* chunk

    s0 = (jnp.zeros((Bsz, H, Pd, N), xh.dtype) if init_state is None
          else init_state)
    final, prev_states = jax.lax.scan(
        body, s0, (jnp.moveaxis(chunk_states, 1, 0),
                   jnp.moveaxis(chunk_decay, 1, 0),
                   jnp.moveaxis(cum, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)        # (B,nc,H,P,N)

    # inter-chunk: y_inter = C_q exp(cum_q) @ state_in
    y_inter = jnp.einsum("bnqhs,bnqh,bnhps->bnqhp",
                         Cc, jnp.exp(cum), prev_states)
    y = (y_intra + y_inter).reshape(Bsz, S, H, Pd)
    return y, final


def ssd_mixer(x, p, s: SSDCfg, cfg: ModelCfg, ctx: ShardCtx,
              *, conv_state=None, ssm_state=None, decode: bool = False):
    """Mamba-2 block. Channels (d_inner, heads) shard over `model`."""
    dp = ctx.dp_spec
    B, S, _ = x.shape
    H = s.d_inner // s.head_dim
    xz = jnp.einsum("bsd,de->bse", x, p["in_xz"])
    bc = jnp.einsum("bsd,de->bse", x, p["in_bc"])
    dtv = jnp.einsum("bsd,dh->bsh", x, p["in_dt"]).astype(jnp.float32)
    xz = ctx.cs(xz, dp, None, ctx.tp)
    xi, z = jnp.split(xz, 2, axis=-1)
    conv_in = jnp.concatenate([xi, bc], axis=-1)

    if decode:
        # causal depthwise conv over the last d_conv inputs
        window = jnp.concatenate([conv_state, conv_in], axis=1)
        new_conv_state = window[:, 1:]
        conv_out = jnp.einsum("btc,tc->bc", window, p["conv_w"])[:, None, :]
    else:
        pad = jnp.zeros((B, s.d_conv - 1, conv_in.shape[-1]), conv_in.dtype)
        win = jnp.concatenate([pad, conv_in], axis=1)
        idx = jnp.arange(S)[:, None] + jnp.arange(s.d_conv)[None, :]
        conv_out = jnp.einsum("bstc,tc->bsc", win[:, idx], p["conv_w"])
        new_conv_state = win[:, -(s.d_conv - 1):] if s.d_conv > 1 else None
    conv_out = jax.nn.silu(conv_out)
    xi = conv_out[..., :s.d_inner]
    Bm, Cm = jnp.split(
        conv_out[..., s.d_inner:].reshape(B, -1, 2 * s.n_groups, s.d_state),
        2, axis=2)

    dtv = jax.nn.softplus(dtv + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    a_log_dt = dtv * A                                    # (B,S,H) log decay
    xi_h = xi.reshape(B, -1, H, s.head_dim).astype(jnp.float32)
    xh = xi_h * dtv[..., None]

    if decode:
        a = jnp.exp(a_log_dt)[:, 0]                       # (B,H)
        st = ssm_state * a[..., None, None] + jnp.einsum(
            "bhp,bhn->bhpn", xh[:, 0],
            jnp.repeat(Bm[:, 0], H // s.n_groups, axis=1).astype(jnp.float32))
        y = jnp.einsum("bhpn,bhn->bhp", st,
                       jnp.repeat(Cm[:, 0], H // s.n_groups,
                                  axis=1).astype(jnp.float32))[:, None]
        new_ssm_state = st
        y = y.reshape(B, 1, H, s.head_dim)
    else:
        chunk = next(c for c in range(min(s.chunk, S), 0, -1) if S % c == 0)
        y, new_ssm_state = _ssd_chunk_scan(
            xh, a_log_dt, Bm.astype(jnp.float32), Cm.astype(jnp.float32),
            chunk, init_state=ssm_state)
        y = y.reshape(B, S, H, s.head_dim)

    y = y + xi_h * p["D"][:, None]                        # skip (D term)
    y = y.reshape(B, -1, s.d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rms_norm(y, p["norm_g"], cfg.norm_eps)
    y = ctx.cs(y, dp, None, ctx.tp)
    out = jnp.einsum("bse,ed->bsd", y, p["out"])
    return ctx.cs_res(out), new_conv_state, new_ssm_state


# --------------------------------------------------------------------------
# RG-LRU mixer (RecurrentGemma)
# --------------------------------------------------------------------------

def rglru_params(kg: KeyGen, cfg: ModelCfg, r: RGLRUCfg, dtype) -> dict:
    d = cfg.d_model
    return {
        "in_xy": _init(kg(), (d, 2 * r.d_rnn), d, dtype),
        "conv_w": _init(kg(), (r.d_conv, r.d_rnn), r.d_conv, dtype),
        "w_r": _init(kg(), (r.d_rnn, r.d_rnn), r.d_rnn, dtype),
        "w_i": _init(kg(), (r.d_rnn, r.d_rnn), r.d_rnn, dtype),
        # a = sigmoid(a_param)^(c*r): init so a^c ~ 0.9..0.999
        "a_param": jnp.asarray(
            np.log(np.expm1(np.linspace(0.9, 0.999, r.d_rnn) ** (
                1.0 / r.c_exponent))), jnp.float32),
        "out": _init(kg(), (r.d_rnn, d), r.d_rnn, dtype),
    }


def rglru_mixer(x, p, r: RGLRUCfg, cfg: ModelCfg, ctx: ShardCtx,
                *, conv_state=None, h_state=None, decode: bool = False):
    """Real-gated LRU: h_t = a_t*h_{t-1} + sqrt(1-a_t^2)*(i_t * x_t)."""
    dp = ctx.dp_spec
    B, S, _ = x.shape
    xy = jnp.einsum("bsd,de->bse", x, p["in_xy"])
    xy = ctx.cs(xy, dp, None, ctx.tp)
    xb, gate_y = jnp.split(xy, 2, axis=-1)

    if decode:
        window = jnp.concatenate([conv_state, xb], axis=1)
        new_conv_state = window[:, 1:]
        xc = jnp.einsum("btc,tc->bc", window, p["conv_w"])[:, None, :]
    else:
        pad = jnp.zeros((B, r.d_conv - 1, xb.shape[-1]), xb.dtype)
        win = jnp.concatenate([pad, xb], axis=1)
        idx = jnp.arange(S)[:, None] + jnp.arange(r.d_conv)[None, :]
        xc = jnp.einsum("bstc,tc->bsc", win[:, idx], p["conv_w"])
        new_conv_state = win[:, -(r.d_conv - 1):] if r.d_conv > 1 else None

    rg = jax.nn.sigmoid(jnp.einsum("bsc,ce->bse", xc, p["w_r"])
                        .astype(jnp.float32))
    ig = jax.nn.sigmoid(jnp.einsum("bsc,ce->bse", xc, p["w_i"])
                        .astype(jnp.float32))
    log_a0 = jax.nn.log_sigmoid(p["a_param"])            # (d_rnn,)
    log_a = r.c_exponent * rg * log_a0                   # (B,S,d_rnn)
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) \
        * ig * xc.astype(jnp.float32)

    if decode:
        h = a[:, 0] * h_state + gated[:, 0]
        new_h, hs = h, h[:, None]
    else:
        if h_state is not None:
            gated = gated.at[:, 0].add(a[:, 0] * h_state)

        def comb(c1, c2):
            a1, b1 = c1
            a2, b2 = c2
            return a1 * a2, b1 * a2 + b2
        av, hs = jax.lax.associative_scan(comb, (a, gated), axis=1)
        new_h = hs[:, -1]

    y = hs.astype(x.dtype) * jax.nn.gelu(gate_y)
    y = ctx.cs(y, dp, None, ctx.tp)
    out = jnp.einsum("bse,ed->bsd", y, p["out"])
    return ctx.cs_res(out), new_conv_state, new_h
