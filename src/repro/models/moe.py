"""Mixture-of-Experts channel block (token-choice top-k).

Distribution: experts shard over the ``data`` axis (EP=16 inside a pod —
expert parallelism stays on intra-pod ICI; pods replicate experts and act as
pure DP, which is also why the 1T kimi-k2 fits: weights live over
data x model = 256 ways).  Each expert's FF dim shards over ``model`` (TP).

Dispatch is capacity-based with a deterministic slot layout so that a single
tiled ``all_to_all`` moves tokens to their expert owners:

    send buffer (EP, E_loc, C3, d):  slot (dest, e_local, c) holds the c-th
    token this sender routes to expert dest*E_loc+e_local; C3 = ceil(T*k/E*cf)
    tokens per (sender, expert) pair; overflow tokens are dropped (standard
    capacity-factor semantics).

The paper's M0 insight (max-per-unit load, not aggregate, bounds step time)
maps 1:1 onto experts: `aux["max_expert_load"]` is the neurocore-aware metric
and the load-balance loss is the stage-1 "sparsity/balance-aware training"
analog.  See EXPERIMENTS.md §Perf for the dispatch-layout hillclimb.

``sp_dispatch=True`` slices the token payload over ``model`` before the
all_to_all (each TP shard moves d/16 of every token) instead of sending the
full ``d`` redundantly on every TP replica — 16x fewer wire bytes for the
dispatch at the cost of one extra all-gather after the return path.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import MoECfg, ModelCfg
from repro.models.layers import ACTS, KeyGen, ShardCtx, _init

from jax import shard_map


def moe_params(kg: KeyGen, cfg: ModelCfg, m: MoECfg, dtype) -> dict:
    d = cfg.d_model
    p = {
        "router": _init(kg(), (d, m.n_experts), d, jnp.float32),
        "wi": _init(kg(), (m.n_experts, d, m.d_ff), d, dtype),
    }
    if m.glu:
        p["wg"] = _init(kg(), (m.n_experts, d, m.d_ff), d, dtype)
    p["wo"] = _init(kg(), (m.n_experts, m.d_ff, d), m.d_ff, dtype)
    if m.n_shared_experts:
        ffs = m.d_ff * m.n_shared_experts
        p["s_wi"] = _init(kg(), (d, ffs), d, dtype)
        if m.glu:
            p["s_wg"] = _init(kg(), (d, ffs), d, dtype)
        p["s_wo"] = _init(kg(), (ffs, d), ffs, dtype)
    return p


def moe_param_specs(cfg: ModelCfg, m: MoECfg, ctx: ShardCtx) -> dict:
    ep = "data" if ctx.mesh is not None else None
    tp = ctx.tp
    specs = {
        "router": P(None, None),
        "wi": P(ep, None, tp),
        "wg": P(ep, None, tp),
        "wo": P(ep, tp, None),
    }
    if m.n_shared_experts:
        specs.update({"s_wi": P(None, tp), "s_wg": P(None, tp),
                      "s_wo": P(tp, None)})
    if not m.glu:                   # non-gated experts carry no wg
        del specs["wg"]
        specs.pop("s_wg", None)
    return specs


def _local_moe(x, p, *, m: MoECfg, cfg: ModelCfg, ep: int, tp_name: str,
               dp_names: tuple[str, ...], capacity_factor: float,
               sp_dispatch: bool):
    """Per-device body (runs under shard_map). x: (B_loc, S, d)."""
    B, S, d = x.shape
    T = B * S
    E, k = m.n_experts, m.top_k
    E_loc = E // ep
    C3 = max(1, math.ceil(T * k / E * capacity_factor))
    act = ACTS[cfg.act_fn]

    xf = x.reshape(T, d)
    logits = (xf.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, ids = jax.lax.top_k(probs, k)                      # (T, k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    # ---- aux: load-balance + z losses, M0 max-expert-load metric ----------
    counts = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0)
    counts = jax.lax.psum(counts, dp_names)
    frac = counts / jnp.maximum(jnp.sum(counts), 1.0)
    mean_prob = jax.lax.pmean(jnp.mean(probs, axis=0), dp_names)
    lb_loss = E * jnp.sum(frac * mean_prob)
    z_loss = jax.lax.pmean(
        jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1))), dp_names)
    aux = {
        "moe_lb_loss": lb_loss,
        "moe_z_loss": z_loss,
        "max_expert_load": jnp.max(counts),
        "mean_expert_load": jnp.mean(counts),
        "dropped_frac": jnp.float32(0.0),                    # filled below
    }

    # ---- dispatch slots ----------------------------------------------------
    flat_e = ids.reshape(T * k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=sorted_e.dtype))
    pos = jnp.arange(T * k) - starts[sorted_e]
    keep = pos < C3
    aux["dropped_frac"] = jax.lax.pmean(
        1.0 - jnp.mean(keep.astype(jnp.float32)), dp_names)
    dest = sorted_e // E_loc
    loc_e = sorted_e % E_loc
    slot = dest * (E_loc * C3) + loc_e * C3 + pos
    slot = jnp.where(keep, slot, ep * E_loc * C3)            # OOB -> dropped
    tok = order // k

    payload = xf
    if sp_dispatch:
        # each TP shard ships a distinct d/tp slice of every routed token
        tp_size = jax.lax.axis_size(tp_name)
        tp_idx = jax.lax.axis_index(tp_name)
        dsh = d // tp_size
        payload = jax.lax.dynamic_slice_in_dim(xf, tp_idx * dsh, dsh, axis=1)
    dd = payload.shape[1]
    send = jnp.zeros((ep * E_loc * C3, dd), payload.dtype)
    send = send.at[slot].set(payload[tok], mode="drop")
    recv = jax.lax.all_to_all(send.reshape(ep, E_loc * C3, dd), "data",
                              split_axis=0, concat_axis=0, tiled=True)
    # (EP_src, E_loc, C3, dd) -> (E_loc, EP_src*C3, dd)
    xe = recv.reshape(ep, E_loc, C3, dd).transpose(1, 0, 2, 3) \
             .reshape(E_loc, ep * C3, dd)
    if sp_dispatch:
        xe = jax.lax.all_gather(xe, tp_name, axis=2, tiled=True)  # full d

    # ---- expert FFN (ff sharded over `model`) -----------------------------
    h = jnp.einsum("ecd,edf->ecf", xe, p["wi"])
    if m.glu:
        h = act(jnp.einsum("ecd,edf->ecf", xe, p["wg"])) * h
    else:
        h = act(h)
    ye = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    if sp_dispatch:
        # reduce-scatter instead of all-reduce: each TP shard directly owns
        # the d/tp slice it will ship on the return all_to_all.
        ye = jax.lax.psum_scatter(ye, tp_name, scatter_dimension=2,
                                  tiled=True)
    else:
        ye = jax.lax.psum(ye, tp_name)                       # row-parallel

    # ---- return path -------------------------------------------------------
    back = ye.reshape(E_loc, ep, C3, -1).transpose(1, 0, 2, 3) \
             .reshape(ep, E_loc * C3, -1)
    back = jax.lax.all_to_all(back, "data", split_axis=0, concat_axis=0,
                              tiled=True)
    back = back.reshape(ep * E_loc * C3, -1)
    back = jnp.concatenate(
        [back, jnp.zeros((1, back.shape[1]), back.dtype)], axis=0)
    gathered = back[slot]                                    # sorted order
    gate_sorted = gate.reshape(T * k)[order]
    contrib = gathered * (gate_sorted * keep)[:, None].astype(back.dtype)
    y = jnp.zeros((T, back.shape[1]), back.dtype).at[tok].add(contrib)
    if sp_dispatch:
        y = jax.lax.all_gather(y, tp_name, axis=1, tiled=True)

    # ---- shared (always-on) experts ---------------------------------------
    if m.n_shared_experts:
        hs = (act(xf @ p["s_wg"]) * (xf @ p["s_wi"]) if m.glu
              else act(xf @ p["s_wi"]))
        ys = jax.lax.psum(hs @ p["s_wo"], tp_name)
        y = y + ys

    return y.reshape(B, S, d).astype(x.dtype), aux


def moe(x: jax.Array, p: dict, m: MoECfg, cfg: ModelCfg, ctx: ShardCtx,
        *, decode: bool = False, sp_dispatch: bool | None = None):
    """MoE block entry point. Returns (y, aux-dict of scalars)."""
    if sp_dispatch is None:
        sp_dispatch = ctx.flags.moe_sp_dispatch
    if ctx.mesh is None:
        raise ValueError("MoE requires a mesh (use single_device_mesh() "
                         "for CPU smoke tests)")
    ep = ctx.mesh.shape["data"]
    cf = m.decode_capacity_factor if decode else m.capacity_factor
    dp = ctx.dp if ctx.batch_sharded else ()
    specs = moe_param_specs(cfg, m, ctx)
    in_specs = (P(ctx.dp_spec, None, None),
                {k: specs[k] for k in p})
    out_specs = (P(ctx.dp_spec, None, None),
                 {k: P() for k in ["moe_lb_loss", "moe_z_loss",
                                   "max_expert_load", "mean_expert_load",
                                   "dropped_frac"]})
    body = functools.partial(
        _local_moe, m=m, cfg=cfg, ep=ep, tp_name=ctx.tp,
        dp_names=tuple(ctx.dp), capacity_factor=cf, sp_dispatch=sp_dispatch)
    fn = shard_map(body, mesh=ctx.mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    return fn(x, p)
