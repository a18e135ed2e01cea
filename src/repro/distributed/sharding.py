"""Per-architecture parameter / batch / gradient PartitionSpecs.

Sharding rules (mesh ``("pod","data","model")`` / ``("data","model")``):

  batch            -> (pod, data)             [replicated when B < |dp|]
  attention        -> Q heads over `model` when divisible (Megatron TP),
                      otherwise head_dim for the projections + context-
                      parallel attention (rules live in models/layers.py;
                      the weight specs here must match)
  MLP / expert FF  -> column->row parallel over `model`
  MoE experts      -> over `data` (EP=16 intra-pod; pods replicate experts)
  vocab            -> over `model` (embed rows / unembed cols; the CE loss
                      reduces over the sharded vocab dim, never gathers)
  SSD / RG-LRU     -> channel dims over `model`
  optimizer state  -> ZeRO-1: + `data` on the first unsharded divisible dim
  giant gradients  -> + `pod` (reduce-scatter instead of all-reduce on the
                      cross-pod DP path) for leaves above ~0.5 GiB

The spec trees are built by mirroring the constructors in models/lm.py so
tree structure always matches ``init_params`` exactly (checked by tests).
"""

from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.models import moe as moe_lib
from repro.models.common import BlockCfg, ModelCfg
from repro.models.encdec import EncDecCfg
from repro.models.layers import ShardCtx


def island_mesh(n_islands: int | None = None, *, devices=None):
    """1-D ``("island",)`` mesh for the sharded evolutionary search.

    The search population's K axis is sharded over this single axis: each
    device holds one island's subpopulation (``docs/distributed.md``).
    ``n_islands`` defaults to every visible device; on CPU, more than one
    device requires ``--xla_force_host_platform_device_count`` to be set
    *before* jax initializes (``benchmarks.run --devices N`` or
    :func:`repro.launch.mesh.force_host_device_count`)."""
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs) if n_islands is None else int(n_islands)
    if n < 1:
        raise ValueError(f"island mesh needs at least 1 device, got {n}")
    if n > len(devs):
        raise RuntimeError(
            f"island mesh needs {n} devices but only {len(devs)} are "
            "visible — on CPU launch via `python -m benchmarks.run "
            f"--devices {n}` (or set XLA_FLAGS=--xla_force_host_platform_"
            f"device_count={n} before python imports jax)")
    return jax.make_mesh((n,), ("island",), (AxisType.Auto,),
                         devices=devs[:n])


def make_ctx(mesh, *, batch_size: int | None = None) -> ShardCtx:
    """ShardCtx from a production mesh (axis names decide dp)."""
    if mesh is None:
        return ShardCtx(mesh=None)
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    sharded = batch_size is None or batch_size % dp_size == 0
    return ShardCtx(mesh=mesh, dp=dp, tp="model", batch_sharded=sharded)


# ----------------------------------------------------------------- params

def _attn_specs(cfg: ModelCfg, ctx: ShardCtx) -> dict:
    tp = ctx.tp
    head_tp = ctx.can_shard(cfg.n_heads)
    kv_tp = ctx.can_shard(cfg.n_kv_heads)
    if head_tp:
        sp = {"wq": P(None, tp, None),
              "wk": P(None, tp if kv_tp else None, None if kv_tp else tp),
              "wv": P(None, tp if kv_tp else None, None if kv_tp else tp),
              "wo": P(tp, None, None)}
    else:   # context-parallel attention: shard head_dim on the projections
        sp = {"wq": P(None, None, tp), "wk": P(None, None, tp),
              "wv": P(None, None, tp), "wo": P(None, tp, None)}
    if cfg.qk_norm:
        sp["q_gamma"] = P(None)
        sp["k_gamma"] = P(None)
    return sp


def _mlp_specs(ctx: ShardCtx) -> dict:
    return {"wi": P(None, ctx.tp), "wg": P(None, ctx.tp),
            "wo": P(ctx.tp, None)}


def _ssd_specs(ctx: ShardCtx) -> dict:
    tp = ctx.tp
    return {"in_xz": P(None, tp), "in_bc": P(None, None),
            "in_dt": P(None, None), "conv_w": P(None, None),
            "A_log": P(None), "D": P(None), "dt_bias": P(None),
            "norm_g": P(tp), "out": P(tp, None)}


def _rglru_specs(ctx: ShardCtx) -> dict:
    tp = ctx.tp
    return {"in_xy": P(None, tp), "conv_w": P(None, tp),
            "w_r": P(None, tp), "w_i": P(None, tp),
            "a_param": P(tp), "out": P(tp, None)}


def _block_specs(blk: BlockCfg, cfg: ModelCfg, ctx: ShardCtx) -> dict:
    sp: dict[str, Any] = {"norm1": P(None)}
    if blk.kind == "attn":
        sp["attn"] = _attn_specs(cfg, ctx)
    elif blk.kind == "ssd":
        sp["ssd"] = _ssd_specs(ctx)
    elif blk.kind == "rglru":
        sp["rglru"] = _rglru_specs(ctx)
    if blk.moe is not None:
        sp["norm2"] = P(None)
        sp["moe"] = moe_lib.moe_param_specs(cfg, blk.moe, ctx)
    elif blk.d_ff:
        sp["norm2"] = P(None)
        sp["mlp"] = _mlp_specs(ctx)
    if blk.post_norms:
        sp["norm1_post"] = P(None)
        sp["norm2_post"] = P(None)
    return sp


def _stack(spec_tree):
    """Prepend the scan (n_repeats) axis to every leaf spec."""
    return jax.tree.map(lambda s: P(*((None,) + tuple(s))), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


def lm_param_specs(cfg: ModelCfg, ctx: ShardCtx) -> dict:
    tp = ctx.tp
    specs: dict[str, Any] = {"embed": P(tp, None), "final_norm": P(None)}
    if not cfg.tie_embeddings:
        specs["unembed"] = P(None, tp)
    for i, blk in enumerate(cfg.prefix):
        specs[f"pre{i}"] = _block_specs(blk, cfg, ctx)
    if cfg.n_repeats:
        specs["pattern"] = _stack(
            {f"blk{j}": _block_specs(blk, cfg, ctx)
             for j, blk in enumerate(cfg.pattern)})
    for i, blk in enumerate(cfg.suffix):
        specs[f"suf{i}"] = _block_specs(blk, cfg, ctx)
    return specs


def encdec_param_specs(cfg: EncDecCfg, ctx: ShardCtx) -> dict:
    mc = cfg.mc

    def enc_block():
        return {"norm1": P(None), "attn": _attn_specs(mc, ctx),
                "norm2": P(None), "mlp": _mlp_specs(ctx)}

    def dec_block():
        return {"norm1": P(None), "attn": _attn_specs(mc, ctx),
                "norm_x": P(None), "xattn": _attn_specs(mc, ctx),
                "norm2": P(None), "mlp": _mlp_specs(ctx)}

    return {"embed": P(ctx.tp, None),
            "enc": _stack(enc_block()), "dec": _stack(dec_block()),
            "enc_norm": P(None), "dec_norm": P(None)}


def param_specs(cfg, ctx: ShardCtx) -> dict:
    if isinstance(cfg, EncDecCfg):
        return encdec_param_specs(cfg, ctx)
    return lm_param_specs(cfg, ctx)


# ------------------------------------------------------- batch / grad / opt

def batch_specs(batch_tree, ctx: ShardCtx):
    """Shard dim 0 (batch) of every input over the DP axes."""
    dp = ctx.dp_spec

    def leaf(x):
        if hasattr(x, "ndim") and x.ndim >= 1:
            return P(*((dp,) + (None,) * (x.ndim - 1)))
        return P()
    return jax.tree.map(leaf, batch_tree)


_GIANT = 256 * 2**20        # elements; ~0.5 GiB in bf16


def grad_specs(params_tree, specs_tree, ctx: ShardCtx):
    """Gradient shardings: same as params, plus `pod` on the first unsharded
    divisible dim of giant leaves (cross-pod reduce-scatter instead of
    all-reduce — the MoE expert tensors of kimi-k2)."""
    if ctx.mesh is None or "pod" not in ctx.mesh.axis_names:
        return specs_tree
    pod = ctx.mesh.shape["pod"]

    def leaf(x, s):
        if np.prod(x.shape) < _GIANT:
            return s
        dims = list(tuple(s) + (None,) * (x.ndim - len(tuple(s))))
        used = set()
        for d in dims:
            for a in (d if isinstance(d, tuple) else (d,)):
                used.add(a)
        if "pod" in used:
            return s
        for i, d in enumerate(dims):
            if d is None and x.shape[i] % pod == 0:
                dims[i] = "pod"
                return P(*dims)
        return s
    return jax.tree.map(leaf, params_tree, specs_tree,
                        is_leaf=lambda s: isinstance(s, P))


def zero1_specs(params_tree, specs_tree, ctx: ShardCtx):
    """Optimizer-state shardings: params spec + `data` on the first
    unsharded divisible dim (ZeRO-1 state sharding over the DP axis)."""
    if ctx.mesh is None:
        return specs_tree
    data = ctx.mesh.shape["data"]

    def leaf(x, s):
        dims = list(tuple(s) + (None,) * (x.ndim - len(tuple(s))))
        used = set()
        for d in dims:
            for a in (d if isinstance(d, tuple) else (d,)):
                used.add(a)
        if "data" in used:
            return s
        for i, d in enumerate(dims):
            if d is None and x.shape[i] % data == 0 and x.shape[i] >= data:
                dims[i] = "data"
                return P(*dims)
        return s
    return jax.tree.map(leaf, params_tree, specs_tree,
                        is_leaf=lambda s: isinstance(s, P))


def to_shardings(spec_tree, mesh):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))
