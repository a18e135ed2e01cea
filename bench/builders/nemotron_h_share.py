"""One partition's share of one period of a Nemotron-H hybrid (Mamba-2,
routed experts, GQA), lowered to fc layers at the configuration's widths.

Reads the catalog keys at the top level of the configuration: the widths
(``hidden_size``, ``mamba_head_dim``, ``ssm_state_size``, ``head_dim``,
``moe_intermediate_size``, ``moe_shared_expert_intermediate_size``), the
routing (``num_experts_per_tok``, ``routed_scaling_factor``), the counts
held here (``mamba_num_heads``, ``num_attention_heads``,
``num_key_value_heads``, ``n_routed_experts``) and the layer string
(``hybrid_override_pattern``); from ``published`` the counts that set the
group and router sizes (``mamba_num_heads`` with ``n_groups``;
``n_routed_experts``); from ``network`` the context and the SSM decay.

Each M is ``in -> state -> out``: the in-projection gives the held heads'
x and z lanes, their group's B and C taps and one dt lane a head; a state
neuron (``ssm``) reads its x lane, its group's B and C taps and its
head's dt.  Each E is ``up -> down``: the up-projection holds the held
experts' neurons, the shared experts' and the router's; its ``router``
(the program's ``Router``) silences every held expert outside the step's
top-k and scales the others.  Each * is ``qkv -> scores -> values ->
out`` over a context of ``network.context`` positions: a score neuron
(head h, position s) reads head h's query lanes, an output lane of head h
its S scores.  Out- and down-projections give this partition's partial
sums.  All but the state neurons are ReLU (relu^2 fires on the same set).

Weights are N(0, 1/fan-in) on the structural mask, drawn from the seed.
"""

from __future__ import annotations

import numpy as np


def _mask_ssd_state(heads: int, head_dim: int, groups: int,
                    state: int) -> np.ndarray:
    """(2 di + 2 groups state + heads, di) mask; fan-in laid out
    ``[x | z | B | C | dt]``."""
    di = heads * head_dim
    per_group = heads // groups
    m = np.zeros((2 * di + 2 * groups * state + heads, di), np.float32)
    j = np.arange(di)
    head = j // head_dim
    g = head // per_group
    m[j, j] = 1.0
    for k in range(state):
        m[2 * di + g * state + k, j] = 1.0
        m[2 * di + groups * state + g * state + k, j] = 1.0
    m[2 * di + 2 * groups * state + head, j] = 1.0
    return m


def _mask_scores(heads: int, kv: int, seq: int, head_dim: int) -> np.ndarray:
    q = heads * head_dim
    m = np.zeros((q + 2 * kv * head_dim, heads * seq), np.float32)
    for h in range(heads):
        m[h * head_dim:(h + 1) * head_dim, h * seq:(h + 1) * seq] = 1.0
    return m


def _mask_values(heads: int, seq: int, head_dim: int) -> np.ndarray:
    m = np.zeros((heads * seq, heads * head_dim), np.float32)
    for h in range(heads):
        m[h * seq:(h + 1) * seq, h * head_dim:(h + 1) * head_dim] = 1.0
    return m


def plan(config: dict) -> list[dict]:
    """The layers without weights: ``name``, ``fanin``, ``width``,
    ``mask`` (a callable giving the 0/1 mask, or None for dense),
    ``nnz``, ``neuron_model`` and ``router`` (or None)."""
    c, pub, net = config, config["published"], config["network"]
    d = int(c["hidden_size"])
    hd_m, st = int(c["mamba_head_dim"]), int(c["ssm_state_size"])
    per_group = int(pub["mamba_num_heads"]) // int(c["n_groups"])
    hh = int(c["mamba_num_heads"])
    groups = max(1, hh // per_group)
    dl = hh * hd_m
    fan = 2 * dl + 2 * groups * st + hh
    hq, hk = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd, seq = int(c["head_dim"]), int(net["context"])
    f = int(c["moe_intermediate_size"])
    shared = int(c["moe_shared_expert_intermediate_size"]) // f
    held, n_router = int(c["n_routed_experts"]), int(pub["n_routed_experts"])
    up = (held + shared) * f + n_router
    router = dict(n_experts=n_router, top_k=int(c["num_experts_per_tok"]),
                  width=f, held=tuple(range(held)), n_shared=shared,
                  scale=float(c["routed_scaling_factor"]))
    out = []

    def add(name, fanin, width, mask=None, nnz=None, neuron="relu",
            rt=None):
        out.append(dict(name=name, fanin=fanin, width=width, mask=mask,
                        nnz=fanin * width if nnz is None else nnz,
                        neuron_model=neuron, router=rt))

    n = {"M": 0, "E": 0, "*": 0}
    for ch in c["hybrid_override_pattern"]:
        p = f"{ch.replace('*', 'A')}{n[ch]}"
        n[ch] += 1
        if ch == "M":
            add(f"{p}.in", d, fan)
            add(f"{p}.state", fan, dl,
                lambda: _mask_ssd_state(hh, hd_m, groups, st),
                dl * (2 * st + 2), "ssm")
            add(f"{p}.out", dl, d)
        elif ch == "E":
            add(f"{p}.up", d, up, rt=router)
            add(f"{p}.down", up, d,
                lambda: np.repeat(np.arange(up) < up - n_router, d)
                .reshape(up, d).astype(np.float32),
                (up - n_router) * d)
        elif ch == "*":
            qkv = (hq + 2 * hk) * hd
            add(f"{p}.qkv", d, qkv)
            add(f"{p}.scores", qkv, hq * seq,
                lambda: _mask_scores(hq, hk, seq, hd), hq * seq * hd)
            add(f"{p}.values", hq * seq, hq * hd,
                lambda: _mask_values(hq, seq, hd), hq * seq * hd)
            add(f"{p}.out", hq * hd, d)
        else:
            raise ValueError(f"unknown layer {ch!r}")
    return out


def build(config: dict, rng: np.random.Generator) -> tuple[list[dict], int]:
    """Layer specs in ``bench/builders``' format, each with its
    ``router`` (or None), and the input width."""
    decay = float(config["network"]["decay"])
    layers = []
    for p in plan(config):
        mask = p["mask"]() if p["mask"] is not None else None
        per_neuron = p["nnz"] / p["width"]
        w = rng.standard_normal((p["fanin"], p["width"]), np.float32)
        w *= np.float32(1.0 / np.sqrt(per_neuron))
        if mask is not None:
            w *= mask
        layers.append(dict(
            name=p["name"], kind="fc", weights=w, stride=1, in_hw=None,
            neuron_model=p["neuron_model"], threshold=0.0, decay=decay,
            sends_deltas=False, router=p["router"]))
    return layers, int(config["hidden_size"])
