"""Model-zoo workload frontend: compile a model config into a priceable
:class:`~repro.neuromorphic.network.SimNetwork`.

Every number the floorline produces is a function of exact event counters
(MACs / weight fetches / NoC messages), so "running a real model" on the
simulator means emitting a layer stack whose *counters* reproduce the
per-token cost arithmetic of the architecture — not its floating-point
function.  :func:`compile_network` takes any
:class:`repro.configs.registry.ArchEntry` id (or a raw
:class:`~repro.models.common.ModelCfg` / :class:`~repro.models.encdec.EncDecCfg`)
and lowers it block by block onto the existing ``SimLayer`` vocabulary:

**Execution model.**  One simulator timestep = one decoded token at steady
state.  The residual stream (width ``d_model``) is the feed-forward chain
backbone; each block becomes a short chain of ``fc`` layers mapping
``d_model -> ... -> d_model``.  The embedding lookup is the network input
(its fetch cost rides on the first layer's input messages) and RMSNorm
scales fold into the adjacent projection (``diag(g) @ W`` — exact linear
algebra, no extra fetches), so norms/embeddings appear only in
:func:`excluded_params`, the documented remainder that makes
``sum(param nnz) + excluded_params(cfg) == cfg.param_count()`` an identity.

**Attention** lowers through the flash-attention kernel contract
(:mod:`repro.kernels.flash_attn`) into an fc-equivalent counter map over a
steady-state context of ``S = min(window, seq_len)`` positions:

* ``qkv``    ``(d, q+2kv)`` dense — the per-token Q/K/V projections; the
  K/V output messages are real NoC traffic (they leave for the KV cache).
* ``scores`` ``(q+2kv, H*S)`` block-sparse — score neuron ``(h, s)`` reads
  exactly its head's ``head_dim`` query lanes: ``H*S*head_dim`` MACs/token,
  the exact ``q . k`` cost of one decode step.
* ``values`` ``(H*S, q)`` block-sparse — output lane ``(h, j)`` reads its
  head's ``S`` score neurons: ``q*S`` MACs/token, the exact ``a . v`` cost.
* ``out``    ``(q, d)`` dense.

The ``scores``/``values`` weights are stand-ins for cache contents (role
``"kv"``, zero parameter nnz); each lowering site is recorded as an
:class:`AttnSpec` so :func:`attention_probe` can execute the *real* Pallas
kernel against its jnp oracle at exactly the lowered (heads, head_dim, seq)
shape (``compile_network(verify_attention=True)`` does this inline).

**SSD / RG-LRU** mixers put their recurrence on the simulator's stateful
neuron models (``"ssm"`` by default, ``recurrent_neuron="sd_relu"`` maps the
state stream onto sigma-delta messaging instead): ``in -> state -> out``
with the state layer's fanin wired per head/group (x channel + B/C group
taps + dt), ``2*d_state + 2`` synapses per state neuron.

**MoE** blocks emit each expert as a contiguous column block (a natural
partition unit) plus ``n_experts`` router-logit columns.  With a softmax
router (``MoECfg.routed_scale`` None) a static ``msg_gate`` keeps exactly
``top_k + n_shared`` expert blocks messaging, so the down projection's
event-driven MACs are ``(top_k + n_shared) * d_ff * d`` —
:meth:`ModelCfg.active_param_count` arithmetic, produced by counters.  With
Nemotron-H's sigmoid router the up-projection carries a
:class:`~repro.neuromorphic.network.Router`: at every step its router
neurons pick the top-k experts, whose messages are scaled by their
renormalised weights, and every other expert is silent.  Non-gated experts
(``MoECfg.glu`` False, relu^2) are one ``d_ff`` block each; relu^2 runs as
the ``relu`` neuron model, which messages on the same set.

**Shares.**  ``share=(index, n)`` lowers what partition ``index`` of ``n``
holds of one period of the layer pattern (``cfg.pattern`` once) when every
layer is divided ``n`` ways: its Mamba-2 heads (with their group's B/C
taps, computed alike by every partition of the group), its query heads with
their KV heads, and its routed experts, beside the router at its full width
and the shared experts, which every partition holds.  Out- and
down-projections give partial sums, and those go on to the next layer; the
rest of the model (other periods, embedding, head) and the other
partitions lie elsewhere and are not stood in for.  Each layer of a share
is a submatrix (``LayerSpec.rows`` / ``cols``) of the same layer cut
``(0, 1)``, and its weights are drawn per synapse from the seed and the
synapse's indices in that uncut layer, so the shares of a layer add up to
it.

Emitted layers are ``kind="fc"`` with static gates or routers, so the
compiled network inherits every existing guarantee unchanged: bit-identical
counters across the two engines (batched/reference) and compute backends
(dense/event), pricing caches, population backends and the evolutionary
search all accept it like any hand-built network.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.models.common import BlockCfg, ModelCfg, MoECfg, RGLRUCfg, SSDCfg
from repro.models.encdec import EncDecCfg
from repro.neuromorphic.network import (Router, SimLayer, SimNetwork,
                                        make_inputs)

DEFAULT_SEQ_LEN = 16        # steady-state decode context for smoke pricing
_RECURRENT_NEURONS = ("ssm", "sd_relu")


# ===================================================================== specs

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """One attention lowering site == one flash_attn kernel instance."""

    name: str
    heads: int
    kv_heads: int
    head_dim: int
    seq: int                        # steady-state context length S
    causal: bool = True
    window: int | None = None
    softcap: float | None = None
    cross: bool = False             # encoder-decoder cross attention


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """Closed-form description of one emitted fc layer.

    ``nnz``/``macs_per_token`` are *arithmetic* (derived from the config,
    not from built weights); compile asserts the built mask reproduces them
    and the property suite asserts the simulator's counters do too.
    ``macs_per_token`` assumes the dense-activity token pipeline (every
    ungated neuron messaging, the compile default); it is None behind a
    router that holds only some of the experts, where it depends on the
    routing.  ``rows`` / ``cols`` are the ``[start, stop)`` ranges of the
    uncut layer's fan-in and neurons that this layer holds (see
    ``share=``).
    """

    name: str
    fanin: int
    width: int
    structure: tuple                # mask family, see _structure_mask
    role: str                       # "param" | "kv" | "state" | "head"
    nnz: int                        # structural nonzero synapses
    param_nnz: int                  # contribution to cfg.param_count()
    macs_per_token: int | None      # exact MACs per timestep
    neuron_model: str = "relu"
    gate: tuple | None = None       # ("moe", E, shared, top_k, d_ff, block)
    router: Router | None = None
    rows: tuple = ()
    cols: tuple = ()


# ----------------------------------------------------------- mask structures

def _structure_nnz(structure: tuple, fanin: int, width: int) -> int:
    kind = structure[0]
    if kind == "dense":
        return fanin * width
    if kind == "first_rows":
        return structure[1] * width
    if kind in ("attn_scores", "attn_values"):
        _, heads, seq, head_dim = structure
        return heads * seq * head_dim
    if kind == "moe_down":
        _, n_experts_total, _, d_ff, _ = structure
        return n_experts_total * d_ff * width
    if kind == "ssd_state":
        _, d_inner, head_dim, n_groups, d_state = structure
        return d_inner * (2 * d_state + 2)
    raise ValueError(f"unknown structure {structure!r}")


def _structure_mask(spec: LayerSpec) -> np.ndarray:
    """0/1 synapse mask (fanin, width) realizing ``spec.structure``."""
    kind = spec.structure[0]
    m = np.zeros((spec.fanin, spec.width), np.float32)
    if kind == "dense":
        m[:] = 1.0
    elif kind == "first_rows":
        m[: spec.structure[1], :] = 1.0
    elif kind == "attn_scores":
        # fanin layout [q | k | v]; neuron (h, s) reads head h's query lanes
        _, heads, seq, hd = spec.structure
        for h in range(heads):
            m[h * hd:(h + 1) * hd, h * seq:(h + 1) * seq] = 1.0
    elif kind == "attn_values":
        # fanin = H*S score lanes; output lane (h, j) reads head h's scores
        _, heads, seq, hd = spec.structure
        for h in range(heads):
            m[h * seq:(h + 1) * seq, h * hd:(h + 1) * hd] = 1.0
    elif kind == "moe_down":
        # fanin layout [expert 0 .. expert n-1 | router], ``block`` neurons
        # an expert (wi|wg, or wi alone for non-gated experts); only the wi
        # part of each expert projects down
        _, n_tot, n_router, f, block = spec.structure
        for e in range(n_tot):
            m[e * block: e * block + f, :] = 1.0
    elif kind == "ssd_state":
        # fanin layout [x (di) | z (di) | B (G*st) | C (G*st) | dt (h)]
        _, di, hd, groups, st = spec.structure
        n_heads = di // hd
        heads_per_group = n_heads // groups
        for j in range(di):
            head = j // hd
            g = head // heads_per_group
            m[j, j] = 1.0                                        # x channel
            m[2 * di + g * st: 2 * di + (g + 1) * st, j] = 1.0   # B taps
            b0 = 2 * di + groups * st
            m[b0 + g * st: b0 + (g + 1) * st, j] = 1.0           # C taps
            m[2 * di + 2 * groups * st + head, j] = 1.0          # dt
    else:
        raise ValueError(f"unknown structure {spec.structure!r}")
    assert int(m.sum()) == spec.nnz, (spec.name, int(m.sum()), spec.nnz)
    return m


def _structure_gate(spec: LayerSpec) -> np.ndarray | None:
    """Static per-neuron message gate (MoE expert activation)."""
    if spec.gate is None:
        return None
    tag, n_experts, n_shared, top_k, f, block = spec.gate
    assert tag == "moe"
    g = np.zeros(spec.width, np.float32)
    for e in range(top_k):                       # routed experts kept live
        g[e * block:(e + 1) * block] = 1.0
    for e in range(n_experts, n_experts + n_shared):   # always-on experts
        g[e * block:(e + 1) * block] = 1.0
    g[-n_experts:] = 1.0                         # router logits always emit
    return g


# ================================================================= lowering

class _Lowering:
    """Accumulates LayerSpecs; tracks the previous layer's gate so per-token
    MAC arithmetic stays exact across gated boundaries.  With ``share``,
    each block keeps partition ``share[0]`` of ``share[1]``'s units."""

    def __init__(self, seq_len: int, recurrent_neuron: str,
                 share: tuple[int, int] | None = None):
        if recurrent_neuron not in _RECURRENT_NEURONS:
            raise ValueError(f"recurrent_neuron must be one of "
                             f"{_RECURRENT_NEURONS}, got {recurrent_neuron!r}")
        self.seq_len = seq_len
        self.recurrent_neuron = recurrent_neuron
        self.share = share
        self.specs: list[LayerSpec] = []
        self.attn_specs: list[AttnSpec] = []
        self._prev_gate: tuple | None = None
        self._prev_router: Router | None = None

    def _held(self, count: int, what: str) -> tuple[int, int]:
        """``[start, stop)`` of the ``count`` units this partition holds."""
        if self.share is None:
            return 0, count
        index, n = self.share
        if count % n:
            raise ValueError(f"{what}: {count} do not divide {n} ways")
        k = count // n
        return index * k, (index + 1) * k

    def _whole(self, what: str) -> None:
        if self.share is not None:
            raise ValueError(f"a share of {what} is not lowered")

    def add(self, name: str, fanin: int, width: int, structure: tuple,
            role: str, *, param_nnz: int = 0, neuron_model: str = "relu",
            gate: tuple | None = None, router: Router | None = None,
            rows: tuple = (), cols: tuple = ()) -> None:
        nnz = _structure_nnz(structure, fanin, width)
        if self._prev_gate is None and self._prev_router is None:
            macs = nnz                       # dense input activity
        else:
            # Input messages are gated by the previous layer's MoE gate:
            # only live expert blocks' wi rows reach nonzero weights.
            assert structure[0] == "moe_down", \
                "only moe_up -> moe_down gating is lowered"
            if self._prev_gate is not None:
                _, n_experts, n_shared, top_k, f, _ = self._prev_gate
                macs = (top_k + n_shared) * f * width
            else:
                r = self._prev_router
                macs = ((r.top_k + r.n_shared) * structure[3] * width
                        if len(r.held) == r.n_experts else None)
        self.specs.append(LayerSpec(
            name=name, fanin=fanin, width=width, structure=structure,
            role=role, nnz=nnz, param_nnz=param_nnz,
            macs_per_token=macs, neuron_model=neuron_model, gate=gate,
            router=router, rows=rows or ((0, fanin),),
            cols=cols or ((0, width),)))
        self._prev_gate = gate
        self._prev_router = router

    # -------------------------------------------------------------- blocks
    def attn(self, prefix: str, d: int, heads: int, kv_heads: int,
             head_dim: int, *, seq: int, causal: bool = True,
             window: int | None = None, softcap: float | None = None,
             cross: bool = False) -> None:
        q, kv = heads * head_dim, kv_heads * head_dim
        h0, h1 = self._held(heads, f"{prefix} query heads")
        per_kv = heads // kv_heads
        k0, k1 = h0 // per_kv, (h1 - 1) // per_kv + 1
        hq, hk = h1 - h0, k1 - k0
        ql, kvl = hq * head_dim, hk * head_dim
        lanes = (h0 * head_dim, h1 * head_dim)
        qkv = (lanes, (q + k0 * head_dim, q + k1 * head_dim),
               (q + kv + k0 * head_dim, q + kv + k1 * head_dim))
        scores = ((h0 * seq, h1 * seq),)
        self.add(f"{prefix}.qkv", d, ql + 2 * kvl, ("dense",), "param",
                 param_nnz=d * (ql + 2 * kvl), cols=qkv)
        self.add(f"{prefix}.scores", ql + 2 * kvl, hq * seq,
                 ("attn_scores", hq, seq, head_dim), "kv", rows=qkv,
                 cols=scores)
        self.add(f"{prefix}.values", hq * seq, ql,
                 ("attn_values", hq, seq, head_dim), "kv", rows=scores,
                 cols=(lanes,))
        self.add(f"{prefix}.out", ql, d, ("dense",), "param",
                 param_nnz=ql * d, rows=(lanes,))
        self.attn_specs.append(AttnSpec(
            name=prefix, heads=hq, kv_heads=hk, head_dim=head_dim,
            seq=seq, causal=causal, window=window, softcap=softcap,
            cross=cross))

    def mlp(self, prefix: str, d: int, d_ff: int) -> None:
        self._whole("a dense MLP")
        # SwiGLU/GeGLU: wi|wg fused up, gate half carries no down weights
        self.add(f"{prefix}.in", d, 2 * d_ff, ("dense",), "param",
                 param_nnz=2 * d * d_ff)
        self.add(f"{prefix}.out", 2 * d_ff, d, ("first_rows", d_ff),
                 "param", param_nnz=d_ff * d)

    def moe(self, prefix: str, d: int, m: MoECfg) -> None:
        E, f, n_sh = m.n_experts, m.d_ff, m.n_shared_experts
        block = 2 * f if m.glu else f        # wi|wg, or wi alone
        if m.routed_scale is None:
            self._whole("a softmax-routed MoE")
            n_tot = E + n_sh
            width = n_tot * block + E
            self.add(f"{prefix}.experts_up", d, width, ("dense",), "param",
                     param_nnz=d * width,
                     gate=("moe", E, n_sh, m.top_k, f, block))
            self.add(f"{prefix}.experts_down", width, d,
                     ("moe_down", n_tot, E, f, block), "param",
                     param_nnz=n_tot * f * d)
            return
        e0, e1 = self._held(E, f"{prefix} routed experts")
        router = Router(n_experts=E, top_k=m.top_k, width=block,
                        held=tuple(range(e0, e1)), n_shared=n_sh,
                        scale=m.routed_scale)
        n_tot = e1 - e0 + n_sh
        up = ((e0 * block, e1 * block), (E * block, (E + n_sh) * block),
              ((E + n_sh) * block, (E + n_sh) * block + E))
        self.add(f"{prefix}.experts_up", d, router.n_neurons, ("dense",),
                 "param", param_nnz=d * router.n_neurons, router=router,
                 cols=up)
        self.add(f"{prefix}.experts_down", router.n_neurons, d,
                 ("moe_down", n_tot, E, f, block), "param",
                 param_nnz=n_tot * f * d, rows=up)

    def ssd(self, prefix: str, d: int, s: SSDCfg) -> None:
        di, st, groups = s.d_inner, s.d_state, s.n_groups
        n_heads = di // s.head_dim
        per_group = n_heads // groups
        h0, h1 = self._held(n_heads, f"{prefix} Mamba heads")
        if (h1 - h0) % per_group and per_group % (h1 - h0):
            raise ValueError(f"{prefix}: {h1 - h0} heads a partition cut "
                             f"groups of {per_group} unevenly")
        g0, g1 = h0 // per_group, (h1 - 1) // per_group + 1
        dl, gl, hd = (h1 - h0) * s.head_dim, g1 - g0, s.head_dim
        b0, c0, t0 = 2 * di, 2 * di + groups * st, 2 * di + 2 * groups * st
        x = (h0 * hd, h1 * hd)
        fan_cols = (x, (di + h0 * hd, di + h1 * hd),
                    (b0 + g0 * st, b0 + g1 * st), (c0 + g0 * st, c0 + g1 * st),
                    (t0 + h0, t0 + h1))
        fan = 2 * dl + 2 * gl * st + (h1 - h0)
        self.add(f"{prefix}.in", d, fan, ("dense",), "param",
                 param_nnz=d * fan, cols=fan_cols)
        self.add(f"{prefix}.state", fan, dl,
                 ("ssd_state", dl, hd, gl, st), "state",
                 neuron_model=self.recurrent_neuron, rows=fan_cols,
                 cols=(x,))
        self.add(f"{prefix}.out", dl, d, ("dense",), "param",
                 param_nnz=dl * d, rows=(x,))

    def rglru(self, prefix: str, d: int, r: RGLRUCfg) -> None:
        self._whole("an RG-LRU mixer")
        dr = r.d_rnn
        self.add(f"{prefix}.in", d, 2 * dr, ("dense",), "param",
                 param_nnz=2 * d * dr)
        # r,i gates are two (dr, dr) maps of the x half: lowered as one
        # dense (2dr, dr) recurrence layer — 2*dr^2 params exactly
        self.add(f"{prefix}.gates", 2 * dr, dr, ("dense",), "state",
                 param_nnz=2 * dr * dr, neuron_model=self.recurrent_neuron)
        self.add(f"{prefix}.out", dr, d, ("dense",), "param",
                 param_nnz=dr * d)

    def head(self, d: int, vocab: int) -> None:
        self._whole("the LM head")
        self.add("head", d, vocab, ("dense",), "head", param_nnz=vocab * d)


def _attn_context(window: int | None, seq_len: int) -> int:
    return min(window, seq_len) if window else seq_len


def lowering_spec(cfg, *, seq_len: int = DEFAULT_SEQ_LEN,
                  recurrent_neuron: str = "ssm",
                  share: tuple[int, int] | None = None
                  ) -> tuple[list[LayerSpec], list[AttnSpec]]:
    """Pure-arithmetic lowering plan for ``cfg`` (no weights built).
    ``share=(index, n)``: partition ``index`` of ``n`` of one period of the
    layer pattern (see the module docstring)."""
    if share is not None:
        index, n = share
        if not (isinstance(cfg, ModelCfg) and cfg.pattern
                and 0 <= index < n):
            raise ValueError(f"share {share} needs a ModelCfg with a "
                             "pattern and 0 <= index < n")
    lo = _Lowering(seq_len, recurrent_neuron, share)
    if isinstance(cfg, EncDecCfg):
        d, H, K, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        for i in range(cfg.n_enc_layers):
            # streaming encoder: one new frame per step, full-frame context
            lo.attn(f"enc{i}.attn", d, H, K, hd, seq=cfg.n_frames,
                    causal=False)
            lo.mlp(f"enc{i}.mlp", d, cfg.d_ff)
        for i in range(cfg.n_dec_layers):
            lo.attn(f"dec{i}.attn", d, H, K, hd, seq=seq_len, causal=True)
            lo.attn(f"dec{i}.xattn", d, H, K, hd, seq=cfg.n_frames,
                    causal=False, cross=True)
            lo.mlp(f"dec{i}.mlp", d, cfg.d_ff)
        lo.head(d, cfg.vocab_size)
        return lo.specs, lo.attn_specs
    if not isinstance(cfg, ModelCfg):
        raise TypeError(f"cannot lower {type(cfg).__name__}; expected "
                        "ModelCfg, EncDecCfg, or a registry arch id")
    d = cfg.d_model
    blocks = cfg.pattern if share is not None else cfg.all_blocks()
    for bi, blk in enumerate(blocks):
        prefix = f"b{bi}"
        if blk.kind == "attn":
            lo.attn(f"{prefix}.attn", d, cfg.n_heads, cfg.n_kv_heads,
                    cfg.head_dim, seq=_attn_context(blk.window, seq_len),
                    window=blk.window, softcap=cfg.attn_softcap)
        elif blk.kind == "ssd":
            lo.ssd(f"{prefix}.ssd", d, blk.ssd)
        elif blk.kind == "rglru":
            lo.rglru(f"{prefix}.rglru", d, blk.rglru)
        else:
            raise ValueError(f"unknown block kind {blk.kind!r}")
        if blk.moe is not None:
            lo.moe(f"{prefix}.moe", d, blk.moe)
        elif blk.d_ff:
            lo.mlp(f"{prefix}.mlp", d, blk.d_ff)
    if share is None:
        lo.head(d, cfg.vocab_size)
    return lo.specs, lo.attn_specs


def excluded_params(cfg) -> int:
    """Parameters the lowering folds away (norms, convs, scalar gains) or
    absorbs into the network input (untied embeddings).  The frontend
    identity — asserted by the property suite — is::

        sum(spec.param_nnz) + excluded_params(cfg) == cfg.param_count()
    """
    d = cfg.d_model
    if isinstance(cfg, EncDecCfg):
        # per-layer norms (enc 2, dec 3) + enc/dec final norms; embeddings
        # are tied to the lowered head
        return cfg.n_enc_layers * 2 * d + cfg.n_dec_layers * 3 * d + 2 * d
    total = d                                       # final norm
    if not cfg.tie_embeddings:
        total += cfg.vocab_size * d                 # input embedding table
    for blk in cfg.all_blocks():
        total += d                                  # mixer pre-norm
        if blk.moe is not None or blk.d_ff:
            total += d                              # mlp pre-norm
        if blk.post_norms:
            total += 2 * d
        if blk.kind == "attn":
            if cfg.qk_norm:
                total += 2 * cfg.head_dim
        elif blk.kind == "ssd":
            s = blk.ssd
            h = s.d_inner // s.head_dim
            total += s.d_conv * (s.d_inner + 2 * s.n_groups * s.d_state)
            total += 3 * h + s.d_inner              # A_log/D/dt_bias + norm
        elif blk.kind == "rglru":
            total += blk.rglru.d_conv * blk.rglru.d_rnn + blk.rglru.d_rnn
    return total


# ================================================================== compile

@dataclasses.dataclass
class CompiledNetwork:
    """A priceable SimNetwork plus the arithmetic it was compiled from."""

    net: SimNetwork
    cfg: object                     # ModelCfg | EncDecCfg
    name: str
    arch_id: str | None
    family: str | None
    seq_len: int
    specs: list[LayerSpec]
    attn_specs: list[AttnSpec]
    share: tuple[int, int] | None = None

    @property
    def d_model(self) -> int:
        return self.net.in_size

    def param_layer_nnz(self) -> int:
        """Total parameter-bearing synapses (== param_count - excluded)."""
        return sum(s.param_nnz for s in self.specs)

    def macs_per_token(self) -> int:
        """Exact per-timestep MAC total of the dense-activity pipeline."""
        if any(s.macs_per_token is None for s in self.specs):
            raise ValueError("the MACs behind a router that holds some of "
                             "the experts depend on the routing")
        return sum(s.macs_per_token for s in self.specs)

    def inputs(self, steps: int, *, density: float = 1.0,
               seed: int = 0) -> np.ndarray:
        """(steps, d_model) embedded-token stream for the compiled net."""
        return make_inputs(self.net.in_size, density, steps, seed)


def _resolve(arch, smoke: bool):
    """(cfg, name, arch_id, family) from an arch id or a raw config."""
    if isinstance(arch, str):
        from repro.configs import registry
        entry = registry.get(arch)
        cfg = entry.smoke() if smoke else entry.config
        return cfg, cfg.name, entry.arch_id, entry.family
    return arch, arch.name, None, None


def _resolve_densities(act_density, n_layers: int) -> list[float | None]:
    """Per-layer message densities from a scalar, a per-layer schedule (any
    length — resampled over normalized depth, the trained analog of
    ``benchmarks.workloads.schedule``), or a
    :class:`~repro.sparsity.profile.SparsityProfile`."""
    if act_density is None:
        return [None] * n_layers
    if hasattr(act_density, "densities_for"):          # SparsityProfile
        return [float(d) for d in act_density.densities_for(n_layers)]
    if isinstance(act_density, (int, float)):
        return [float(act_density)] * n_layers
    seq = np.asarray(act_density, np.float64)
    if seq.ndim != 1 or seq.size == 0:
        raise ValueError("act_density schedule must be a non-empty 1-D "
                         f"sequence; got shape {seq.shape}")
    if seq.size == n_layers:
        return [float(d) for d in seq]
    if seq.size == 1:
        return [float(seq[0])] * n_layers
    src = np.linspace(0.0, 1.0, seq.size)
    dst = np.linspace(0.0, 1.0, n_layers)
    return [float(d) for d in np.interp(dst, src, seq)]


def _ranges(ranges: tuple) -> np.ndarray:
    return np.concatenate([np.arange(a, b) for a, b in ranges])


def _uniform(seed: int, layer: int, rows: np.ndarray,
             cols: np.ndarray) -> np.ndarray:
    """(len(rows), len(cols)) float64 uniforms in [0, 1), one per synapse,
    a function of the seed, the layer and the synapse's (row, col) alone:
    SplitMix64 of their combination."""
    u64 = np.uint64
    with np.errstate(over="ignore"):
        key = (u64(seed % 2**64) * u64(0x9E3779B97F4A7C15)
               + u64(layer) * u64(0xD1B54A32D192ED03))
        z = (key + (rows.astype(u64)[:, None] << u64(32))
             + cols.astype(u64)[None, :] + u64(0x9E3779B97F4A7C15))
        z = (z ^ (z >> u64(30))) * u64(0xBF58476D1CE4E5B9)
        z = (z ^ (z >> u64(27))) * u64(0x94D049BB133111EB)
        z = z ^ (z >> u64(31))
    return (z >> u64(11)).astype(np.float64) * 2.0 ** -53


def _share_weights(spec: LayerSpec, uncut: LayerSpec, seed: int,
                   layer: int) -> np.ndarray:
    """A share's weights: each synapse drawn from its indices in the uncut
    layer, so that every share of the layer holds the same values there."""
    rows, cols = _ranges(spec.rows), _ranges(spec.cols)
    scale = 0.5 / np.sqrt(max(1.0, uncut.nnz / uncut.width))
    w = np.empty((spec.fanin, spec.width), np.float32)
    for r0 in range(0, spec.fanin, 256):
        v = 2.0 * _uniform(seed, layer, rows[r0:r0 + 256], cols) - 1.0
        w[r0:r0 + 256] = np.where(v >= 0, 1.0, -1.0) * (0.5 + np.abs(v)) \
            * scale
    return w * _structure_mask(spec)


def _build_layer(spec: LayerSpec, rng: np.random.Generator,
                 act_density: float | None,
                 weights: np.ndarray | None = None) -> SimLayer:
    if weights is not None:
        w = weights
    else:
        mask = _structure_mask(spec)
        # weight magnitudes bounded away from zero so nnz (hence every
        # counter) is exactly the structural count; scale keeps the
        # forced-active message magnitudes stable across deep stacks
        scale = 0.5 / np.sqrt(max(1.0, spec.nnz / spec.width))
        vals = rng.normal(0.0, 1.0, (spec.fanin, spec.width))
        w = np.where(vals >= 0, 1.0, -1.0) * (0.5 + np.abs(vals)) * scale
        w = (w * mask).astype(np.float32)
    gate = _structure_gate(spec)
    if act_density is not None:
        live = np.nonzero(gate)[0] if gate is not None \
            else np.arange(spec.width)
        keep = int(round(act_density * live.size))
        g = np.zeros(spec.width, np.float32)
        if keep > 0:
            g[rng.choice(live, size=keep, replace=False)] = 1.0
        gate = g
    sd = spec.neuron_model == "sd_relu"
    return SimLayer(
        name=spec.name, kind="fc", weights=w,
        neuron_model=spec.neuron_model, msg_gate=gate,
        force_active=not sd, decay=0.5,
        threshold=0.05 if sd else 0.0, sends_deltas=sd, router=spec.router)


def compile_network(arch, *, seq_len: int = DEFAULT_SEQ_LEN,
                    smoke: bool = True, seed: int = 0,
                    act_density=None,
                    recurrent_neuron: str = "ssm",
                    verify_attention: bool = False,
                    share: tuple[int, int] | None = None) -> CompiledNetwork:
    """Compile a registry arch id (or raw config) into a CompiledNetwork.

    ``arch``: a ``repro.configs.registry`` id (``smoke=True`` selects the
    arch's smoke config, ``False`` the full assigned config) or a
    ``ModelCfg`` / ``EncDecCfg`` instance.  ``seq_len`` sets the
    steady-state decode context (attention layers price
    ``min(window, seq_len)`` cache positions).  ``act_density`` programs an
    exact message density on top of the structural gates (None = the dense
    token pipeline, the counter-exact default); it accepts a scalar, a
    per-layer density schedule (any length — resampled over normalized
    depth), or a trained :class:`~repro.sparsity.profile.SparsityProfile`
    (its measured densities drive the lowered layers — the trained
    replacement for synthetic schedules).  ``verify_attention`` runs
    the real flash_attn kernel against its oracle at every lowered
    attention shape before returning.  ``share=(index, n)`` compiles what
    partition ``index`` of ``n`` holds of one period (module docstring);
    its weights are those of the same synapses in ``share=(0, 1)``.
    """
    cfg, name, arch_id, family = _resolve(arch, smoke)
    kw = dict(seq_len=seq_len, recurrent_neuron=recurrent_neuron)
    specs, attn_specs = lowering_spec(cfg, share=share, **kw)
    rng = np.random.default_rng(seed)
    dens = _resolve_densities(act_density, len(specs))
    if share is None:
        layers = [_build_layer(s, rng, d) for s, d in zip(specs, dens)]
    else:
        uncut = lowering_spec(cfg, share=(0, 1), **kw)[0]
        layers = [_build_layer(s, rng, d, _share_weights(s, u, seed, i))
                  for i, (s, u, d) in enumerate(zip(specs, uncut, dens))]
    net = SimNetwork(layers=layers, in_size=cfg.d_model)
    compiled = CompiledNetwork(
        net=net, cfg=cfg, name=name, arch_id=arch_id, family=family,
        seq_len=seq_len, specs=specs, attn_specs=attn_specs, share=share)
    if verify_attention:
        for spec in attn_specs:
            out, ref = attention_probe(spec, seed=seed)
            err = float(np.max(np.abs(out - ref))) if out.size else 0.0
            if err > 2e-4:
                raise ValueError(
                    f"flash_attn kernel diverged from oracle at {spec} "
                    f"(max err {err:.2e})")
    return compiled


def attention_probe(spec: AttnSpec, *, seed: int = 0
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Run the Pallas flash_attn kernel and its jnp oracle at exactly the
    (heads, head_dim, seq) shape ``spec`` was lowered for; returns
    ``(kernel_out, oracle_out)`` as float32 arrays."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attn import flash_attention, flash_attention_ref

    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (1, spec.seq, spec.heads, spec.head_dim),
                          jnp.float32)
    k = jax.random.normal(kk, (1, spec.seq, spec.kv_heads, spec.head_dim),
                          jnp.float32)
    v = jax.random.normal(kv, (1, spec.seq, spec.kv_heads, spec.head_dim),
                          jnp.float32)
    kw = dict(causal=spec.causal, window=spec.window, softcap=spec.softcap)
    out = np.asarray(flash_attention(q, k, v, **kw), np.float32)
    ref = np.asarray(flash_attention_ref(q, k, v, **kw), np.float32)
    return out, ref
