"""nemotron-3-nano-30b-a3b [hybrid] — 52L d_model=2688: Mamba-2, GQA, MoE.

[hf: nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, config.json].  The layer
string ``MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME`` holds 23
Mamba-2 layers (M: 64 heads x 64, ssm_state 128, 8 groups, conv 4), 23
MoE layers (E: 128 routed experts of width 1,856, top-6, and one shared
expert of width 3,712; non-gated relu^2 experts; a sigmoid router whose
top-6 weights are renormalised and scaled by 2.5) and 6 GQA layers
(*: 32 query heads, 2 KV heads, head_dim 128).  Vocabulary 131,072,
untied.  31.6B parameters, 3.2B active without the input embedding.

Each E follows an M or a *, so the string maps onto ``BlockCfg`` s with no
new block kind: a Mamba-2 or attention mixer with the MoE as its channel
MLP, or a Mamba-2 mixer alone.  The period ``MEMEM*E`` is
``[ssd+moe, ssd+moe, ssd, attn+moe]``, five times, then the remaining
``MEMEMEM*E MEMEMEME``.  Nemotron's one norm per layer is the mixer's or
the MLP's pre-norm here.

Departures, all in how the model is expressed, not in its sizes:

- the shared expert of width 3,712 is two shared experts of width 1,856:
  for non-gated experts that is the same function and the same parameters;
- the JAX model (``repro.models.moe``) routes with a softmax; the
  neuromorphic lowering (``repro.neuromorphic.frontend``) routes with the
  published sigmoid, renormalised top-6 and the 2.5 scale;
- the router's score-correction bias (128 per MoE layer) is zero and not
  counted;
- the Mamba-2 conv bias is not counted (as for every SSD arch here);
- in the neuromorphic lowering relu^2 runs as the ``relu`` neuron model,
  which messages on the same set (only the message values differ), and
  the depthwise conv and the norms are folded away, as for every arch.
"""

from repro.configs.shapes import FULL_ATTN_SHAPES
from repro.models.common import BlockCfg, ModelCfg, MoECfg, SSDCfg

ARCH_ID = "nemotron-3-nano-30b-a3b"
LAYERS = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
PERIOD = "MEMEM*E"


def blocks(layers: str, ssd: SSDCfg, moe: MoECfg) -> tuple[BlockCfg, ...]:
    """``BlockCfg`` s of a Nemotron-H layer string: M and * open a block,
    E becomes the MLP of the block before it."""
    out: list[BlockCfg] = []
    for ch in layers:
        if ch == "M":
            out.append(BlockCfg(kind="ssd", ssd=ssd))
        elif ch == "*":
            out.append(BlockCfg(kind="attn"))
        elif ch == "E":
            if not out or out[-1].moe is not None:
                raise ValueError(f"an E must follow an M or a * in {layers}")
            out[-1] = BlockCfg(kind=out[-1].kind, ssd=out[-1].ssd, moe=moe)
        else:
            raise ValueError(f"unknown layer {ch!r} in {layers}")
    return tuple(out)


_SSD = SSDCfg(d_inner=4096, head_dim=64, d_state=128, n_groups=8,
              chunk=128, d_conv=4)
_MOE = MoECfg(n_experts=128, top_k=6, d_ff=1856, n_shared_experts=2,
              glu=False, routed_scale=2.5, capacity_factor=1.25)
_PERIOD = blocks(PERIOD, _SSD, _MOE)
_REPEATS = 5
_SUFFIX = blocks(LAYERS[len(PERIOD) * _REPEATS:], _SSD, _MOE)

CONFIG = ModelCfg(
    name=ARCH_ID,
    d_model=2688, n_heads=32, n_kv_heads=2, head_dim=128,
    vocab_size=131_072,
    pattern=_PERIOD, n_repeats=_REPEATS, suffix=_SUFFIX,
    act_fn="relu2", rope_theta=10_000.0, norm_eps=1e-5,
)
assert CONFIG.all_blocks() == list(blocks(LAYERS, _SSD, _MOE))

SHAPES = FULL_ATTN_SHAPES


def smoke() -> ModelCfg:
    """One period at small widths, with the published ratios that a share
    cuts: two Mamba heads of a group per quarter, eight query heads over
    two KV heads, top-6 of 16 experts and two shared."""
    ssd = SSDCfg(d_inner=64, head_dim=8, d_state=16, n_groups=2, chunk=8)
    moe = MoECfg(n_experts=16, top_k=6, d_ff=16, n_shared_experts=2,
                 glu=False, routed_scale=2.5, capacity_factor=2.0)
    return ModelCfg(
        name="nemotron3-smoke", d_model=32, n_heads=8, n_kv_heads=2,
        head_dim=8, vocab_size=256,
        pattern=blocks(PERIOD, ssd, moe), n_repeats=1,
        act_fn="relu2", param_dtype="float32", compute_dtype="float32")
