"""Network abstraction executed by the neuromorphic simulator.

A :class:`SimNetwork` is a feed-forward stack of :class:`SimLayer` s.  Each
layer owns its synaptic weights, neuron model (ReLU / IF-spiking / sigma-delta
ReLU / SSM state), optional message gate (used to *program* exact activation
sparsity, as the paper does in §V-A by "explicitly toggling neuron activation
messaging on and off"), optional :class:`Router` (a routed-expert gate that
its own router neurons set at every step), and weight format (dense/sparse,
Fig. 4).

Two execution engines produce identical event counts:

* **step-major** (``step`` / ``run``): one timestep at a time, layer by
  layer — the reference implementation, kept for parity checking.
* **layer-major, time-batched** (``step_batch`` / ``run_batch``): for each
  layer in order, the full ``(T, n_in)`` message matrix is consumed at once.
  This is *exact* for feed-forward stacks because within a timestep messages
  flow strictly downstream (layer ``l`` at step ``t`` sees only layer
  ``l-1``'s step-``t`` output), so the time axis of a stateless layer is
  embarrassingly parallel: ReLU layers become a single GEMM and conv layers
  a single batched ``conv_general_dilated`` with batch = T.  Stateful
  neurons (IF / sigma-delta / SSM) carry state only *along* time within one
  layer, so they reduce to a tight vectorized recurrence over T applied to
  the whole ``(T, n)`` pre-activation block.  Sigma-delta input
  reconstruction is a cumulative sum over the time axis.

The per-layer synaptic forward itself (the pre-activation GEMM / conv plus
the exact MAC / fetch counter maps) is pluggable: both engines delegate it
to a :class:`repro.neuromorphic.compute.LayerCompute` backend (``compute=``
on :meth:`SimLayer.step` / :meth:`SimLayer.step_batch` /
:meth:`SimNetwork.run` / :meth:`SimNetwork.run_batch`).  ``"dense"`` — the
original jnp GEMM / ``conv_general_dilated`` math, bit-exact — is the
default; ``"event"`` routes the forward through the event-driven Pallas
kernel path, where work scales with activation density.  Neuron-state
recurrences and message gating stay here: they are the neuron model, not
the synaptic compute.

The cost model in :mod:`repro.neuromorphic.timestep` turns the exact counter
maps of either engine into per-core times and energies.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.neuromorphic import compute as _compute


@dataclasses.dataclass
class CounterMaps:
    """Exact per-timestep event counts for one layer.

    Per-neuron maps are flattened in *partition order* (channel-major for
    conv layers) so contiguous core ranges are meaningful.
    """

    msgs_in: float                 # input messages arriving this step
    macs: np.ndarray               # nnz multiply-accumulates per neuron
    fetches_dense: np.ndarray      # dense-format weight fetches per neuron
    msgs_out: np.ndarray           # 0/1 message emitted per neuron
    acts_evented: np.ndarray       # 0/1 neuron received >= 1 synop


@dataclasses.dataclass
class BatchCounters:
    """Exact event counts for one layer over ALL timesteps (time-major).

    The layer-major engine's counterpart of :class:`CounterMaps`: per-neuron
    maps are ``(T, n_neurons)`` arrays in the same partition order, so one
    segment-sum per layer aggregates every timestep at once.
    """

    msgs_in: np.ndarray            # (T,) input messages per step
    macs: np.ndarray               # (T, n) nnz multiply-accumulates
    fetches_dense: np.ndarray      # (T, n) dense-format weight fetches
    msgs_out: np.ndarray           # (T, n) 0/1 message emitted
    acts_evented: np.ndarray       # (T, n) 0/1 neuron received >= 1 synop

    def step_view(self, t: int) -> CounterMaps:
        """Per-step view, for parity checks against the step-major engine."""
        return CounterMaps(
            msgs_in=float(self.msgs_in[t]), macs=self.macs[t],
            fetches_dense=self.fetches_dense[t], msgs_out=self.msgs_out[t],
            acts_evented=self.acts_evented[t])


@dataclasses.dataclass(frozen=True)
class Router:
    """A routed-expert message gate over an fc layer's own neurons.

    The layer's neurons are laid out ``[held experts | shared experts |
    router]``: ``len(held)`` blocks of ``width`` neurons for the routed
    experts held here (expert ids ``held``, in that order), ``n_shared``
    blocks of ``width`` for the always-on experts, and ``n_experts`` router
    neurons.  At every step the router neurons' pre-activations ``r`` give
    sigmoid scores; the ``top_k`` largest of all ``n_experts`` (ties to the
    lower id) are renormalised to sum to one and multiplied by ``scale``.
    A held expert's messages are scaled by its weight, or silenced when it
    is not among the top ``top_k``; shared experts message unscaled; the
    router neurons never message (the gate consumes them where they are).
    Scaling an up-projection's messages is exact for the linear
    down-projection that reads them.
    """

    n_experts: int
    top_k: int
    width: int
    held: tuple[int, ...]
    n_shared: int = 0
    scale: float = 1.0

    @property
    def n_neurons(self) -> int:
        return (len(self.held) + self.n_shared) * self.width + self.n_experts

    def expert_weights(self, pre: np.ndarray) -> np.ndarray:
        """(T, n_experts) float32 routing weights: zero off the top_k."""
        r = np.asarray(pre, np.float32)[:, -self.n_experts:]
        scores = (1.0 / (1.0 + np.exp(-r.astype(np.float64)))
                  ).astype(np.float32)
        top = np.argsort(-scores, axis=1, kind="stable")[:, :self.top_k]
        w = np.take_along_axis(scores, top, axis=1)
        total = w[:, 0]
        for j in range(1, self.top_k):          # one fixed summation order
            total = total + w[:, j]
        out = np.zeros_like(scores)
        np.put_along_axis(out, top,
                          w / total[:, None] * np.float32(self.scale), axis=1)
        return out

    def gate(self, pre: np.ndarray) -> np.ndarray:
        """(T, n_neurons) float32 per-neuron message scale of a step block
        of the layer's pre-activations."""
        T = pre.shape[0]
        held = self.expert_weights(pre)[:, list(self.held)]
        return np.concatenate(
            [np.repeat(held, self.width, axis=1),
             np.ones((T, self.n_shared * self.width), np.float32),
             np.zeros((T, self.n_experts), np.float32)], axis=1)

    def apply(self, pre: np.ndarray, y: np.ndarray) -> np.ndarray:
        """``y`` gated, under the span ``sim.moe.gate`` with the count of
        held-expert messages let through as ``expert_msgs``."""
        with tracing.span("sim.moe.gate"):
            y = y * self.gate(pre)
            n_routed = len(self.held) * self.width
            tracing.count("expert_msgs",
                          int(np.count_nonzero(y[:, :n_routed])))
            return y


@dataclasses.dataclass
class SimLayer:
    """One layer mapped onto one-or-more neurocores."""

    name: str
    kind: str                       # 'fc' | 'conv'
    weights: np.ndarray             # fc: (fanin, nout); conv: (kh, kw, cin, cout)
    bias: np.ndarray | None = None
    neuron_model: str = "relu"      # 'relu' | 'if' | 'sd_relu' | 'ssm'
    weight_format: str | None = None   # None -> platform default
    msg_gate: np.ndarray | None = None # 0/1 per neuron; programs act sparsity
    threshold: float = 0.0          # IF spike / sigma-delta threshold
    decay: float = 0.9              # SSM state decay (diag A)
    stride: int = 1                 # conv only
    in_hw: tuple[int, int] | None = None   # conv only: input spatial dims
    force_active: bool = False      # characterization mode: all neurons emit
    sends_deltas: bool = False      # sigma-delta layers emit deltas
    router: Router | None = None    # fc only: routed-expert message gate

    def __post_init__(self):
        if self.router is not None and (
                self.kind != "fc" or self.router.n_neurons != self.n_neurons):
            raise ValueError(
                f"layer {self.name}: a router of {self.router.n_neurons} "
                f"neurons needs an fc layer of that width")

    # ------------------------------------------------------------------ sizes
    @property
    def n_neurons(self) -> int:
        if self.kind == "fc":
            return int(self.weights.shape[1])
        kh, kw, cin, cout = self.weights.shape
        oh, ow = self.out_hw
        return int(cout * oh * ow)

    @property
    def out_hw(self) -> tuple[int, int]:
        assert self.kind == "conv" and self.in_hw is not None
        h, w = self.in_hw
        return (h // self.stride, w // self.stride)   # SAME padding

    @property
    def n_weights(self) -> int:
        return int(np.prod(self.weights.shape))

    @property
    def fanin(self) -> int:
        if self.kind == "fc":
            return int(self.weights.shape[0])
        kh, kw, cin, _ = self.weights.shape
        return int(kh * kw * cin)

    def weights_per_core(self, n_cores: int) -> int:
        """Synaptic memory words needed per core under an n_cores split
        (fc: neuron ranges; conv: output-channel ranges)."""
        if self.kind == "fc":
            per = -(-self.weights.shape[1] // n_cores)
            return int(self.weights.shape[0] * per)
        kh, kw, cin, cout = self.weights.shape
        per = -(-cout // n_cores)
        return int(kh * kw * cin * per)

    # --------------------------------------------- cached derived weight data
    # Caches are keyed on the identity of the weights array (not just the
    # layer object), so rebinding ``layer.weights`` — e.g. SparsityProfile
    # applying a mask to an already-simulated layer — invalidates every
    # derived structure instead of serving stale data.

    @property
    def w_mask(self) -> np.ndarray:
        """0/1 mask of nonzero weights (fc MAC counting)."""
        return _compute.derived_from_weights(
            self, "_w_mask", lambda l: (l.weights != 0).astype(np.float32))

    @property
    def w_nnz(self) -> int:
        """Number of nonzero synaptic weights."""
        return _compute.derived_from_weights(
            self, "_w_nnz", lambda l: int((l.weights != 0).sum()))

    @property
    def _conv_kernels(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Device-resident conv kernels: (weights, nnz mask)."""
        def build(l):
            wj = jnp.asarray(l.weights)
            return wj, (wj != 0).astype(jnp.float32)
        return _compute.derived_from_weights(self, "_conv_kernels_cache",
                                             build)

    def init_state(self) -> dict[str, np.ndarray]:
        n = self.n_neurons
        st: dict[str, Any] = {}
        if self.neuron_model == "if":
            st["v"] = np.zeros(n, np.float32)
        elif self.neuron_model == "sd_relu":
            st["y_sent"] = np.zeros(n, np.float32)
        elif self.neuron_model == "ssm":
            st["x"] = np.zeros(n, np.float32)
        return st

    # ------------------------------------------------------------------ step
    def step(self, x_in: np.ndarray, state: dict[str, np.ndarray],
             in_acc: np.ndarray | None, *,
             compute=None) -> tuple[np.ndarray, dict, CounterMaps,
                                    np.ndarray | None]:
        """One timestep: consume input messages ``x_in``, produce output
        messages, update neuron state, and count events exactly.

        ``in_acc`` reconstructs the upstream activation when the upstream
        layer sends deltas (sigma-delta); otherwise it is None and the raw
        messages are the activation.  ``compute`` selects the synaptic
        backend (:func:`repro.neuromorphic.compute.get_compute`); the
        forward runs through the backend's batched contract at T = 1.
        """
        cc = _compute.get_compute(compute)
        x_in = np.asarray(x_in, np.float32)
        if in_acc is not None:
            in_acc = in_acc + x_in          # delta reconstruction
            x_eff = in_acc
        else:
            x_eff = x_in

        act_mask = (x_in != 0).astype(np.float32)   # events on the wire
        msgs_in = float(act_mask.sum())

        pre, macs, fetches_dense = cc.forward(
            self, x_eff[None, :], act_mask[None, :],
            np.asarray([msgs_in], np.float32))
        pre = pre[0]
        macs, fetches_dense = macs[0], fetches_dense[0]

        if self.bias is not None:
            pre = pre + self.bias

        y_msgs, state = self._neuron(pre, state)
        if self.msg_gate is not None:
            y_msgs = y_msgs * self.msg_gate
        if self.router is not None:
            y_msgs = self.router.apply(pre[None, :], y_msgs[None, :])[0]
        msgs_out = (y_msgs != 0).astype(np.float32)

        counters = CounterMaps(
            msgs_in=msgs_in,
            macs=np.asarray(macs, np.float32).reshape(-1),
            fetches_dense=np.asarray(fetches_dense, np.float32).reshape(-1),
            msgs_out=msgs_out.reshape(-1),
            acts_evented=(np.asarray(macs).reshape(-1) > 0).astype(np.float32),
        )
        return y_msgs, state, counters, in_acc

    # ------------------------------------------------------- batched step
    def step_batch(self, x_in: np.ndarray, state: dict[str, np.ndarray],
                   in_acc: np.ndarray | None, *,
                   compute=None) -> tuple[np.ndarray, dict, BatchCounters,
                                          np.ndarray | None]:
        """All T timesteps at once: consume the full ``(T, n_in)`` message
        matrix, produce ``(T, n)`` output messages, and count events exactly.

        Equivalent to T calls of :meth:`step`: the input-side delta
        reconstruction is a cumulative sum over time, the synaptic forward is
        one GEMM / one batched conv (through the selected
        :class:`~repro.neuromorphic.compute.LayerCompute` backend), and
        neuron state advances in a vectorized recurrence over T.  Counters
        and neuron recurrences use the same float op order as the
        step-major path (bit-identical); the delta accumulator matches bit
        for bit when it starts at zero, which :meth:`SimNetwork.init_accs`
        guarantees for every run — a caller chaining ``step_batch`` from a
        *nonzero* accumulator gets ``acc + cumsum(x)``, equal to the
        step-major chain only to within float32 rounding.
        """
        cc = _compute.get_compute(compute)
        x_in = np.asarray(x_in, np.float32)
        if x_in.ndim != 2:
            raise ValueError(f"step_batch needs (T, n_in), got {x_in.shape}")

        act_mask = (x_in != 0).astype(np.float32)   # events on the wire
        msgs_in = act_mask.sum(axis=1)              # (T,)

        with tracing.span("sim.synaptic"):
            if in_acc is not None:
                # delta reconstruction (acc_t = acc_0 + sum_{k<=t} x_k) is
                # the backend's to own: the base implementation is the
                # bit-exact dense time cumsum; event backends reconstruct in
                # temporal tiles so quiet windows compact away before the
                # matmul.
                pre, macs, fetches_dense, new_acc = cc.delta_forward(
                    self, x_in, in_acc, act_mask, msgs_in)
            else:
                new_acc = None
                pre, macs, fetches_dense = cc.forward(self, x_in, act_mask,
                                                      msgs_in)

        if self.bias is not None:
            pre = pre + self.bias

        with tracing.span("sim.neuron"):
            y_msgs, state = self._neuron_batch(pre, state)
        if self.msg_gate is not None:
            y_msgs = y_msgs * self.msg_gate
        if self.router is not None:
            y_msgs = self.router.apply(pre, y_msgs)
        msgs_out = (y_msgs != 0).astype(np.float32)

        counters = BatchCounters(
            msgs_in=msgs_in.astype(np.float64),
            macs=np.asarray(macs, np.float32),
            fetches_dense=np.asarray(fetches_dense, np.float32),
            msgs_out=msgs_out,
            acts_evented=(np.asarray(macs) > 0).astype(np.float32),
        )
        return y_msgs, state, counters, new_acc

    # ------------------------------------------------------------ neuron fns
    def _neuron(self, pre: np.ndarray, state: dict) -> tuple[np.ndarray, dict]:
        if self.neuron_model == "relu":
            y = np.maximum(pre, 0.0)
            if self.force_active:
                y = np.abs(pre) + 1.0
            return y, state
        if self.neuron_model == "if":
            v = state["v"] + pre
            thr = max(self.threshold, 1e-6)
            spikes = (v >= thr).astype(np.float32)
            state = dict(state, v=v - thr * spikes)
            return spikes, state
        if self.neuron_model == "sd_relu":
            y = np.maximum(pre, 0.0)
            delta = y - state["y_sent"]
            thr = max(self.threshold, 1e-9)
            q = np.where(np.abs(delta) >= thr,
                         np.round(delta / thr) * thr, 0.0).astype(np.float32)
            state = dict(state, y_sent=state["y_sent"] + q)
            return q, state
        if self.neuron_model == "ssm":
            x = self.decay * state["x"] + pre
            state = dict(state, x=x)
            y = np.abs(x) + 1.0 if self.force_active else x
            return y.astype(np.float32), state
        raise ValueError(f"unknown neuron model {self.neuron_model}")

    def _neuron_batch(self, pre: np.ndarray,
                      state: dict) -> tuple[np.ndarray, dict]:
        """Neuron update over the whole (T, n) pre-activation block.

        Stateless models vectorize fully; stateful models run a recurrence
        over T with every per-step operation vectorized across the n neurons
        (identical float op order to T sequential :meth:`_neuron` calls).
        """
        T = pre.shape[0]
        if self.neuron_model == "relu":
            y = np.maximum(pre, 0.0)
            if self.force_active:
                y = np.abs(pre) + 1.0
            return y, state
        if self.neuron_model == "if":
            thr = max(self.threshold, 1e-6)
            v = state["v"]
            y = np.empty_like(pre)
            for t in range(T):
                v = v + pre[t]
                spikes = (v >= thr).astype(np.float32)
                v = v - thr * spikes
                y[t] = spikes
            return y, dict(state, v=v)
        if self.neuron_model == "sd_relu":
            relu = np.maximum(pre, 0.0)
            thr = max(self.threshold, 1e-9)
            y_sent = state["y_sent"]
            y = np.empty_like(pre)
            for t in range(T):
                delta = relu[t] - y_sent
                q = np.where(np.abs(delta) >= thr,
                             np.round(delta / thr) * thr,
                             0.0).astype(np.float32)
                y_sent = y_sent + q
                y[t] = q
            return y, dict(state, y_sent=y_sent)
        if self.neuron_model == "ssm":
            x = state["x"]
            y = np.empty_like(pre)
            for t in range(T):
                x = self.decay * x + pre[t]
                y[t] = np.abs(x) + 1.0 if self.force_active else x
            return y, dict(state, x=x)
        raise ValueError(f"unknown neuron model {self.neuron_model}")

@dataclasses.dataclass
class SimNetwork:
    """Feed-forward stack of SimLayers with per-layer state threading."""

    layers: list[SimLayer]
    in_size: int

    def init_states(self) -> list[dict]:
        return [l.init_state() for l in self.layers]

    def init_accs(self) -> list[np.ndarray | None]:
        """Delta-reconstruction accumulators at each layer boundary: layer i
        needs one iff layer i-1 (or the network input) sends deltas."""
        accs: list[np.ndarray | None] = []
        prev_sends_deltas = False
        prev_n = self.in_size
        for l in self.layers:
            accs.append(np.zeros(prev_n, np.float32) if prev_sends_deltas else None)
            prev_sends_deltas = l.sends_deltas or l.neuron_model == "sd_relu"
            prev_n = l.n_neurons
        return accs

    def step(self, x: np.ndarray, states: list[dict],
             accs: list[np.ndarray | None], *,
             compute=None) -> tuple[np.ndarray, list, list,
                                    list[CounterMaps]]:
        cc = _compute.get_compute(compute)
        counters: list[CounterMaps] = []
        new_states, new_accs = [], []
        cur = np.asarray(x, np.float32)
        for layer, st, acc in zip(self.layers, states, accs):
            cur, st, cnt, acc = layer.step(cur, st, acc, compute=cc)
            counters.append(cnt)
            new_states.append(st)
            new_accs.append(acc)
        return cur, new_states, new_accs, counters

    def run(self, xs: np.ndarray, *,
            compute=None) -> tuple[np.ndarray, list[list[CounterMaps]]]:
        """Step-major reference run: (T, in_size) inputs -> (T, out) outputs
        and per-timestep per-layer counters."""
        cc = _compute.get_compute(compute)
        states, accs = self.init_states(), self.init_accs()
        outs, all_counters = [], []
        for t in range(xs.shape[0]):
            y, states, accs, counters = self.step(xs[t], states, accs,
                                                  compute=cc)
            outs.append(np.asarray(y).reshape(-1))
            all_counters.append(counters)
        return np.stack(outs), all_counters

    def run_batch(self, xs: np.ndarray, *,
                  compute=None) -> tuple[np.ndarray, list[BatchCounters]]:
        """Layer-major run: (T, in_size) inputs -> (T, out) outputs and one
        :class:`BatchCounters` per layer.  Exactly equivalent to :meth:`run`
        (see the module docstring) but visits each layer once with the full
        time batch instead of T times.  ``compute`` selects the synaptic
        backend for every layer (resolved once per run)."""
        with tracing.span("sim.run_batch"):
            cc = _compute.get_compute(compute)
            states, accs = self.init_states(), self.init_accs()
            cur = np.asarray(xs, np.float32)
            all_counters: list[BatchCounters] = []
            for i, layer in enumerate(self.layers):
                cur, states[i], cnt, accs[i] = layer.step_batch(
                    cur, states[i], accs[i], compute=cc)
                all_counters.append(cnt)
            T = xs.shape[0]
            return np.asarray(cur).reshape(T, -1), all_counters


# ====================================================================== builders

def _exact_density_mask(shape: tuple[int, ...], density: float,
                        rng: np.random.Generator) -> np.ndarray:
    """0/1 mask with an exact (rounded) fraction of ones, uniformly placed."""
    n = int(np.prod(shape))
    k = int(round(density * n))
    flat = np.zeros(n, np.float32)
    if k > 0:
        flat[rng.choice(n, size=k, replace=False)] = 1.0
    return flat.reshape(shape)


def fc_network(sizes: list[int], *, weight_density: float | list[float] = 1.0,
               neuron_model: str = "relu", seed: int = 0,
               weight_format: str | None = None) -> SimNetwork:
    """Random fully-connected network with exact per-layer weight density."""
    rng = np.random.default_rng(seed)
    wd = ([weight_density] * (len(sizes) - 1)
          if np.isscalar(weight_density) else list(weight_density))
    layers = []
    for i in range(len(sizes) - 1):
        w = rng.normal(0, 1.0 / np.sqrt(sizes[i]),
                       (sizes[i], sizes[i + 1])).astype(np.float32)
        w *= _exact_density_mask(w.shape, wd[i], rng)
        layers.append(SimLayer(name=f"fc{i}", kind="fc", weights=w,
                               neuron_model=neuron_model,
                               weight_format=weight_format))
    return SimNetwork(layers=layers, in_size=sizes[0])


def programmed_fc_network(sizes: list[int], *, weight_densities: list[float],
                          act_densities: list[float], seed: int = 0,
                          weight_format: str | None = None,
                          neuron_model: str = "relu") -> SimNetwork:
    """Characterization-mode network (§V-A): weight density exact per layer,
    activation (message) density exactly *programmed* via per-neuron message
    gates with all neurons forced active — the simulator analog of the
    paper's "explicitly toggling neuron activation messaging on and off"."""
    assert len(weight_densities) == len(sizes) - 1
    assert len(act_densities) == len(sizes) - 1
    rng = np.random.default_rng(seed)
    layers = []
    for i in range(len(sizes) - 1):
        w = rng.normal(0, 1.0 / np.sqrt(sizes[i]),
                       (sizes[i], sizes[i + 1])).astype(np.float32)
        w *= _exact_density_mask(w.shape, weight_densities[i], rng)
        gate = _exact_density_mask((sizes[i + 1],), act_densities[i], rng)
        layers.append(SimLayer(name=f"fc{i}", kind="fc", weights=w,
                               neuron_model=neuron_model, msg_gate=gate,
                               force_active=True, weight_format=weight_format))
    return SimNetwork(layers=layers, in_size=sizes[0])


def make_inputs(n: int, density: float, steps: int, seed: int = 0) -> np.ndarray:
    """(steps, n) inputs with exact per-step message density.

    One batched draw: values come from a single (steps, n) normal sample and
    the per-step masks from one row-wise argsort of uniform noise (each row
    keeps exactly ``round(density * n)`` ones, uniformly placed)."""
    rng = np.random.default_rng(seed)
    vals = np.abs(rng.normal(1.0, 0.2, (steps, n))).astype(np.float32)
    k = int(round(density * n))
    mask = np.zeros((steps, n), np.float32)
    if k > 0:
        order = rng.random((steps, n)).argsort(axis=1)
        np.put_along_axis(mask, order[:, :k], 1.0, axis=1)
    return vals * mask
