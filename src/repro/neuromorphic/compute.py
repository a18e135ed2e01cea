"""Pluggable per-layer synaptic-compute backends for the simulator.

The simulator's hot path is the per-layer synaptic forward: consume the
``(T, n_in)`` effective-activation block, produce the ``(T, n_out)``
pre-activations plus the exact MAC / dense-fetch counter maps the cost
model prices.  This module is the seam that makes that forward pluggable —
:class:`SimLayer` (``repro.neuromorphic.network``) delegates every
pre-activation GEMM / conv to a :class:`LayerCompute` backend instead of
hard-coding dense math:

* ``"dense"`` (:class:`DenseCompute`, the default) — the original host GEMM
  and ``conv_general_dilated`` math, each conv layer run as one device
  program.  It is the bit-exact reference: every counter and every float op
  order is unchanged, so the
  engine-parity suites (``tests/test_sim_equivalence.py``) and the pricing
  caches are oblivious to the refactor.
* ``"event"`` (:class:`EventCompute`) — event-driven execution in the
  paper's sense: *"a message is only sent for a nonzero activation, and
  only its weights are fetched"*.  Work scales with the number of events
  instead of the dense shape.  Two kernel modes share one semantic
  contract (``y == x @ w`` exactly where skipped work is genuinely
  event-free, so outputs agree with dense to float roundoff and all
  integer counters agree exactly):

  - ``"pallas"`` — the block-sparse TPU kernel
    (:func:`repro.kernels.event_matmul.ops.event_matmul_pair`): (bm, bk)
    activation tiles with no events skip both the weight-tile DMA and the
    MXU issue.  Interpret mode is auto-selected on CPU backends, so CI
    executes the real kernel body on every push.
  - ``"gather"`` — the column-granular host expression of the same
    event contract: the time axis is cut into ``bm``-step tiles, each
    tile's *union of active input columns* is compacted, and only those
    columns' weight rows are fetched into one dense
    ``(bm, k_tile) @ (k_tile, n_out)`` contraction.  Weight fetches and
    MACs are proportional to activation density (the weight-row fetch is
    amortized over the whole tile) — the hardware-faithful fast path on
    hosts without an MXU.

  ``mode="auto"`` picks ``pallas`` on TPU/GPU backends and ``gather`` on
  CPU: the kernel where block-skipping pays, the density-proportional
  gather where interpret-mode overhead would bury it.

Conv layers run event-driven through an im2col view: a zero-copy
``sliding_window_view`` lowers the SAME-padded strided conv to a
``(T * oh * ow, cin * kh * kw)`` patch matrix, and the patch rows feed the
same event matmul as fc layers — window positions whose receptive field
holds no event fetch no weights, and input features (channel taps) that
are quiet across a tile are never contracted.  The conv win is therefore
largest for *structured* activation sparsity (quiet channels / feature
maps), mirroring the paper's CNN weight-format finding that structure is
what converts sparsity into skipped fetches.

Backends are selected per call (``compute=`` on ``simulate`` /
``precompute_pricing`` / ``SimEvaluator`` / ``SimNetwork.run_batch``) by
name, by instance, or by the process-wide :data:`DEFAULT_COMPUTE`
(``benchmarks/run.py --compute`` flips it globally, mirroring
``--engine``).  ``docs/kernels.md`` documents the kernel contracts;
``tests/test_compute_backends.py`` asserts the dense/event parity.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.kernels.event_matmul.ops import (event_matmul_pair,
                                            weight_block_occupancy)
from repro.kernels.sigma_delta.ops import window_reconstruct

#: Backend used when a ``compute=`` argument is omitted.  ``"dense"`` is the
#: bit-exact reference; ``benchmarks/run.py --compute`` overrides this
#: module attribute globally, the supported way to flip every simulation in
#: a process (same contract as ``timestep.DEFAULT_ENGINE``).
DEFAULT_COMPUTE = "dense"


def _to_device(*arrays, dtype=None) -> tuple:
    """``jnp.asarray`` of each array under the span ``kernel.put``,
    counting the bytes of every host array sent as ``h2d_bytes`` and of
    every operand already on the device as ``h2d_reused_bytes``; a None
    operand stays None."""
    with tracing.span("kernel.put"):
        out = tuple(None if a is None else jnp.asarray(a, dtype)
                    for a in arrays)
        for a, d in zip(arrays, out):
            if isinstance(a, np.ndarray):
                tracing.count("h2d_bytes", d.nbytes)
            elif isinstance(a, jax.Array):
                tracing.count("h2d_reused_bytes", d.nbytes)
    return out


def _to_host(*arrays) -> tuple:
    """``np.asarray`` of each device result under the span
    ``kernel.fetch``, where the host waits for the device, counting the
    bytes fetched as ``d2h_bytes``."""
    with tracing.span("kernel.fetch"):
        out = tuple(np.asarray(a) for a in arrays)
        tracing.count("d2h_bytes", sum(a.nbytes for a in out))
    return out


class LayerCompute:
    """Backend protocol: the per-layer synaptic forward over a time batch.

    Implementations provide :meth:`fc_forward` and :meth:`conv_forward`;
    both consume the full ``(T, n_in)`` effective-activation block plus the
    0/1 wire-event mask and per-step message counts, and return
    ``(pre, macs, fetches_dense)`` as ``(T, n_out)`` maps (channel-major
    flat for conv, so contiguous core ranges stay meaningful).  The
    single-step engine path is the same contract at ``T == 1``.

    Contract every backend must honor (``tests/test_compute_backends.py``):

    * ``macs`` and ``fetches_dense`` are exact event counts — integer-valued
      and bit-identical across backends (counter sums stay well below the
      2**24 float32 integer horizon);
    * ``pre`` equals the dense reference to float roundoff (backends may
      reassociate the contraction, so parity is rtol <= 1e-6, not bitwise).
    """

    name = "?"

    def fc_forward(self, layer, x_eff: np.ndarray, act_mask: np.ndarray,
                   msgs_in: np.ndarray):
        raise NotImplementedError

    def conv_forward(self, layer, x_eff: np.ndarray, act_mask: np.ndarray,
                     msgs_in: np.ndarray):
        raise NotImplementedError

    def forward(self, layer, x_eff: np.ndarray, act_mask: np.ndarray,
                msgs_in: np.ndarray):
        """Dispatch on the layer kind; the one entry point SimLayer calls."""
        if layer.kind == "fc":
            return self.fc_forward(layer, x_eff, act_mask, msgs_in)
        return self.conv_forward(layer, x_eff, act_mask, msgs_in)

    def delta_forward(self, layer, x_in: np.ndarray, in_acc: np.ndarray,
                      act_mask: np.ndarray, msgs_in: np.ndarray):
        """Forward for a layer whose upstream sends deltas: reconstruct the
        effective activation from the carried accumulator, run the synaptic
        forward, and return ``(pre, macs, fetches_dense, new_acc)``.

        The base implementation is the bit-exact reference: a dense
        cumulative sum over the time axis (sequential ``np.add.accumulate``
        matches the step-major addition order bit for bit when the
        accumulator starts at zero, which :meth:`SimNetwork.init_accs`
        guarantees).  Event backends may override with temporal-tile
        reconstruction; counters never depend on the reconstruction (they
        derive from ``act_mask`` / ``msgs_in`` alone), so overrides change
        ``pre`` only within the float-reassociation tolerance.
        """
        if np.any(in_acc):
            x_eff = in_acc[None, :] + np.cumsum(x_in, axis=0)
        else:
            x_eff = np.cumsum(x_in, axis=0)
        new_acc = x_eff[-1].copy()
        pre, macs, fetches = self.forward(layer, x_eff, act_mask, msgs_in)
        return pre, macs, fetches, new_acc


# ------------------------------------------------------------------- dense

class DenseCompute(LayerCompute):
    """The original dense path: one host GEMM per fc layer, one device
    program per conv layer (:func:`_dense_conv`).

    Bit-exact reference — identical ops in identical order to the pre-seam
    ``SimLayer`` implementation, so every existing parity suite and every
    pricing cache sees unchanged numbers.
    """

    name = "dense"

    def fc_forward(self, layer, x_eff, act_mask, msgs_in):
        pre = x_eff @ layer.weights
        macs = act_mask @ layer.w_mask
        fetches = np.broadcast_to(msgs_in[:, None].astype(np.float32),
                                  macs.shape)
        return pre, macs, fetches

    def conv_forward(self, layer, x_eff, act_mask, msgs_in):
        """All-timesteps conv as one device program (:func:`_dense_conv`):
        ``x_eff`` goes over as float32 and the 0/1 ``act_mask`` as bool,
        one byte an element, and the three maps come back in one fetch."""
        return self._conv(layer, np.asarray(x_eff, np.float32),
                          np.asarray(act_mask) != 0, None)

    def delta_forward(self, layer, x_in, in_acc, act_mask, msgs_in):
        """Conv layers reconstruct on the device: only the delta stream
        ``x_in`` is sent (and ``in_acc`` where it is nonzero), and the
        program derives the wire mask ``x_in != 0`` itself.  Fc layers keep
        the base host cumsum."""
        if layer.kind != "conv":
            return super().delta_forward(layer, x_in, in_acc, act_mask,
                                         msgs_in)
        return self._conv(layer, np.asarray(x_in, np.float32), None,
                          np.asarray(in_acc, np.float32)
                          if np.any(in_acc) else None)

    def _conv(self, layer, x, mask, acc):
        """Run :func:`_dense_conv` and fetch its outputs with one
        ``_to_host``; the fetch map, one channel on the device, is the same
        in every output channel and is tiled here into the channel-major
        flat map pricing reads."""
        wj, wmask = layer._conv_kernels
        x, mask, acc = _to_device(x, mask, acc)
        pre, macs, fetch1, *new_acc = _to_host(*_dense_conv(
            x, mask, acc, wj, wmask, hw=layer.in_hw, stride=layer.stride))
        fetches = np.tile(fetch1, (1, wj.shape[3]))
        return (pre, macs, fetches, *new_acc)


@functools.partial(jax.jit, static_argnames=("hw", "stride"))
def _dense_conv(x, mask, acc, wj, wmask, *, hw, stride):
    """One dense conv layer's synaptic forward over all T steps, on the
    device: ``(T, cin * h * w)`` channel-major flat input in,
    ``(pre, macs, fetch1)`` out, ``pre`` and ``macs`` channel-major flat
    ``(T, cout * oh * ow)`` and ``fetch1`` the ``(T, oh * ow)`` fetch map of
    one output channel.  Flat boundaries are channel-major ((c, h, w)) on
    both sides so conv->conv stacks keep consistent receptive fields.

    With ``mask`` None, ``x`` is a delta stream: the wire mask is
    ``x != 0``, the effective input is rebuilt by a sequential scan over T
    from zero (the addition order of ``np.cumsum`` along time, so the bits
    are the host's; ``jnp.cumsum`` may differ in the last bit), plus
    ``acc`` where given, and the last row is returned as the new
    accumulator.

    The values conv runs at HIGHEST: a TPU's default single bf16 pass
    moves them ~3e-3 relative, while the 0/1 MAC-mask conv is exact in one
    pass.  The fetch map counts each window's events over every input
    channel, so it is one conv of the channel-summed mask with a ones
    kernel; its integer sums stay far below 2**24, so it is exact."""
    T = x.shape[0]
    h, w = hw
    kh, kw, cin, _ = wj.shape
    new_acc = ()
    if mask is None:
        mask = x != 0
        _, x = jax.lax.scan(lambda c, r: (c + r, c + r),
                            jnp.zeros_like(x[0]), x)
        if acc is not None:
            x = acc[None, :] + x
        new_acc = (x[-1],)
    to_nhwc = lambda a: a.reshape(T, cin, h, w).transpose(0, 2, 3, 1)
    x4, m4 = to_nhwc(x), to_nhwc(mask.astype(jnp.float32))
    conv = lambda lhs, rhs, precision=None: jax.lax.conv_general_dilated(
        lhs, rhs, window_strides=(stride, stride), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
    highest = jax.lax.Precision.HIGHEST
    pre = conv(x4, wj, highest)
    macs = conv(m4, wmask)
    fetch1 = conv(m4.sum(axis=3, keepdims=True),
                  jnp.ones((kh, kw, 1, 1), jnp.float32), highest)
    to_flat = lambda a: a.transpose(0, 3, 1, 2).reshape(T, -1)
    return (to_flat(pre), to_flat(macs), fetch1.reshape(T, -1), *new_acc)


# ------------------------------------------------------------------- event

def derived_from_weights(layer, key: str, builder):
    """Per-layer cache of data derived from ``layer.weights``, keyed on the
    *identity of the weights array* rather than the layer object alone.

    The slot stores ``(weights_ref, value)``; a cached value is served only
    while ``layer.weights`` is still the same array object, so rebinding the
    weights (e.g. :meth:`SparsityProfile.apply` writing masked weights onto
    an already-simulated layer) invalidates every derived structure on the
    next access instead of serving stale caches.  ``builder(layer)`` runs on
    a miss.
    """
    slot = layer.__dict__.get(key)
    if slot is None or slot[0] is not layer.weights:
        slot = (layer.weights, builder(layer))
        layer.__dict__[key] = slot
    return slot[1]


def _patch_weights(layer) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per-layer cache of the conv weights in im2col patch order:
    ``(kh, kw, cin, cout) -> (cin * kh * kw, cout)`` values + nnz mask +
    per-feature-row liveness (row has >= 1 nonzero tap), matching
    :func:`_im2col`'s (cin, kh, kw) feature layout.  Cached through
    :func:`derived_from_weights`, so rewriting ``layer.weights`` rebuilds
    the flattening instead of serving stale patch weights."""
    def build(layer):
        w = np.transpose(layer.weights, (2, 0, 1, 3))
        wf = np.ascontiguousarray(w.reshape(-1, layer.weights.shape[3]))
        return (wf, (wf != 0).astype(np.float32), (wf != 0).any(axis=1))
    return derived_from_weights(layer, "_patch_weights", build)


class _WeightBlocks:
    """Block-CSR weight-sparsity structure for one 2-D weight matrix.

    ``live`` (K,) bool marks weight rows with >= 1 nonzero (CSR row
    liveness — an input column whose row is dead fetches nothing);
    ``occ`` / ``occ_j`` are the (Kb, Nb) weight-tile occupancy map as a
    host array (gather mode) and device array (pallas scalar prefetch).
    Computed once per layer from the immutable post-mask weights and cached
    via :func:`derived_from_weights`.
    """

    __slots__ = ("live", "occ", "occ_j", "bk", "bn")

    def __init__(self, w2: np.ndarray, bk: int, bn: int):
        self.bk, self.bn = bk, bn
        nz = w2 != 0
        self.live = nz.any(axis=1)
        K, N = w2.shape
        kb, nb = -(-K // bk), -(-N // bn)
        pad = np.zeros((kb * bk, nb * bn), bool)
        pad[:K, :N] = nz
        self.occ = pad.reshape(kb, bk, nb, bn).any(axis=(1, 3))
        self.occ_j = jnp.asarray(self.occ)

    @classmethod
    def rows_only(cls, live: np.ndarray, bk: int, bn: int) -> "_WeightBlocks":
        """Row-liveness-only structure (conv gather, where the patch-weight
        feature axis is compacted per call so a tile map would not line up)."""
        wb = cls.__new__(cls)
        wb.live, wb.bk, wb.bn = live, bk, bn
        wb.occ = np.ones((1, 1), bool)
        wb.occ_j = None
        return wb


def _device_weights(layer, w: np.ndarray, wm: np.ndarray
                    ) -> tuple[jax.Array, jax.Array]:
    """The event kernel's weight operands on the device, one copy per
    weights array: ``w`` (fc weights or conv patch weights, both derived
    from ``layer.weights``) and its nnz mask ``wm`` are copied on first use
    and cached through :func:`derived_from_weights`, so every later call,
    and the windowed delta path's second pass, sends only activations."""
    return derived_from_weights(
        layer, "_event_weights_device",
        lambda _: _to_device(w, wm, dtype=jnp.float32))


def _fc_weight_blocks(layer, bk: int, bn: int) -> _WeightBlocks:
    return derived_from_weights(
        layer, f"_fc_weight_blocks_{bk}x{bn}",
        lambda l: _WeightBlocks(np.asarray(l.weights), bk, bn))


def _conv_weight_blocks(layer, bk: int, bn: int) -> _WeightBlocks:
    return derived_from_weights(
        layer, f"_conv_weight_blocks_{bk}x{bn}",
        lambda l: _WeightBlocks(_patch_weights(l)[0], bk, bn))


def _im2col(x4: np.ndarray, kh: int, kw: int, stride: int,
            oh: int, ow: int) -> np.ndarray:
    """SAME-padded strided im2col: ``(T, cin, h, w) -> (T * oh * ow,
    cin * kh * kw)`` patch rows in (cin, kh, kw) feature order.

    Padding follows the XLA "SAME" split (``lo = total // 2``), so the
    extracted windows are exactly the receptive fields of the dense
    ``conv_general_dilated`` path.  The window view is zero-copy; the only
    copy is the final contiguous patch matrix (``T*oh*ow*F`` words — a
    ``1/cout`` fraction of the conv's MACs)."""
    T, cin, h, w = x4.shape
    pad_h = max(0, (oh - 1) * stride + kh - h)
    pad_w = max(0, (ow - 1) * stride + kw - w)
    x4 = np.pad(x4, ((0, 0), (0, 0),
                     (pad_h // 2, pad_h - pad_h // 2),
                     (pad_w // 2, pad_w - pad_w // 2)))
    win = np.lib.stride_tricks.sliding_window_view(
        x4, (kh, kw), axis=(2, 3))[:, :, ::stride, ::stride]
    # (T, cin, oh, ow, kh, kw) -> (T, oh, ow, cin, kh, kw) -> rows
    return np.ascontiguousarray(
        win.transpose(0, 2, 3, 1, 4, 5).reshape(T * oh * ow, cin * kh * kw))


class EventCompute(LayerCompute):
    """Event-driven synaptic forward: skip all work for event-free inputs.

    ``threshold`` defines an event (``|x| > threshold``; 0.0 — the wire
    semantics of the simulator, where any nonzero message is an event —
    keeps both kernel modes *exactly* equal to the dense contraction, since
    skipped inputs contribute exact zeros).  ``bm``/``bk``/``bn`` are the
    pallas-mode tile sizes; ``mode`` picks the kernel path (see the module
    docstring).  Instances are stateless across calls and shared via
    :func:`get_compute`.
    """

    name = "event"

    def __init__(self, mode: str = "auto", threshold: float = 0.0,
                 bm: int = 128, bk: int = 128, bn: int = 128,
                 gather_bm: int = 32, delta_mode: str = "window",
                 delta_window: int | None = None):
        if mode not in ("auto", "pallas", "gather"):
            raise ValueError(f"unknown event kernel mode {mode!r}")
        if delta_mode not in ("window", "cumsum"):
            raise ValueError(f"unknown delta mode {delta_mode!r}")
        self.mode = mode
        self.threshold = float(threshold)
        self.bm, self.bk, self.bn = bm, bk, bn
        self.gather_bm = int(gather_bm)
        self.delta_mode = delta_mode
        self.delta_window = delta_window

    def _kernel_mode(self) -> str:
        if self.mode != "auto":
            return self.mode
        return "gather" if jax.default_backend() == "cpu" else "pallas"

    def _delta_window_size(self) -> int:
        """Temporal tile length for windowed delta reconstruction: match the
        kernel's time-tile (``bm``) in pallas mode so quiet windows line up
        with skippable activation tiles; a sublane-aligned multiple of the
        gather row tile otherwise."""
        if self.delta_window is not None:
            return int(self.delta_window)
        if self._kernel_mode() == "pallas":
            return self.bm
        return max(8, self.gather_bm)

    # ---------------------------------------------------- event contractions
    def _gather_matmul(self, x: np.ndarray, w: np.ndarray,
                       bm: int | None = None,
                       wb: "_WeightBlocks | None" = None) -> np.ndarray:
        """Column-granular event contraction: ``x @ w`` fetching only the
        weight rows of inputs active within each ``bm``-row tile
        (``gather_bm`` timesteps by default; conv passes a larger tile
        since its rows are window positions, not steps).

        For each tile of rows, the union of active columns is compacted
        (``k_tile`` of them) and one dense ``(bm, k_tile) @ (k_tile, n_out)``
        GEMM runs on the compacted operands.  Inactive columns contribute
        exact zeros, so the result equals the dense contraction up to float
        reassociation.  Weight fetches are ``k_tile * n_out`` words per tile
        (amortized over ``bm`` rows) and MACs ``bm * k_tile * n_out`` —
        both proportional to activation density, against the dense path's
        fixed ``n_in``-wide GEMM.

        With ``wb`` (the layer's :class:`_WeightBlocks`), sparsity goes 2-D
        — the CPU expression of the same block-CSR format the pallas kernel
        consumes: active columns whose weight row is all-zero are dropped
        from the union (CSR row skipping — a dead row fetches nothing), and
        output n-blocks whose occupancy is dead for every surviving k-tile
        skip their slice of the GEMM outright.  Both skips are exact: the
        dropped operand entries are exact zeros.
        """
        M, K = x.shape
        bm = max(1, bm or self.gather_bm)
        mask = np.abs(x) > self.threshold
        live = mask.any(axis=0)
        if wb is not None:
            live &= wb.live                  # CSR row skipping
        out = np.zeros((M, w.shape[1]), np.float32)
        for i0 in range(0, M, bm):
            i1 = min(i0 + bm, M)
            cols = np.flatnonzero(mask[i0:i1].any(axis=0) & live)
            if cols.size == 0:
                continue                     # event-free tile: no fetch
            if wb is not None and wb.occ.shape[1] > 1:
                nb_live = wb.occ[np.unique(cols // wb.bk)].any(axis=0)
                if not nb_live.all():        # block-CSR n-tile skipping
                    ncols = np.flatnonzero(
                        np.repeat(nb_live, wb.bn)[:w.shape[1]])
                    out[i0:i1, ncols] = x[i0:i1, cols] @ w[np.ix_(cols, ncols)]
                    continue
            if 2 * cols.size >= K:           # near-dense tile: the compacted
                out[i0:i1] = x[i0:i1] @ w    # GEMM wouldn't repay the copies
            else:
                out[i0:i1] = x[i0:i1, cols] @ w[cols]
        return out

    def _pair(self, layer, x: np.ndarray, m: np.ndarray, w: np.ndarray,
              wm: np.ndarray, wb: "_WeightBlocks | None" = None
              ) -> tuple[np.ndarray, np.ndarray]:
        """(pre, macs) through the selected kernel mode.  ``wb`` threads the
        layer's block-CSR weight structure into both contractions: ``wm`` is
        the nnz mask of ``w``, so the two share one occupancy map and skip
        exactly the same tiles — which is what keeps the counter matmul
        bit-identical to the dense reference under weight skipping.  The
        kernel takes ``layer``'s device copy of ``(w, wm)``."""
        if self._kernel_mode() == "gather":
            return (self._gather_matmul(np.asarray(x, np.float32), w, wb=wb),
                    self._gather_matmul(np.asarray(m, np.float32), wm, wb=wb))
        y, macs = event_matmul_pair(
            *_to_device(x, m, *_device_weights(layer, w, wm),
                        dtype=jnp.float32),
            wb.occ_j if wb is not None else None, threshold=self.threshold,
            bm=self.bm, bk=self.bk, bn=self.bn)
        return _to_host(y, macs)

    # ------------------------------------------------------------ layer kinds
    def fc_forward(self, layer, x_eff, act_mask, msgs_in):
        wb = _fc_weight_blocks(layer, self.bk, self.bn)
        pre, macs = self._pair(layer, x_eff, act_mask, layer.weights,
                               layer.w_mask, wb)
        fetches = np.broadcast_to(msgs_in[:, None].astype(np.float32),
                                  macs.shape)
        return pre, macs, fetches

    def _conv_gather(self, a4: np.ndarray, wf: np.ndarray, layer,
                     wlive: np.ndarray | None = None
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Channel-compacted gather-mode conv: input channels with no event
        anywhere in the time batch are dropped *before* the im2col copy, so
        both the patch matrix and the weight fetch scale with structured
        (channel-level) activation density; the per-tile column union then
        harvests the remaining fine-grained sparsity.  Returns the
        ``(T * oh * ow, cout)`` result and the per-window event row sums
        (dropped channels are exact zeros, so both are unchanged).

        ``wlive`` (cin * kh * kw,) feeds CSR row skipping *inside the GEMM
        only*: feature taps whose weight row is all-zero fetch nothing, but
        the event row sums are taken before any weight-based dropping —
        dense fetch counts every event in the window regardless of the
        weight mask, and that counter contract must not move."""
        kh, kw = layer.weights.shape[:2]
        cin = a4.shape[1]
        oh, ow = layer.out_hw
        active_c = np.abs(a4).max(axis=(0, 2, 3)) > self.threshold
        k_c = int(active_c.sum())
        if k_c == 0:
            T = a4.shape[0]
            z = np.zeros((T * oh * ow, wf.shape[1]), np.float32)
            return z, np.zeros(T * oh * ow, np.float32)
        if 2 * k_c < cin:
            ch = np.flatnonzero(active_c)
            a4 = a4[:, ch]
            wf = np.ascontiguousarray(
                wf.reshape(cin, kh * kw, -1)[ch].reshape(k_c * kh * kw, -1))
            if wlive is not None:
                wlive = np.ascontiguousarray(
                    wlive.reshape(cin, kh * kw)[ch].reshape(-1))
        pat = _im2col(a4, kh, kw, layer.stride, oh, ow)
        rows = pat.sum(axis=1, dtype=np.float32)
        wb = None
        if wlive is not None and not wlive.all():
            wb = _WeightBlocks.rows_only(wlive, self.bk, self.bn)
        # conv rows are window positions (oh*ow of them per step): tile a
        # whole timestep's windows together so the per-tile overhead stays
        # per-step, like the fc path
        return self._gather_matmul(pat, wf, bm=max(self.gather_bm,
                                                   oh * ow), wb=wb), rows

    def conv_forward(self, layer, x_eff, act_mask, msgs_in):
        """Event-driven conv through the im2col view: each output position's
        receptive field is one patch row; windows without events fetch no
        weights.  Counter semantics match the dense conv bit for bit:
        ``macs`` sums the weight-nnz mask over each window's events and
        ``fetches_dense`` counts every event in the window once per output
        channel."""
        T = x_eff.shape[0]
        h, w = layer.in_hw
        cin = layer.weights.shape[2]
        kh, kw = layer.weights.shape[:2]
        oh, ow = layer.out_hw
        cout = layer.weights.shape[3]
        wf, wfm, wlive = _patch_weights(layer)
        x4 = np.asarray(x_eff, np.float32).reshape(T, cin, h, w)
        m4 = np.asarray(act_mask, np.float32).reshape(T, cin, h, w)
        if self._kernel_mode() == "gather":
            pre, _ = self._conv_gather(x4, wf, layer, wlive)
            macs, fetch_rows = self._conv_gather(m4, wfm, layer, wlive)
        else:
            xpat = _im2col(x4, kh, kw, layer.stride, oh, ow)
            mpat = _im2col(m4, kh, kw, layer.stride, oh, ow)
            pre, macs = self._pair(layer, xpat, mpat, wf, wfm,
                                   _conv_weight_blocks(layer, self.bk,
                                                       self.bn))
            fetch_rows = mpat.sum(axis=1, dtype=np.float32)
        fetches = np.broadcast_to(fetch_rows[:, None], (T * oh * ow, cout))
        # (T*oh*ow, cout) -> channel-major (T, cout * oh * ow) flat maps
        to_flat = lambda a: np.transpose(
            a.reshape(T, oh, ow, cout), (0, 3, 1, 2)).reshape(T, -1)
        return to_flat(pre), to_flat(macs), to_flat(fetches)

    # --------------------------------------------- temporal-tile delta path
    def delta_forward(self, layer, x_in, in_acc, act_mask, msgs_in):
        """Windowed delta reconstruction: instead of materializing the full
        dense ``acc + cumsum(x_in)`` (which is dense in time even when the
        delta stream is almost silent), split time into ``window``-step
        tiles and exploit linearity of the synaptic forward:

            x_eff = repeat(bases, window) + xwin
            pre   = forward(bases) repeated + forward(xwin)

        ``xwin`` (the within-window cumsums) is exactly zero throughout
        quiet windows, so its event matmul skips them wholesale — temporal
        tile sparsity; the per-window base vectors pay one small dense
        contraction (``T / window`` rows).  Counters are computed on the
        unchanged ``act_mask`` / ``msgs_in``, hence bit-identical to the
        reference; ``pre`` differs only by float reassociation.
        """
        T = x_in.shape[0]
        window = self._delta_window_size()
        if self.delta_mode != "window" or T <= window:
            return super().delta_forward(layer, x_in, in_acc, act_mask,
                                         msgs_in)
        if self._kernel_mode() == "pallas":
            bases, xwin, new_acc = _to_host(*window_reconstruct(
                *_to_device(x_in, in_acc, dtype=jnp.float32),
                window=window))
        else:
            bases, xwin, new_acc = _window_reconstruct_np(x_in, in_acc,
                                                          window)
        pre_w, macs, fetches = self.forward(layer, xwin, act_mask, msgs_in)
        # value-only pass over the base rows: a zero event mask yields zero
        # counters, which are discarded — only the contraction is kept
        zmask = np.zeros_like(bases)
        zmsgs = np.zeros(bases.shape[0], np.float32)
        pre_b, _, _ = self.forward(layer, bases, zmask, zmsgs)
        pre = pre_w + np.repeat(pre_b, window, axis=0)[:T]
        return pre, macs, fetches, new_acc


def _window_reconstruct_np(x_in: np.ndarray, acc: np.ndarray, window: int
                           ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host fast path of :func:`repro.kernels.sigma_delta.ops.
    window_reconstruct` (same decomposition, same float op order per
    window): quiet windows are skipped outright — no cumsum rows are ever
    computed for them — which is where the gather backend's win over the
    dense time cumsum comes from."""
    T, n = x_in.shape
    pt = (-T) % window
    xp = x_in if pt == 0 else np.concatenate(
        [x_in, np.zeros((pt, n), np.float32)])
    xw = xp.reshape(-1, window, n)
    ws = xw.sum(axis=1)                        # per-window totals
    csum = np.cumsum(ws, axis=0)
    bases = np.empty_like(csum)
    bases[0] = acc
    bases[1:] = acc[None, :] + csum[:-1]
    new_acc = acc + csum[-1]
    live = np.flatnonzero((xw != 0).any(axis=(1, 2)))
    xwin = np.zeros_like(xw)
    if live.size:
        xwin[live] = np.cumsum(xw[live], axis=1)
    return bases, xwin.reshape(-1, n)[:T], new_acc


# ---------------------------------------------------------------- registry

_REGISTRY: dict[str, type[LayerCompute]] = {
    "dense": DenseCompute,
    "event": EventCompute,
}
_INSTANCES: dict[str, LayerCompute] = {}


def register_compute(name: str, factory: type[LayerCompute]) -> None:
    """Register a backend class under ``name`` (overwrites; the instance
    cache is invalidated so the next :func:`get_compute` rebuilds)."""
    _REGISTRY[name] = factory
    _INSTANCES.pop(name, None)


def get_compute(spec: "str | LayerCompute | None" = None) -> LayerCompute:
    """Resolve a ``compute=`` argument: None -> :data:`DEFAULT_COMPUTE`,
    a registered name -> its (shared) instance, an instance -> itself."""
    if spec is None:
        spec = DEFAULT_COMPUTE
    if isinstance(spec, LayerCompute):
        return spec
    if spec not in _REGISTRY:
        raise ValueError(f"unknown compute backend {spec!r}; registered: "
                         f"{sorted(_REGISTRY)}")
    if spec not in _INSTANCES:
        _INSTANCES[spec] = _REGISTRY[spec]()
    return _INSTANCES[spec]
