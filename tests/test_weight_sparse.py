"""2-D (activation x weight tile) sparsity + temporal-tile sigma-delta.

Three layers of guarantees:

* kernel level — the joint-sparsity event matmul (`w_occ=`) matches its
  pure-jnp oracle and the dense contraction, including all-zero-weight-block
  edge cases, and the windowed delta reconstruction decomposes the dense
  time cumsum exactly (quiet windows produce exact-zero rows);
* backend level — dense / event-gather / event-pallas three-way parity over
  an (act_density, weight_density) grid: bit-identical counters, roundoff
  outputs.  Weight masks are *tile-structured* (whole (128, 128) blocks
  dead) so the tile-skip machinery actually engages, mirroring the paper's
  finding that structure is what converts weight sparsity into skipped
  fetches;
* cache level — every weight-derived structure (patch weights, block-CSR
  occupancy, w_mask) is keyed on the identity of the weights array, so
  rebinding ``layer.weights`` after a forward has run (the SparsityProfile
  staleness hazard) rebuilds instead of serving stale caches.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels import (event_matmul, event_matmul_pair,
                           weight_block_occupancy, window_reconstruct)
from repro.kernels.event_matmul.ref import event_matmul2_ref
from repro.kernels.sigma_delta.ref import window_reconstruct_ref
from repro.neuromorphic import EventCompute, SimLayer, SimNetwork, fc_network, make_inputs
from repro.neuromorphic.compute import (_fc_weight_blocks, _patch_weights,
                                        _window_reconstruct_np,
                                        derived_from_weights)
from repro.neuromorphic.network import _exact_density_mask

from tests.test_compute_backends import (FLOAT_TOL, assert_backends_match,
                                         conv_stack)

quick = pytest.mark.quick


def tile_structured_weights(K, N, tile_density, rng, bk=128, bn=128):
    """(K, N) weights where whole (bk, bn) tiles are dead with exact tile
    density — the structured weight sparsity the block-CSR format prices."""
    w = rng.normal(0, 1.0 / np.sqrt(K), (K, N)).astype(np.float32)
    kb, nb = -(-K // bk), -(-N // bn)
    tmask = _exact_density_mask((kb, nb), tile_density, rng)
    w *= np.repeat(np.repeat(tmask, bk, axis=0), bn, axis=1)[:K, :N]
    return w


# ================================================================= kernels

class TestWeightSparseKernel:
    @quick
    def test_occupancy_map(self):
        w = np.zeros((256, 384), np.float32)
        w[10, 5] = 1.0          # tile (0, 0)
        w[200, 300] = -2.0      # tile (1, 2)
        occ = np.asarray(weight_block_occupancy(jnp.asarray(w)))
        expect = np.zeros((2, 3), bool)
        expect[0, 0] = expect[1, 2] = True
        assert np.array_equal(occ, expect)

    @quick
    def test_occupancy_pads_ragged_shapes(self):
        w = np.ones((130, 140), np.float32)
        occ = np.asarray(weight_block_occupancy(jnp.asarray(w)))
        assert occ.shape == (2, 2) and occ.all()

    @quick
    @pytest.mark.parametrize("act_d,w_d", [(0.1, 0.1), (0.5, 0.25),
                                           (1.0, 0.5), (0.25, 1.0)])
    def test_joint_matmul_matches_dense(self, act_d, w_d):
        rng = np.random.default_rng(int(act_d * 100 + w_d * 10))
        x = make_inputs(384, act_d, 256, seed=1)
        w = tile_structured_weights(384, 256, w_d, rng)
        occ = weight_block_occupancy(jnp.asarray(w))
        y = np.asarray(event_matmul(jnp.asarray(x), jnp.asarray(w), occ))
        # occupancy derived from w itself: skipped tiles are exact zeros,
        # so the joint kernel equals the dense contraction to roundoff
        np.testing.assert_allclose(y, x @ w, **FLOAT_TOL)
        yr = np.asarray(event_matmul2_ref(
            jnp.asarray(x), jnp.asarray(w), occ, threshold=0.0,
            bm=128, bk=128, bn=128))
        np.testing.assert_allclose(y, yr, **FLOAT_TOL)

    @quick
    def test_all_zero_weight_blocks(self):
        """Edge cases: a dead n-column of tiles, a dead k-row, and a fully
        dead weight matrix must all come out exact (zeros where dead)."""
        rng = np.random.default_rng(0)
        x = make_inputs(256, 0.5, 128, seed=2)
        w = rng.normal(size=(256, 256)).astype(np.float32)
        w[:, 128:] = 0.0         # dead n-column of tiles
        w[128:, :] = 0.0         # dead k-row of tiles
        occ = weight_block_occupancy(jnp.asarray(w))
        assert np.asarray(occ).sum() == 1
        y = np.asarray(event_matmul(jnp.asarray(x), jnp.asarray(w), occ))
        np.testing.assert_allclose(y, x @ w, **FLOAT_TOL)
        assert np.all(y[:, 128:] == 0.0)

        wz = np.zeros((256, 256), np.float32)
        yz = np.asarray(event_matmul(jnp.asarray(x), jnp.asarray(wz),
                                     weight_block_occupancy(jnp.asarray(wz))))
        assert np.all(yz == 0.0)

    @quick
    def test_overclaimed_occupancy_zeroes_tiles(self):
        """w_occ is the contract, not a hint: tiles declared dead are
        dropped even when the weights there are nonzero (the oracle defines
        this; it is what makes the counter matmul prices honest)."""
        rng = np.random.default_rng(3)
        x = rng.normal(size=(128, 256)).astype(np.float32)
        w = rng.normal(size=(256, 128)).astype(np.float32)
        occ = jnp.asarray(np.array([[True], [False]]))
        y = np.asarray(event_matmul(jnp.asarray(x), jnp.asarray(w), occ))
        np.testing.assert_allclose(y, x[:, :128] @ w[:128], **FLOAT_TOL)

    @quick
    def test_pair_counters_exact_under_weight_skipping(self):
        rng = np.random.default_rng(4)
        x = make_inputs(384, 0.2, 256, seed=5)
        m = (x != 0).astype(np.float32)
        w = tile_structured_weights(384, 256, 0.25, rng)
        wm = (w != 0).astype(np.float32)
        occ = weight_block_occupancy(jnp.asarray(w))
        y, macs = event_matmul_pair(jnp.asarray(x), jnp.asarray(m),
                                    jnp.asarray(w), jnp.asarray(wm), occ)
        assert np.array_equal(np.asarray(macs), m @ wm)
        np.testing.assert_allclose(np.asarray(y), x @ w, **FLOAT_TOL)


class TestWindowReconstruct:
    @quick
    @pytest.mark.parametrize("T,window", [(64, 16), (100, 16), (48, 8)])
    def test_decomposition_matches_cumsum(self, T, window):
        rng = np.random.default_rng(T)
        x = rng.normal(size=(T, 40)).astype(np.float32)
        acc = rng.normal(size=(40,)).astype(np.float32)
        x_eff = acc[None] + np.cumsum(x, axis=0)
        for impl in (window_reconstruct,
                     window_reconstruct_ref,
                     lambda a, b, window: _window_reconstruct_np(
                         np.asarray(a), np.asarray(b), window)):
            bases, xwin, new_acc = impl(jnp.asarray(x), jnp.asarray(acc),
                                        window=window)
            rec = (np.repeat(np.asarray(bases), window, axis=0)[:T]
                   + np.asarray(xwin))
            np.testing.assert_allclose(rec, x_eff, rtol=1e-5, atol=1e-5)
            np.testing.assert_allclose(np.asarray(new_acc), x_eff[-1],
                                       rtol=1e-5, atol=1e-5)

    @quick
    def test_quiet_windows_are_exact_zeros(self):
        """The temporal tile skip: a window with no deltas contributes
        exact-zero xwin rows (so the downstream event matmul compacts it
        away) in all three implementations."""
        x = make_inputs(32, 0.3, 64, seed=7)
        x[16:48] = 0.0           # two fully quiet 16-step windows
        acc = np.ones(32, np.float32)
        for impl in (window_reconstruct,
                     lambda a, b, window: _window_reconstruct_np(
                         np.asarray(a), np.asarray(b), window)):
            _, xwin, _ = impl(jnp.asarray(x), jnp.asarray(acc), window=16)
            assert np.all(np.asarray(xwin)[16:48] == 0.0)

    @quick
    def test_window_must_be_sublane_aligned(self):
        with pytest.raises(ValueError, match="multiple of 8"):
            window_reconstruct(jnp.zeros((16, 8)), jnp.zeros(8), window=5)


# ======================================================== backend parity

GRID = [(a, wd) for a in (0.05, 0.3) for wd in (0.1, 0.5, 1.0)]


class TestThreeWayParity:
    """dense / event-gather / event-pallas over the (act_d, w_d) grid."""

    def _net(self, w_d, seed=0):
        rng = np.random.default_rng(seed)
        sizes = [256, 256, 128]
        layers = []
        for i in range(len(sizes) - 1):
            w = tile_structured_weights(sizes[i], sizes[i + 1], w_d, rng)
            layers.append(SimLayer(name=f"fc{i}", kind="fc", weights=w))
        return SimNetwork(layers=layers, in_size=sizes[0])

    @quick
    @pytest.mark.parametrize("act_d,w_d", GRID)
    def test_fc_grid_gather(self, act_d, w_d):
        net = self._net(w_d)
        xs = make_inputs(256, act_d, 6, seed=1)
        assert_backends_match(net, xs, event=EventCompute(mode="gather"))

    @quick
    @pytest.mark.parametrize("act_d,w_d", [(0.05, 0.1), (0.3, 0.5)])
    def test_fc_grid_pallas(self, act_d, w_d):
        net = self._net(w_d)
        xs = make_inputs(256, act_d, 6, seed=2)
        assert_backends_match(net, xs, event=EventCompute(mode="pallas"))

    @quick
    def test_fc_dead_weight_matrix(self):
        """All-zero-weight-block edge through the full simulator: a layer
        whose weights are entirely dead must price zero MACs everywhere and
        still agree across all three backends."""
        net = self._net(0.5, seed=3)
        net.layers[1].weights = np.zeros_like(net.layers[1].weights)
        xs = make_inputs(256, 0.3, 4, seed=3)
        for ev in (EventCompute(mode="gather"), EventCompute(mode="pallas")):
            _, cnt = net.run_batch(xs, compute=ev)
            assert np.all(cnt[1].macs == 0)
        assert_backends_match(net, xs, event=EventCompute(mode="gather"))

    @quick
    @pytest.mark.parametrize("w_d", [0.2, 0.6])
    def test_conv_weight_masked(self, w_d):
        net = conv_stack(weight_density=w_d, seed=1)
        xs = make_inputs(net.in_size, 0.25, 6, seed=4)
        assert_backends_match(net, xs, event=EventCompute(mode="gather"))
        assert_backends_match(net, xs, event=EventCompute(mode="pallas"))

    @quick
    def test_conv_dead_input_channel_taps(self):
        """Conv weight rows dead for one input channel: CSR row skipping in
        the gather GEMM must not change the dense-fetch counter (fetches
        count every event once per output channel regardless of w_mask)."""
        net = conv_stack(weight_density=0.9, seed=2)
        net.layers[0].weights = net.layers[0].weights.copy()
        net.layers[0].weights[:, :, 1, :] = 0.0   # channel 1 taps all dead
        xs = make_inputs(net.in_size, 0.4, 5, seed=5)
        assert_backends_match(net, xs, event=EventCompute(mode="gather"))


class TestWindowedDeltaBackend:
    def _sd_net(self, seed=0):
        net = fc_network([64, 48, 32], weight_density=0.5, seed=seed,
                         neuron_model="sd_relu")
        for l in net.layers:
            l.threshold = 0.05
            l.sends_deltas = True
        return net

    @quick
    @pytest.mark.parametrize("event", [
        EventCompute(mode="gather", delta_window=16),
        EventCompute(mode="pallas", delta_window=16),
        EventCompute(mode="gather", delta_mode="cumsum"),
    ], ids=["gather-window", "pallas-window", "gather-cumsum"])
    def test_sd_chain_quiet_stretch(self, event):
        net = self._sd_net()
        xs = make_inputs(64, 0.3, 64, seed=9)
        xs[20:60] = 0.0          # quiet stretch spanning whole windows
        assert_backends_match(net, xs, event=event)

    @quick
    def test_window_path_engages(self):
        """The windowed path must actually run (not silently fall back):
        T > window with a nonzero accumulator through a quiet batch."""
        net = self._sd_net(seed=1)
        ev = EventCompute(mode="gather", delta_window=8)
        xs = make_inputs(64, 0.5, 40, seed=10)
        out_w, _ = net.run_batch(xs, compute=ev)
        out_d, _ = net.run_batch(xs, compute="dense")
        np.testing.assert_allclose(out_w, out_d, **FLOAT_TOL)

    @quick
    def test_conv_sd_chain_windowed(self):
        net = conv_stack(neuron_model="sd_relu", sends_deltas=True,
                         threshold=0.05, seed=3)
        xs = make_inputs(net.in_size, 0.3, 24, seed=11)
        xs[8:16] = 0.0
        assert_backends_match(
            net, xs, event=EventCompute(mode="gather", delta_window=8))


# ========================================================== cache staleness

class TestDerivedWeightCaches:
    @quick
    def test_derived_from_weights_invalidates_on_rebind(self):
        layer = SimLayer(name="l", kind="fc",
                         weights=np.ones((4, 4), np.float32))
        calls = []
        build = lambda l: calls.append(1) or l.weights.sum()
        assert derived_from_weights(layer, "_t", build) == 16.0
        assert derived_from_weights(layer, "_t", build) == 16.0
        assert len(calls) == 1                      # cached while same array
        layer.weights = np.zeros((4, 4), np.float32)
        assert derived_from_weights(layer, "_t", build) == 0.0
        assert len(calls) == 2                      # rebuilt on rebind

    @quick
    @pytest.mark.parametrize("index", [0, 2], ids=["conv", "fc"])
    def test_device_weight_pair_invalidates_on_rebind(self, index):
        """The event kernel's device copy of (weights, nnz mask) — conv
        patch weights or fc weights — is kept while ``layer.weights`` is
        the same array and copied anew after a rebind."""
        net = conv_stack(seed=5)
        layer = net.layers[index]
        xs = make_inputs(net.in_size, 0.4, 4, seed=6)
        cc = EventCompute(mode="pallas")

        def pair():
            net.run_batch(xs, compute=cc)
            host = (_patch_weights(layer)[:2] if layer.kind == "conv"
                    else (layer.weights, layer.w_mask))
            dev = layer.__dict__["_event_weights_device"][1]
            for h, d in zip(host, dev, strict=True):
                np.testing.assert_array_equal(np.asarray(d), h)
            return dev

        first = pair()
        assert pair() is first                      # kept while same array
        rng = np.random.default_rng(0)
        layer.weights = layer.weights * _exact_density_mask(
            layer.weights.shape, 0.5, rng)
        assert pair() is not first                  # copied anew on rebind

    @quick
    def test_patch_weights_staleness_regression(self):
        """The PR-10 satellite bug: run a conv forward (populating the
        patch-weight cache), then rewrite the weights in place as
        SparsityProfile.apply would on a live layer — the next forward must
        use the NEW weights on every backend."""
        rng = np.random.default_rng(0)
        net = conv_stack(seed=5)
        xs = make_inputs(net.in_size, 0.4, 4, seed=6)
        for compute in ("dense", EventCompute(mode="gather"),
                        EventCompute(mode="pallas")):
            net.run_batch(xs, compute=compute)      # warm every cache
        mask = _exact_density_mask(net.layers[0].weights.shape, 0.5, rng)
        net.layers[0].weights = (net.layers[0].weights * mask)

        fresh = conv_stack(seed=5)
        fresh.layers[0].weights = fresh.layers[0].weights * mask
        for compute in ("dense", EventCompute(mode="gather"),
                        EventCompute(mode="pallas")):
            out_stale, cnt_s = net.run_batch(xs, compute=compute)
            out_fresh, cnt_f = fresh.run_batch(xs, compute=compute)
            np.testing.assert_array_equal(out_stale, out_fresh)
            for a, b in zip(cnt_s, cnt_f):
                assert np.array_equal(a.macs, b.macs)

    @quick
    def test_fc_block_structure_invalidates(self):
        layer = SimLayer(name="l", kind="fc",
                         weights=np.ones((256, 256), np.float32))
        wb = _fc_weight_blocks(layer, 128, 128)
        assert wb.occ.all() and wb.live.all()
        w2 = layer.weights.copy()
        w2[:, 128:] = 0.0
        layer.weights = w2
        wb2 = _fc_weight_blocks(layer, 128, 128)
        assert wb2.occ.tolist() == [[True, False], [True, False]]
        assert layer.w_mask.sum() == 256 * 128      # w_mask rebuilt too
        assert layer.w_nnz == 256 * 128
