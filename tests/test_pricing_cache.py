"""The pricing cache's neuron-axis cumsums (``precompute_pricing``).

Each counter map is reduced in at most one pass, and a map that is one
integral value per step (a synchronous chip's activity map, an fc layer's
broadcast fetch map) takes its closed form without a sum.  Every
:class:`~repro.neuromorphic.timestep.LayerPricing` array must keep the
dtype, shape and bits of the three-pass sum it replaced, frozen below.
"""

import dataclasses

import numpy as np
import pytest

from repro import tracing
from repro.neuromorphic import (EventCompute, SimLayer, SimNetwork,
                                fc_network, loihi2_like, make_inputs)
from repro.neuromorphic.network import Router
from repro.neuromorphic.platform import speck_like
from repro.neuromorphic.timestep import precompute_pricing
from test_sim_equivalence import conv_stack

FIELDS = ("csum_macs", "csum_fetches", "csum_acts", "csum_msgs")


def _frozen_csum(per_neuron):
    """The three-pass cumsum the cache was built with before closed forms:
    cast, sum, prepend a zero column."""
    a = np.asarray(per_neuron, np.float64)
    return np.concatenate([np.zeros((a.shape[0], 1)),
                           np.cumsum(a, axis=1)], axis=1)


def _frozen_layer(counters, profile):
    acts = (counters.acts_evented if not profile.synchronous
            else np.ones_like(counters.macs))
    return dict(csum_macs=_frozen_csum(counters.macs),
                csum_fetches=_frozen_csum(counters.fetches_dense),
                csum_acts=_frozen_csum(acts),
                csum_msgs=_frozen_csum(counters.msgs_out))


def _routed_net():
    """fc -> routed fc (two held experts of four, one shared, top 2) -> fc."""
    rng = np.random.default_rng(4)
    r = Router(n_experts=4, top_k=2, width=3, held=(0, 2), n_shared=1,
               scale=2.5)
    sizes = [16, 20, r.n_neurons, 6]
    layers = [SimLayer(name=f"fc{i}", kind="fc",
                       weights=rng.normal(0, 0.4, (k, n)).astype(np.float32),
                       router=r if i == 1 else None)
              for i, (k, n) in enumerate(zip(sizes[:-1], sizes[1:]))]
    return SimNetwork(layers=layers, in_size=sizes[0])


def _sigma_delta_fc():
    net = fc_network([24, 40, 16], seed=2, neuron_model="sd_relu")
    for layer in net.layers:
        layer.threshold = 0.05
        layer.sends_deltas = True
    return net


def _row_constant(run, values):
    """``run`` with layer 0's fetch map replaced by a zero-stride view of
    one value per step."""
    outputs, counters = run
    c0 = counters[0]
    v = np.resize(np.asarray(values, np.float32), c0.macs.shape[0])
    fetches = np.broadcast_to(v[:, None], c0.macs.shape)
    return outputs, [dataclasses.replace(c0, fetches_dense=fetches),
                     *counters[1:]]


# name: (network, inputs' density, profile, backend, edit of the run,
#        maps taken in closed form)
CASES = {
    # fetches and activity of each fc layer
    "fc_dense": (lambda: fc_network([24, 40, 16], seed=0,
                                    neuron_model="ssm"),
                 0.3, loihi2_like, "dense", None, 4),
    "fc_event": (lambda: fc_network([24, 40, 16], seed=0,
                                    neuron_model="ssm"),
                 0.3, loihi2_like, "event", None, 4),
    "fc_event_pallas": (lambda: fc_network([24, 40, 16], seed=0,
                                           neuron_model="ssm"),
                        0.3, loihi2_like,
                        lambda: EventCompute(mode="pallas"), None, 4),
    # a conv layer's fetch map is a real map: its activity alone
    "conv": (lambda: conv_stack(), 0.3, loihi2_like, "dense", None, 2 + 2),
    "conv_sigma_delta": (lambda: conv_stack(neuron_model="sd_relu",
                                            sends_deltas=True,
                                            threshold=0.03),
                         0.3, loihi2_like, "dense", None, 2 + 2),
    "fc_sigma_delta_event": (_sigma_delta_fc, 0.3, loihi2_like,
                             lambda: EventCompute(delta_window=4), None, 4),
    "router": (_routed_net, 0.5, loihi2_like, "dense", None, 6),
    # an asynchronous chip's activity map is the evented one: summed
    "async_fc": (lambda: fc_network([24, 40, 16], seed=1,
                                    neuron_model="if"),
                 0.3, speck_like, "dense", None, 2),
    "async_conv": (lambda: conv_stack(neuron_model="if", threshold=0.2),
                   0.3, speck_like, "dense", None, 1),
    # hand-made row-constant views: closed only where integral, and
    # below 2**53 when summed across the row
    "row_constant_fractional": (
        lambda: fc_network([24, 40, 16], seed=0), 0.3, loihi2_like,
        "dense", lambda run: _row_constant(run, [0.1, 1.0, 2.25]), 3),
    "row_constant_past_2**53": (
        lambda: fc_network([24, 40, 16], seed=0), 0.3, loihi2_like,
        "dense", lambda run: _row_constant(run, [2.0 ** 50, 3.0]), 3),
    "row_constant_integral": (
        lambda: fc_network([24, 40, 16], seed=0), 0.3, loihi2_like,
        "dense",
        lambda run: _row_constant(run, [0.0, -7.0, 2.0 ** 40, -0.0]), 4),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_cumsums_keep_the_three_pass_bits(case):
    build, density, make_profile, compute, edit, n_closed = CASES[case]
    net, profile = build(), make_profile()
    xs = make_inputs(net.in_size, density, 6, seed=3)
    run = net.run_batch(xs, compute=compute() if callable(compute)
                        else compute)
    if edit is not None:
        run = edit(run)
    tracing.drain()
    with tracing.enable():
        cache = precompute_pricing(net, xs, profile, precomputed=run)
    spans, _ = tracing.drain()
    assert len(cache.layers) == len(net.layers)
    for l, (lp, counters) in enumerate(zip(cache.layers, run[1])):
        want = _frozen_layer(counters, profile)
        for f in FIELDS:
            got = getattr(lp, f)
            assert got.dtype == want[f].dtype, (l, f)
            assert got.shape == want[f].shape, (l, f)
            assert np.array_equal(got, want[f]), (l, f)
            assert np.array_equal(np.ascontiguousarray(got).view(np.uint64),
                                  want[f].view(np.uint64)), (l, f)
    (cumsum,) = [s for s in spans if s.name == "price.cumsum"]
    assert cumsum.counts == {"csum_closed": n_closed,
                             "csum_summed": 4 * len(net.layers) - n_closed}
