"""Barrier-synchronized timestep cost model + full simulation entry point.

Implements the paper's execution model (§II-A, Fig. 1 bottom): within a
timestep every neurocore (1) accumulates synops for each input message,
(2) computes activations, (3) emits activation messages, (4) barrier-syncs.
Per-core synop and activation stages are pipelined, so a core's time is the
max of its memory stage and compute stage (the floorline's straight-boundary
assumption, §VI-A); the timestep is set by the slowest core or by NoC
congestion, plus barrier overhead.

Asynchronous platforms (Speck) have no barrier: a sample's latency is the
pipeline sum over layers of event-driven core work, and idle cores consume
no active power.

Two engines price a workload:

* ``engine="batched"`` (default) — **layer-major, time-batched**: the
  functional network runs once per layer over the whole ``(T, n)`` block
  (:meth:`SimNetwork.run_batch`), counters are aggregated to cores with one
  segment-sum per layer over the ``(T, n_neurons)`` maps, NoC routing is one
  matmul against a cached flow incidence (:func:`route_batch`), and all
  per-step bookkeeping (times, energies, stage votes, max-per-core stats)
  is array ops over the time axis.  This is exact for feed-forward stacks:
  messages cross a layer boundary only within a step, and neuron state flows
  only along time *within* a layer, so reordering the (t, l) loop nest to
  layer-major changes no value.
* ``engine="reference"`` — the original step-major loop, kept so the batched
  engine's outputs and counters can be checked for exact parity
  (``tests/test_sim_equivalence.py``).

Orthogonal to the engine choice, ``compute=`` selects the per-layer
synaptic backend of the functional run (``"dense"`` GEMM/conv reference or
the event-driven ``"event"`` kernel path —
:mod:`repro.neuromorphic.compute`).  Counters are exact across backends,
so every pricing product (reports, caches, populations) is
backend-agnostic (``tests/test_compute_backends.py``).

The batched engine is split into two phases so optimization loops can share
work across many candidates:

* :func:`precompute_pricing` runs the functional network once and reduces its
  ``(T, n_neurons)`` counter maps to per-layer neuron-axis cumulative sums —
  everything that is independent of (partition, mapping).
* :func:`price_candidate` prices one (partition, mapping) pair from a cache:
  per-core segment sums are O(cores) gathers into the cumsums, and the NoC
  matmuls run against the cached flow/path incidence of
  :mod:`repro.neuromorphic.noc`.
* :func:`simulate_population` prices a whole candidate population from one
  cache, gathering every candidate's segment sums in one stacked indexing
  operation per counter per layer (the population axis is the leading axis
  of the stacked boundary array).  Results are bit-identical to per-candidate
  :func:`simulate` calls — the same cumsums are indexed and the same float op
  order runs downstream — which :mod:`tests.test_search` asserts.

Population pricing itself comes in three backends (``backend=`` on
:func:`simulate_population`): ``"numpy"`` — the bit-exact reference above;
``"device"`` — the genome arrays are the program input and one jitted
``jax.vmap`` over the padded population axis derives every per-core
structure and prices it on device (:class:`DevicePopulationPricer`,
:func:`price_population_device`), which is what lets the evolutionary
search's ``engine="device"`` generation loop stay accelerator-resident;
and ``"sharded"`` — the same program with the population axis sharded
over a device mesh (:func:`price_population_sharded`).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro import tracing
from repro.core.metrics import LoadStats, WorkloadMetrics
from repro.neuromorphic.network import BatchCounters, CounterMaps, SimNetwork
from repro.neuromorphic.noc import (Mapping, NocTraffic, flow_structures_rows,
                                    incidence_tables, ordered_mapping,
                                    route_batch, route_step)
from repro.neuromorphic.partition import (Partition, max_cores_for_layer,
                                          minimal_partition)
from repro.neuromorphic.platform import ChipProfile

# jax is a hard dependency of the functional engine (repro.neuromorphic.
# network) already; the device population backend additionally needs x64
# scoping for float64 parity with the NumPy pricing path.
import jax
import jax.numpy as jnp

#: Engine used when :func:`simulate` is called without an explicit
#: ``engine=``.  ``"batched"`` is the layer-major, time-batched engine;
#: ``"reference"`` is the step-major loop kept for parity checking.
#: ``benchmarks/run.py --engine`` overrides this module attribute globally,
#: which is the supported way to flip every simulation in a process.
DEFAULT_ENGINE = "batched"


@dataclasses.dataclass
class CoreCounters:
    """Per-core event counts for one layer at one timestep."""

    msgs_in: np.ndarray        # input messages seen by each core (broadcast)
    synops: np.ndarray         # format-effective weight fetches per core
    macs: np.ndarray           # nnz multiply-accumulates per core
    acts: np.ndarray           # neuron updates per core
    msgs_out: np.ndarray       # messages emitted per core
    neurons: np.ndarray        # neurons mapped per core
    sparse_format: bool


@dataclasses.dataclass
class BatchCoreCounters:
    """Per-core event counts for one layer over ALL timesteps (time-major:
    every array is (T, cores) except ``neurons``)."""

    msgs_in: np.ndarray        # (T, cores) input messages (broadcast)
    synops: np.ndarray         # (T, cores)
    macs: np.ndarray           # (T, cores)
    acts: np.ndarray           # (T, cores)
    msgs_out: np.ndarray       # (T, cores)
    neurons: np.ndarray        # (cores,)
    sparse_format: bool


def _segment_sums(per_neuron: np.ndarray, bounds: np.ndarray) -> np.ndarray:
    csum = np.concatenate([[0.0], np.cumsum(per_neuron, dtype=np.float64)])
    return csum[bounds[1:]] - csum[bounds[:-1]]


def _layer_format(layer, profile: ChipProfile) -> bool:
    fmt = layer.weight_format or (
        profile.default_format_conv if layer.kind == "conv"
        else profile.default_format_fc)
    return fmt == "sparse"


def aggregate_layer(counters: CounterMaps, layer_idx: int, part: Partition,
                    net: SimNetwork, profile: ChipProfile) -> CoreCounters:
    layer = net.layers[layer_idx]
    n = layer.n_neurons
    bounds = part.boundaries(layer_idx, n)
    sparse = _layer_format(layer, profile)
    macs = _segment_sums(counters.macs, bounds)
    fetches_dense = _segment_sums(counters.fetches_dense, bounds)
    synops = macs if sparse else fetches_dense
    acts_map = (counters.acts_evented if not profile.synchronous
                else np.ones_like(counters.macs))
    return CoreCounters(
        msgs_in=np.full(part.cores[layer_idx], counters.msgs_in, np.float64),
        synops=np.asarray(synops, np.float64),
        macs=np.asarray(macs, np.float64),
        acts=_segment_sums(acts_map, bounds),
        msgs_out=_segment_sums(counters.msgs_out, bounds),
        neurons=np.diff(bounds).astype(np.float64),
        sparse_format=sparse,
    )


def core_times(cc, neuron_model: str,
               profile: ChipProfile) -> tuple[np.ndarray, np.ndarray]:
    """(memory-stage, compute-stage) time per core of one layer.  Works on
    both per-step :class:`CoreCounters` and time-major
    :class:`BatchCoreCounters` (the formulas are elementwise)."""
    p = profile
    if cc.sparse_format:
        mem = (cc.msgs_in * (p.c_msg_recv + p.c_decode_msg)
               + cc.synops * (p.c_fetch + p.c_decode_word + p.c_mac))
    else:
        mem = cc.msgs_in * p.c_msg_recv + cc.synops * (p.c_fetch + p.c_mac)
    act = cc.acts * p.neuron_cost(neuron_model)
    return mem, act


@dataclasses.dataclass
class SimReport:
    """Simulation output: performance + M0 metrics + raw per-core arrays.

    ``time_per_step``/``energy_per_step`` are means over the per-step
    ``times``/``energies`` arrays (for asynchronous platforms a "step" is a
    sample and ``times`` holds pipeline latencies).  ``max_synops``,
    ``max_acts`` and ``max_link_load`` are the M0 neurocore-aware intensity
    metrics: per-step maxima over cores (routers for link load), averaged
    over steps — the x-axis / floor / traffic terms of the floorline model.
    The ``per_core_*`` arrays are per-logical-core means over steps in
    partition order; the §VI-B optimizer and the evolutionary search read
    them to locate bottleneck layers.  ``bottleneck_stage`` names the term
    ("memory" / "compute" / "traffic" / "barrier") that set the step time on
    a plurality of steps.
    """

    time_per_step: float            # mean over steps (timestep duration /
                                    # sample latency for async chips)
    energy_per_step: float
    times: np.ndarray               # per-step
    energies: np.ndarray
    metrics: WorkloadMetrics        # M0 (means over steps)
    max_synops: float               # mean over steps of max-per-core synops
    max_acts: float
    max_link_load: float
    n_cores_active: int
    outputs: np.ndarray             # functional network outputs (T, out)
    per_core_synops: np.ndarray     # (n_logical_cores,) mean over steps
    per_core_acts: np.ndarray
    per_core_msgs_out: np.ndarray
    bottleneck_stage: str           # which term set the mean step time

    def summary(self) -> str:
        return (f"time/step={self.time_per_step:.1f} "
                f"energy/step={self.energy_per_step:.1f} "
                f"max_synops={self.max_synops:.0f} "
                f"cores={self.n_cores_active} "
                f"bottleneck={self.bottleneck_stage}")


def simulate(net: SimNetwork, xs: np.ndarray, profile: ChipProfile,
             part: Partition | None = None,
             mapping: Mapping | None = None, *,
             engine: str | None = None,
             compute=None,
             precomputed: tuple | None = None,
             sparsity_profile=None) -> SimReport:
    """Run the network on the simulated chip and price every timestep.

    Args:
      engine: "batched" (layer-major, default) or "reference" (step-major).
      compute: per-layer synaptic backend — ``"dense"`` (default) or
        ``"event"``, a :class:`~repro.neuromorphic.compute.LayerCompute`
        instance, or None for
        :data:`repro.neuromorphic.compute.DEFAULT_COMPUTE`.  Both engines
        honor it; counters (and therefore the priced report) are exact
        across backends, outputs agree to float roundoff.
      precomputed: a cached ``net.run_batch(xs)`` result to reuse — the
        functional run is independent of partition/mapping/profile, so
        optimization loops that re-price many partitions of the same
        (net, xs) pair should compute it once.  Batched engine only: the
        reference engine ignores it and re-runs the network step-major.
        Takes precedence over ``compute`` (the run is already done).
      sparsity_profile: a trained
        :class:`~repro.sparsity.profile.SparsityProfile` to program onto
        ``net`` (via its ``apply``) before simulation — per-layer message
        gates + weight masks; the pricing math itself is untouched, so
        every engine/backend parity guarantee carries over.  Mutually
        exclusive with ``precomputed`` (a functional run is net-bound).
    """
    with tracing.span("sim.simulate"):
        engine = engine or DEFAULT_ENGINE
        if sparsity_profile is not None:
            if precomputed is not None:
                raise ValueError("sparsity_profile cannot be combined with "
                                 "precomputed: the cached run is bound to "
                                 "the un-profiled network")
            net = sparsity_profile.apply(net)
        part = part or minimal_partition(net, profile)
        mapping = mapping or ordered_mapping(part, profile)
        if engine == "batched":
            return _simulate_batched(net, xs, profile, part, mapping,
                                     precomputed, compute)
        if engine == "reference":
            return _simulate_reference(net, xs, profile, part, mapping,
                                       compute)
        raise ValueError(f"unknown engine {engine!r}")


def _finish_report(net, part, T, times, energies, outputs, mean_synops,
                   mean_acts, mean_msgs, max_synops_steps, max_acts_steps,
                   max_link_steps, total_msgs, total_neuron_steps,
                   stage_votes) -> SimReport:
    """Shared report assembly for both engines (identical float math)."""
    w_nnz = sum(l.w_nnz for l in net.layers)
    w_cap = sum(l.n_weights for l in net.layers)
    metrics = WorkloadMetrics(
        synops=LoadStats.of(mean_synops),
        acts=LoadStats.of(mean_acts),
        traffic=LoadStats.of(np.array([max_link_steps.mean()])),
        msgs_total=total_msgs / T,
        weight_density=w_nnz / max(w_cap, 1),
        act_density=(total_msgs / max(total_neuron_steps, 1.0)),
    )
    bottleneck = max(stage_votes.items(), key=lambda kv: kv[1])[0]
    return SimReport(
        time_per_step=float(times.mean()),
        energy_per_step=float(energies.mean()),
        times=times, energies=energies, metrics=metrics,
        max_synops=float(max_synops_steps.mean()),
        max_acts=float(max_acts_steps.mean()),
        max_link_load=float(max_link_steps.mean()),
        n_cores_active=part.total_cores,
        outputs=outputs,
        per_core_synops=mean_synops,
        per_core_acts=mean_acts,
        per_core_msgs_out=mean_msgs,
        bottleneck_stage=bottleneck,
    )


@dataclasses.dataclass
class LayerPricing:
    """Partition/mapping-independent pricing state for one layer: neuron-axis
    cumulative sums of every counter map, so any core boundary's segment sum
    is a 2-element gather (same cumulative-sum difference as the per-step
    :func:`_segment_sums`, identical bits for every partition — and, unlike
    ``np.add.reduceat``, an empty segment correctly sums to 0 when a
    partition holds more cores than the layer has neurons)."""

    msgs_in: np.ndarray        # (T,) float64
    csum_macs: np.ndarray      # (T, n_neurons + 1) float64
    csum_fetches: np.ndarray   # (T, n_neurons + 1)
    csum_acts: np.ndarray      # (T, n_neurons + 1) of the profile's acts map;
                               # on a synchronous profile a read-only view
    csum_msgs: np.ndarray      # (T, n_neurons + 1)
    n_neurons: int
    sparse: bool


@dataclasses.dataclass
class PricingCache:
    """Everything :func:`price_candidate` needs that does not depend on the
    candidate: the functional outputs plus per-layer :class:`LayerPricing`.
    A cache is bound to one (net, xs, profile) workload."""

    outputs: np.ndarray
    T: int
    layers: list[LayerPricing]
    #: lazily-built :class:`DevicePopulationPricer` for the ``device`` and
    #: ``sharded`` backends and the device-resident search engines (one
    #: per cache)
    device_pricer_obj: object = dataclasses.field(default=None, repr=False,
                                                  compare=False)


def _neuron_csum(per_neuron: np.ndarray) -> np.ndarray:
    """(T, n) -> (T, n+1) float64 cumulative sum with a leading zero column;
    paired with :func:`_seg` it is the batched analog of
    :func:`_segment_sums`.  One allocation: the map is cast into it (a
    float32 -> float64 cast is exact) and summed there in place, which on
    wide rows is faster than a float64 ``cumsum`` read straight from the
    float32 map."""
    a = np.asarray(per_neuron)
    out = np.empty((a.shape[0], a.shape[1] + 1))
    out[:, 0] = 0.0
    out[:, 1:] = a
    np.cumsum(out[:, 1:], axis=1, out=out[:, 1:])
    return out


def _row_constant_csum(per_neuron: np.ndarray) -> np.ndarray | None:
    """:func:`_neuron_csum` in closed form, row ``t`` being
    ``v[t] * arange(n + 1)``, for a map that is a zero-stride view of one
    integral value ``v[t]`` per row (the fc fetch map); None for any other.
    Every partial sum is then an integer below 2**53, so the product is the
    sequential sum's exact bits."""
    a = np.asarray(per_neuron)
    n = a.shape[1]
    if n == 0 or a.strides[1] != 0:
        return None
    v = a[:, 0].astype(np.float64)
    if not (np.all(v == np.floor(v))
            and np.abs(v).max(initial=0.0) * n < 2.0 ** 53):
        return None
    out = np.multiply.outer(v, np.arange(n + 1, dtype=np.float64))
    out[:, 0] = 0.0             # the sum's +0.0, where v[t] is negative
    return out


def precompute_pricing(net: SimNetwork, xs: np.ndarray, profile: ChipProfile,
                       *, precomputed: tuple | None = None,
                       compute=None, sparsity_profile=None) -> PricingCache:
    """Run the functional network (or reuse a cached ``net.run_batch(xs)``
    result) and reduce its counter maps to per-layer cumsums.  One cache
    prices any number of (partition, mapping) candidates.  ``compute``
    selects the synaptic backend of the functional run (counters — and so
    the cache — are exact across backends).  ``sparsity_profile`` programs
    a trained :class:`~repro.sparsity.profile.SparsityProfile` onto ``net``
    before the run (mutually exclusive with ``precomputed``)."""
    if sparsity_profile is not None:
        if precomputed is not None:
            raise ValueError("sparsity_profile cannot be combined with "
                             "precomputed: the cached run is bound to the "
                             "un-profiled network")
        net = sparsity_profile.apply(net)
    outputs, all_counters = precomputed or net.run_batch(xs, compute=compute)
    layers = []
    closed = 0
    with tracing.span("price.cumsum"):
        for l, counters in enumerate(all_counters):
            T, n = counters.macs.shape
            if profile.synchronous:     # every neuron updates every step
                csum_acts = np.broadcast_to(
                    np.arange(n + 1, dtype=np.float64), (T, n + 1))
                closed += 1
            else:
                csum_acts = _neuron_csum(counters.acts_evented)
            csum_fetches = _row_constant_csum(counters.fetches_dense)
            if csum_fetches is None:
                csum_fetches = _neuron_csum(counters.fetches_dense)
            else:
                closed += 1
            layers.append(LayerPricing(
                msgs_in=np.asarray(counters.msgs_in, np.float64),
                csum_macs=_neuron_csum(counters.macs),
                csum_fetches=csum_fetches,
                csum_acts=csum_acts,
                csum_msgs=_neuron_csum(counters.msgs_out),
                n_neurons=net.layers[l].n_neurons,
                sparse=_layer_format(net.layers[l], profile)))
        tracing.count("csum_closed", closed)
        tracing.count("csum_summed", 4 * len(layers) - closed)
    return PricingCache(outputs=outputs, T=int(xs.shape[0]), layers=layers)


def _seg(csum: np.ndarray, bounds: np.ndarray) -> np.ndarray:
    """(T, cores) segment sums from cached cumsums: a two-point gather and
    subtraction per core boundary."""
    return csum[:, bounds[1:]] - csum[:, bounds[:-1]]


def _seg_population(csum: np.ndarray, bounds_stack: np.ndarray) -> np.ndarray:
    """Stacked population gather: (T, n+1) cumsums x (K, C+1) padded
    per-candidate boundaries -> (K, T, C) segment sums for every candidate
    in one indexing operation.  Padded (repeated) boundaries yield empty
    zero segments that callers slice away; each candidate's slice carries
    exactly the bits :func:`_seg` would produce."""
    g = csum[:, bounds_stack]                       # (T, K, C+1)
    return np.moveaxis(g[:, :, 1:] - g[:, :, :-1], 1, 0)


def _cached_layer_counters(lp: LayerPricing, part: Partition, layer_idx: int,
                           T: int,
                           segments: tuple | None = None) -> BatchCoreCounters:
    """All-timesteps analog of :func:`aggregate_layer`, built from a
    :class:`LayerPricing` (and optionally pre-gathered
    ``(macs, fetches, acts, msgs_out)`` segment arrays from the population
    path)."""
    bounds = part.boundaries(layer_idx, lp.n_neurons)
    if segments is None:
        macs = _seg(lp.csum_macs, bounds)
        fetches_dense = _seg(lp.csum_fetches, bounds)
        acts = _seg(lp.csum_acts, bounds)
        msgs_out = _seg(lp.csum_msgs, bounds)
    else:
        macs, fetches_dense, acts, msgs_out = segments
    c = part.cores[layer_idx]
    return BatchCoreCounters(
        msgs_in=np.broadcast_to(lp.msgs_in[:, None], (T, c)),
        synops=macs if lp.sparse else fetches_dense,
        macs=macs,
        acts=acts,
        msgs_out=msgs_out,
        neurons=np.diff(bounds).astype(np.float64),
        sparse_format=lp.sparse,
    )


def simulate_population(net: SimNetwork, xs: np.ndarray, profile: ChipProfile,
                        candidates, *, precomputed: tuple | None = None,
                        cache: PricingCache | None = None,
                        backend: str = "numpy",
                        compute=None, sparsity_profile=None) -> list[SimReport]:
    """Price many (partition, mapping) candidates from ONE functional run.

    ``candidates`` is an iterable of ``(Partition, Mapping)`` pairs.  The
    expensive (T, n_neurons) work — the functional network run and the
    per-layer counter cumsums — happens once (or is reused from ``cache`` /
    ``precomputed``); each candidate's per-core segment sums are then
    gathered for the whole population at once (:func:`_seg_population`), and
    only the small (T, cores) stage/energy/NoC math runs per candidate.

    Three backends price the population (``docs/simulator.md`` has the
    full decision guide):

    * ``backend="numpy"`` (default) — stacked cumsum gathers plus
      per-candidate NumPy stage math.  Every report is bit-identical to the
      corresponding single-candidate ``simulate(net, xs, profile, part,
      mapping)`` call with the batched engine: the same cumsums are indexed
      and the same float op order runs on the gathered segments (asserted
      by ``tests/test_search.py``).  The reference the other two are
      checked against.
    * ``backend="device"`` — the genome rows themselves are the program
      input: candidates are encoded to stacked ``(K, n_layers)`` /
      ``(K, n_slots)`` arrays and everything downstream — segment
      boundaries, NoC flow structures, pricing — runs inside one jitted
      ``jax.vmap`` over the population axis
      (:func:`price_population_device`).  Agrees with the NumPy path
      within float64 roundoff; this is the pricer the device-resident
      search engine (``repro.core.search``, ``engine="device"``) keeps
      entirely on the accelerator.
    * ``backend="sharded"`` — the device path with the K axis sharded over
      a 1-D ``("island",)`` device mesh (:func:`price_population_sharded`;
      every visible device prices its own block of rows).  Per-row parity
      with ``"device"`` to float64 roundoff; useful past pop ≈ 4k on a
      multi-device host (``docs/distributed.md``).

    ``sparsity_profile`` programs a trained
    :class:`~repro.sparsity.profile.SparsityProfile` onto ``net`` before
    the functional run — every backend then prices the profiled workload
    with its usual parity guarantee (mutually exclusive with ``cache`` /
    ``precomputed``, which are bound to the un-profiled network).
    """
    if sparsity_profile is not None:
        if cache is not None or precomputed is not None:
            raise ValueError("sparsity_profile cannot be combined with "
                             "cache/precomputed: both are bound to the "
                             "un-profiled network")
        net = sparsity_profile.apply(net)
    cands = list(candidates)
    if not cands:
        return []
    for k, (part, mapping) in enumerate(cands):
        if len(mapping.phys) != part.total_cores:
            raise ValueError(
                f"candidate {k}: mapping places {len(mapping.phys)} logical "
                f"cores but the partition allocates {part.total_cores} "
                f"(cores={tuple(part.cores)}); partition and mapping must "
                "agree before pricing")
    cache = cache or precompute_pricing(net, xs, profile,
                                        precomputed=precomputed,
                                        compute=compute)
    if backend == "device":
        cores, perm = _pairs_to_rows(cands, len(cache.layers),
                                     profile.n_cores)
        return price_population_device(net, profile, cache, cores, perm)
    if backend == "sharded":
        cores, perm = _pairs_to_rows(cands, len(cache.layers),
                                     profile.n_cores)
        return price_population_sharded(net, profile, cache, cores, perm)
    if backend != "numpy":
        raise ValueError(f"unknown population backend {backend!r}")
    n_layers = len(cache.layers)
    seg_by_cand: list[list[tuple]] = [[None] * n_layers for _ in cands]
    for l, lp in enumerate(cache.layers):
        all_bounds = [p.boundaries(l, lp.n_neurons) for p, _ in cands]
        c_max = max(len(b) - 1 for b in all_bounds)
        stack = np.stack([np.pad(b, (0, c_max + 1 - len(b)), mode="edge")
                          for b in all_bounds])          # (K, c_max + 1)
        pop_segs = tuple(_seg_population(csum, stack) for csum in
                         (lp.csum_macs, lp.csum_fetches,
                          lp.csum_acts, lp.csum_msgs))
        for k, b in enumerate(all_bounds):
            c = len(b) - 1
            seg_by_cand[k][l] = tuple(s[k, :, :c] for s in pop_segs)
    return [price_candidate(net, profile, cache, p, m,
                            layer_segments=seg_by_cand[k])
            for k, (p, m) in enumerate(cands)]


def _simulate_batched(net: SimNetwork, xs: np.ndarray, profile: ChipProfile,
                      part: Partition, mapping: Mapping,
                      precomputed: tuple | None, compute=None) -> SimReport:
    """Layer-major engine: one pricing-cache build + one candidate pricing."""
    cache = precompute_pricing(net, xs, profile, precomputed=precomputed,
                               compute=compute)
    return price_candidate(net, profile, cache, part, mapping)


def price_candidate(net: SimNetwork, profile: ChipProfile,
                    cache: PricingCache, part: Partition, mapping: Mapping,
                    *, layer_segments: list[tuple] | None = None) -> SimReport:
    """Price one (partition, mapping) candidate from a pricing cache; every
    per-step quantity is a (T, ...) array."""
    with tracing.span("price.candidate"):
        outputs = cache.outputs
        T = cache.T
        n_layers = len(cache.layers)
        n_logical = part.total_cores

        with tracing.span("price.segments"):
            layer_cc = [_cached_layer_counters(
                            cache.layers[l], part, l, T,
                            layer_segments[l] if layer_segments else None)
                        for l in range(n_layers)]

        mem_all, act_all = [], []
        e_events = np.zeros(T, np.float64)
        total_msgs = 0.0
        total_neuron_steps = 0.0
        with tracing.span("price.cores"):
            for l, cc in enumerate(layer_cc):
                model = net.layers[l].neuron_model
                mem, act = core_times(cc, model, profile)
                mem_all.append(mem)
                act_all.append(act)
                # event energies: fetch every (format-effective) synop; MAC
                # energy only on nonzero weights (dense formats skip the
                # multiply -> the small Fig-2 energy benefit of CNN weight
                # sparsity)
                e_events += (profile.e_fetch * cc.synops.sum(axis=1)
                             + profile.e_mac * cc.macs.sum(axis=1)
                             + (profile.e_decode * cc.synops.sum(axis=1)
                                if cc.sparse_format else 0.0)
                             + profile.e_act * cc.acts.sum(axis=1)
                             * (profile.neuron_cost(model) / profile.c_act))
                total_msgs += cc.msgs_out.sum()
                total_neuron_steps += T * cc.neurons.sum()

        synops_all = np.concatenate([cc.synops for cc in layer_cc], axis=1)
        acts_all = np.concatenate([cc.acts for cc in layer_cc], axis=1)
        msgs_all = np.concatenate([cc.msgs_out for cc in layer_cc], axis=1)

        traffic = route_batch(part, mapping, msgs_all, profile)
        mem_cat = np.concatenate(mem_all, axis=1)       # (T, n_logical)
        act_cat = np.concatenate(act_all, axis=1)
        core_time = np.maximum(mem_cat, act_cat) + profile.t_core_fixed
        # Congestion: the busiest router serializes every packet touching
        # it; cores also serialize their own (duplicated) injections.
        max_link_steps = traffic.max_router_load        # (T,)
        traffic_time = (profile.c_route * max_link_steps
                        + profile.c_inject
                        * traffic.inject_per_core.max(axis=1, initial=0.0))

        stage_votes = {"memory": 0, "compute": 0, "traffic": 0,
                       "barrier": 0}
        if profile.synchronous:
            t_compute = core_time.max(axis=1, initial=0.0)
            times = np.maximum(t_compute, traffic_time) + profile.t_barrier
            traffic_bound = traffic_time > t_compute
            mem_bound = (mem_cat.max(axis=1, initial=0.0)
                         >= act_cat.max(axis=1, initial=0.0))
            stage_votes["traffic"] = int(traffic_bound.sum())
            stage_votes["memory"] = int((~traffic_bound & mem_bound).sum())
            stage_votes["compute"] = int((~traffic_bound & ~mem_bound).sum())
        else:
            # async pipeline: sample latency = sum over layers of the
            # layer's slowest event-driven core + NoC transit
            times = np.zeros(T, np.float64)
            for m, a in zip(mem_all, act_all):
                times = times + np.maximum(m, a).max(axis=1, initial=0.0)
            times = times + (profile.c_msg_hop * traffic.total_hops
                             / max(part.total_cores, 1))
            stage_votes["memory"] = T

        n_active = np.sum((synops_all + msgs_all) > 0,
                          axis=1).astype(np.float64)
        n_active[n_active == 0] = n_logical
        e_hops = profile.e_msg_hop * traffic.total_hops
        energies = (times * (profile.p_idle + profile.p_core * n_active)
                    + e_events + e_hops)

        mean_synops = synops_all.sum(axis=0) / T
        mean_acts = acts_all.sum(axis=0) / T
        mean_msgs = msgs_all.sum(axis=0) / T
        return _finish_report(
            net, part, T, times, energies, outputs, mean_synops, mean_acts,
            mean_msgs,
            max_synops_steps=synops_all.max(axis=1, initial=0.0),
            max_acts_steps=acts_all.max(axis=1, initial=0.0),
            max_link_steps=max_link_steps,
            total_msgs=total_msgs, total_neuron_steps=total_neuron_steps,
            stage_votes=stage_votes)


@dataclasses.dataclass(frozen=True)
class LayerStageTimes:
    """Per-layer floorline coordinates (one row per network layer).

    ``mem_time`` / ``act_time`` are the mean-over-steps memory/compute stage
    times of the layer's slowest core (the same :func:`core_times` formulas
    the pricer uses); ``traffic_time`` is the layer's share of the NoC
    serialization time, apportioned by its message volume; ``msgs_out`` is
    its mean messages per step.  These are the coordinates
    :func:`repro.core.guidance.floorline_layer_guidance` classifies with
    the :class:`~repro.core.floorline.FloorlineModel`.
    """

    name: str
    mem_time: float
    act_time: float
    traffic_time: float
    msgs_out: float

    @property
    def total_time(self) -> float:
        return max(self.mem_time, self.act_time) + self.traffic_time


def layer_stage_times(net: SimNetwork, xs: np.ndarray, profile: ChipProfile,
                      part: Partition | None = None,
                      mapping: Mapping | None = None, *,
                      cache: PricingCache | None = None
                      ) -> list[LayerStageTimes]:
    """Decompose a priced workload into per-layer stage times.

    The pricer's report localizes the bottleneck to a *stage*; this
    decomposes it to *layers*, using the identical counter segments and
    stage formulas (the per-layer maxima it reports are the terms whose
    global maxima set the report's step time).  This is the measurement the
    floorline-guided training loop weighs its regularizers with."""
    part = part or minimal_partition(net, profile)
    mapping = mapping or ordered_mapping(part, profile)
    cache = cache or precompute_pricing(net, xs, profile)
    T = cache.T
    layer_cc = [_cached_layer_counters(cache.layers[l], part, l, T)
                for l in range(len(cache.layers))]
    msgs_all = np.concatenate([cc.msgs_out for cc in layer_cc], axis=1)
    traffic = route_batch(part, mapping, msgs_all, profile)
    traffic_time = (profile.c_route * traffic.max_router_load
                    + profile.c_inject
                    * traffic.inject_per_core.max(axis=1, initial=0.0))
    layer_msgs = np.array([cc.msgs_out.sum() for cc in layer_cc], np.float64)
    share = layer_msgs / max(layer_msgs.sum(), 1.0)
    out = []
    for l, cc in enumerate(layer_cc):
        mem, act = core_times(cc, net.layers[l].neuron_model, profile)
        out.append(LayerStageTimes(
            name=net.layers[l].name,
            mem_time=float(mem.max(axis=1, initial=0.0).mean()),
            act_time=float(act.max(axis=1, initial=0.0).mean()),
            traffic_time=float(traffic_time.mean() * share[l]),
            msgs_out=float(layer_msgs[l] / T)))
    return out


# ------------------------------------------------------------- device backend
#
# The population pricer: every candidate's (T, cores) stage reductions and
# NoC matmuls run as ONE jitted ``jax.vmap`` over the population axis, and
# the program input is the raw genome arrays — (K, n_layers) core counts +
# (K, n_slots) slot permutations.  Everything else is derived on device:
# per-core layer ids and cumsum gather indices from an integer decode of the
# core-count rows, and the NoC (PL, ph, dup) structures from a pure-jnp
# scatter/fold (:func:`repro.neuromorphic.noc.flow_structures_rows`).
# Because the decode is shape-static it traces into larger jitted programs
# — the device-resident evolutionary search keeps survivor genomes on the
# accelerator across generations and re-prices them without any host sync.
#
# Padding/masking contract:
#
# * logical cores are padded to a fixed width ``Ncap`` (the workload's
#   maximum feasible total cores, capped at ``profile.n_cores``) so the
#   compiled executable is reused across generations and population sizes;
# * a padded core has ``seg_lo == seg_hi == 0`` (its cumsum gather is an
#   empty segment -> exact 0 counters), ``mask == 0`` (its broadcast
#   ``msgs_in`` and fixed core overhead are zeroed before any max/sum), and
#   all-zero ``PL`` rows (it injects nothing into the NoC);
# * per-layer cost constants are folded into per-layer coefficient vectors in
#   float64 Python — the same constant folding as the NumPy path — and
#   gathered per core through the layer-id vector.
#
# Boundary parity: ``Partition.boundaries`` is ``np.linspace(0, n, c+1)
# .astype(int)`` = ``int(i * (n/c))`` with the endpoint pinned to ``n``;
# the decode reproduces exactly that float64 arithmetic, so the gathered
# cumsum indices are identical to the NumPy path's.
#
# Arithmetic runs in float64 (``jax.enable_x64(True)`` scoped to this
# path), with the same elementwise formulas and reduction semantics as the
# NumPy path; XLA may reassociate/fuse (FMA), so results agree to float64
# roundoff rather than bit-for-bit — the parity suite asserts
# ``rtol=1e-9`` (``tests/test_population_pricing.py``).


def population_pad_width(net: SimNetwork, profile: ChipProfile) -> int:
    """Fixed logical-core padding width for (net, profile): every feasible
    candidate fits, and the jitted pricer compiles exactly once."""
    cap = sum(min(max_cores_for_layer(net, l), profile.n_cores)
              for l in range(len(net.layers)))
    return min(cap, profile.n_cores)


def _assemble_reports(out, n_logical, cache: PricingCache,
                      w_density: float) -> list[SimReport]:
    """Host-side :class:`SimReport` assembly shared by the device and
    sharded population backends: ``out`` is the pricer's host dict with a
    leading population axis, ``n_logical`` the (K,) live-core counts."""
    T = cache.T
    outputs = cache.outputs
    stage_names = ("memory", "compute", "traffic", "barrier")
    reports = []
    for k in range(len(n_logical)):
        n = int(n_logical[k])
        votes = out["votes"][k]

        def _stats(total, mx, n_act):
            total, mx, n_act = float(total), float(mx), int(n_act)
            mean = total / max(n_act, 1)
            return LoadStats(total=total, max=mx, mean=mean,
                             imbalance=(mx / mean) if mean > 0 else 1.0,
                             n_units=int(n), n_active=n_act)

        link_mean = float(out["max_link_load"][k])
        total_msgs = float(out["total_msgs"][k])
        metrics = WorkloadMetrics(
            synops=_stats(out["syn_total"][k], out["syn_max"][k],
                          out["syn_nact"][k]),
            acts=_stats(out["act_total"][k], out["act_max"][k],
                        out["act_nact"][k]),
            traffic=LoadStats(
                total=link_mean, max=link_mean,
                mean=link_mean if link_mean > 0 else 0.0, imbalance=1.0,
                n_units=1, n_active=int(link_mean > 0)),
            msgs_total=total_msgs / T,
            weight_density=w_density,
            act_density=(total_msgs
                         / max(float(out["total_neuron_steps"][k]), 1.0)),
        )
        reports.append(SimReport(
            time_per_step=float(out["time_per_step"][k]),
            energy_per_step=float(out["energy_per_step"][k]),
            times=out["times"][k], energies=out["energies"][k],
            metrics=metrics,
            max_synops=float(out["max_synops"][k]),
            max_acts=float(out["max_acts"][k]),
            max_link_load=link_mean,
            n_cores_active=n,
            outputs=outputs,
            per_core_synops=out["mean_synops"][k, :n],
            per_core_acts=out["mean_acts"][k, :n],
            per_core_msgs_out=out["mean_msgs"][k, :n],
            bottleneck_stage=stage_names[int(np.argmax(votes))],
        ))
    return reports


class DevicePopulationPricer:
    """Genome-array population pricer bound to one :class:`PricingCache`.

    Holds the device-resident workload constants (concatenated counter
    cumsums, per-layer coefficient vectors, NoC path incidence) and the
    jitted vmapped pricer.  ``price(cores, perm)`` accepts already-on-device
    (or host) stacked genome rows and returns the pricing dict;
    :meth:`price_row` is the traced single-genome program for composition
    into larger jitted functions (the device search engine vmaps it inside
    its generation step).  Shapes are fixed by ``Ncap``; the population
    axis K is the vmap axis, so a new population size only re-traces, it
    does not rebuild the constants.  Besides the report fields, a row's
    output carries the mutation-policy fields the search consumes on
    device: ``stage`` (argmax of the bottleneck votes,
    memory/compute/traffic/barrier order) and ``hot_mem``/``hot_act``
    (layer of the max-loaded core).
    """

    def __init__(self, net: SimNetwork, profile: ChipProfile,
                 cache: PricingCache):
        self.profile = profile
        self.synchronous = profile.synchronous
        self.T = cache.T
        self.n_layers = len(cache.layers)
        w_nnz = sum(l.w_nnz for l in net.layers)
        w_cap = sum(l.n_weights for l in net.layers)
        self.weight_density = w_nnz / max(w_cap, 1)
        p = profile
        # per-layer coefficient vectors, folded with the SAME Python-float
        # constant arithmetic as core_times()/price_candidate()
        mem_msg, mem_syn, ncost, sparse_f, e_act_c = [], [], [], [], []
        for l, lp in enumerate(cache.layers):
            model = net.layers[l].neuron_model
            if lp.sparse:
                mem_msg.append(p.c_msg_recv + p.c_decode_msg)
                mem_syn.append(p.c_fetch + p.c_decode_word + p.c_mac)
            else:
                mem_msg.append(p.c_msg_recv)
                mem_syn.append(p.c_fetch + p.c_mac)
            ncost.append(p.neuron_cost(model))
            sparse_f.append(1.0 if lp.sparse else 0.0)
            e_act_c.append(p.e_act * (p.neuron_cost(model) / p.c_act))
        self.n_pad = population_pad_width(net, profile)
        rows, cols = profile.grid
        self.cpr = max(1, profile.n_cores // (rows * cols))
        widths = np.asarray([lp.n_neurons + 1 for lp in cache.layers])
        with jax.enable_x64(True):
            self.csums = tuple(
                jnp.asarray(np.concatenate([getattr(lp, f) for lp in
                                            cache.layers], axis=1))
                for f in ("csum_macs", "csum_fetches", "csum_acts",
                          "csum_msgs"))
            self.msgs_in_all = jnp.asarray(
                np.stack([lp.msgs_in for lp in cache.layers], axis=1))
            self.coefs = tuple(jnp.asarray(np.asarray(v, np.float64))
                               for v in (mem_msg, mem_syn, ncost, sparse_f,
                                         e_act_c))
            self.block_off = jnp.asarray(
                np.concatenate([[0], np.cumsum(widths)])[:-1]
                .astype(np.int32))
            self.n_neurons_vec = jnp.asarray(
                np.asarray([lp.n_neurons for lp in cache.layers], np.int32))
            inc3, hops2 = incidence_tables(profile.grid)
            self.inc3 = jnp.asarray(inc3)
            self.hops2 = jnp.asarray(hops2)
        self._fn = jax.jit(jax.vmap(self.price_row))

    def structures_row(self, cores_row, perm_row):
        """(n_layers,) cores + (n_slots,) perm -> the padded per-core
        pricing structures ``(mask, lid, seg_lo, seg_hi, neurons, PL, ph,
        dup)``, each with a leading ``Ncap`` axis, all on device."""
        L, ncap = self.n_layers, self.n_pad
        csum = jnp.cumsum(cores_row)                        # (L,)
        total = csum[-1]
        j = jnp.arange(ncap)
        alive = j < total
        lid = jnp.minimum(jnp.searchsorted(csum, j, side="right"),
                          L - 1).astype(jnp.int32)
        within = j - (csum - cores_row)[lid]                # index in layer
        n_l = self.n_neurons_vec[lid]
        c_l = cores_row[lid]
        # same float64 arithmetic as np.linspace(0, n, c+1).astype(int)
        step = n_l.astype(jnp.float64) / c_l.astype(jnp.float64)
        lo_loc = (within.astype(jnp.float64) * step).astype(jnp.int32)
        hi_loc = jnp.where(within + 1 == c_l, n_l,
                           ((within + 1).astype(jnp.float64) * step)
                           .astype(jnp.int32))
        lid = jnp.where(alive, lid, 0)
        seg_lo = jnp.where(alive, self.block_off[lid] + lo_loc, 0) \
            .astype(jnp.int32)
        seg_hi = jnp.where(alive, self.block_off[lid] + hi_loc, 0) \
            .astype(jnp.int32)
        neurons = jnp.where(alive, hi_loc - lo_loc, 0).astype(jnp.float64)
        mask = alive.astype(jnp.float64)
        router = jnp.where(alive, perm_row[:ncap] // self.cpr, 0) \
            .astype(jnp.int32)
        PL, ph, dup = flow_structures_rows(lid, router, mask, L,
                                           self.inc3, self.hops2)
        return mask, lid, seg_lo, seg_hi, neurons, PL, ph, dup

    def _price_one(self, mask, lid, seg_lo, seg_hi, neurons, PL, ph, dup):
        """One candidate's pricing from its padded per-core structures."""
        p = self.profile
        T = self.T
        csum_macs, csum_fetches, csum_acts, csum_msgs = self.csums
        mem_msg, mem_syn, ncost, sparse_f, e_act_c = self.coefs

        macs = csum_macs[:, seg_hi] - csum_macs[:, seg_lo]        # (T, Ncap)
        fetches = csum_fetches[:, seg_hi] - csum_fetches[:, seg_lo]
        acts = csum_acts[:, seg_hi] - csum_acts[:, seg_lo]
        msgs = csum_msgs[:, seg_hi] - csum_msgs[:, seg_lo]

        sp_c = sparse_f[lid]                                      # (Ncap,)
        synops = jnp.where(sp_c > 0, macs, fetches)
        msgs_in_c = self.msgs_in_all[:, lid] * mask               # (T, Ncap)
        mem = msgs_in_c * mem_msg[lid] + synops * mem_syn[lid]
        act = acts * ncost[lid]
        core_time = (jnp.maximum(mem, act) + p.t_core_fixed) * mask

        e_events = (p.e_fetch * synops.sum(axis=1)
                    + p.e_mac * macs.sum(axis=1)
                    + p.e_decode * (synops * sp_c).sum(axis=1)
                    + (acts * e_act_c[lid]).sum(axis=1))

        loads = msgs @ PL                                         # (T, R)
        hops = msgs @ ph                                          # (T,)
        inject = msgs * dup
        max_link = loads.max(axis=1)
        traffic_time = (p.c_route * max_link
                        + p.c_inject * inject.max(axis=1))

        n_logical = mask.sum().astype(jnp.int32)
        if self.synchronous:
            t_compute = core_time.max(axis=1)
            times = jnp.maximum(t_compute, traffic_time) + p.t_barrier
            tb = traffic_time > t_compute
            mb = mem.max(axis=1) >= act.max(axis=1)
            votes = jnp.stack([(~tb & mb).sum(), (~tb & ~mb).sum(),
                               tb.sum(), jnp.zeros((), jnp.int32)])
        else:
            val = jnp.maximum(mem, act)                           # (T, Ncap)
            per_layer = jax.ops.segment_max(
                (val * mask).T, lid, num_segments=self.n_layers)  # (L, T)
            times = (jnp.maximum(per_layer, 0.0).sum(axis=0)
                     + p.c_msg_hop * hops / jnp.maximum(n_logical, 1))
            votes = jnp.stack([jnp.full((), T, jnp.int32)] +
                              [jnp.zeros((), jnp.int32)] * 3)

        n_active = (((synops + msgs) > 0) & (mask > 0)).sum(axis=1)
        n_active = jnp.where(n_active == 0, n_logical, n_active)
        energies = (times * (p.p_idle + p.p_core * n_active)
                    + e_events + p.e_msg_hop * hops)

        mean_synops = synops.sum(axis=0) / T
        mean_acts = acts.sum(axis=0) / T
        mean_msgs = msgs.sum(axis=0) / T
        total_msgs = msgs.sum()
        return dict(
            times=times, energies=energies,
            time_per_step=times.mean(), energy_per_step=energies.mean(),
            max_synops=synops.max(axis=1).mean(),
            max_acts=acts.max(axis=1).mean(),
            max_link_load=max_link.mean(),
            mean_synops=mean_synops, mean_acts=mean_acts,
            mean_msgs=mean_msgs,
            # LoadStats ingredients (pads are exact zeros -> don't count)
            syn_total=mean_synops.sum(), syn_max=mean_synops.max(),
            syn_nact=(mean_synops > 0).sum(),
            act_total=mean_acts.sum(), act_max=mean_acts.max(),
            act_nact=(mean_acts > 0).sum(),
            votes=votes,
            total_msgs=total_msgs,
            total_neuron_steps=T * neurons.sum(),
        )

    def price_row(self, cores_row, perm_row):
        """The traced per-genome pricing program (vmap/jit composable)."""
        mask, lid, seg_lo, seg_hi, neurons, PL, ph, dup = \
            self.structures_row(cores_row, perm_row)
        out = self._price_one(mask, lid, seg_lo, seg_hi, neurons, PL, ph, dup)
        out["stage"] = jnp.argmax(out["votes"]).astype(jnp.int32)
        out["hot_mem"] = lid[jnp.argmax(out["mean_synops"])]
        out["hot_act"] = lid[jnp.argmax(out["mean_acts"])]
        return out

    def price(self, cores, perm, *, device: bool = False) -> dict:
        """Price stacked genome rows (host or device arrays).  Returns the
        pricing dict on host (``device=False``, default) or device-resident
        (``device=True`` — no transfer, for callers that keep going on
        device)."""
        with jax.enable_x64(True):
            out = self._fn(jnp.asarray(cores, jnp.int32),
                           jnp.asarray(perm, jnp.int32))
        return out if device else jax.device_get(out)


def device_pricer(net: SimNetwork, profile: ChipProfile,
                  cache: PricingCache) -> DevicePopulationPricer:
    """The cache's :class:`DevicePopulationPricer` (built on first use; a
    cache is bound to one (net, xs, profile) workload, so one pricer —
    and its compiled programs — serves every population it prices)."""
    if cache.device_pricer_obj is None:
        cache.device_pricer_obj = DevicePopulationPricer(net, profile, cache)
    return cache.device_pricer_obj


def _pairs_to_rows(pairs, n_layers: int,
                   n_slots: int) -> tuple[np.ndarray, np.ndarray]:
    """(Partition, Mapping) pairs -> stacked fixed-shape genome rows; the
    permutation tail (unexpressed slots) is filled ascending, mirroring
    ``repro.core.search.encode``."""
    K = len(pairs)
    cores = np.zeros((K, n_layers), np.int32)
    perm = np.zeros((K, n_slots), np.int32)
    for k, (part, mapping) in enumerate(pairs):
        cores[k] = part.cores
        used = [int(p) for p in mapping.phys]
        taken = set(used)
        perm[k] = used + [s for s in range(n_slots) if s not in taken]
    return cores, perm


def price_population_device(net: SimNetwork, profile: ChipProfile,
                            cache: PricingCache, cores,
                            perm) -> list[SimReport]:
    """Device-resident re-pricing entry point: price already-stacked (and
    possibly already-on-device) genome rows — ``cores`` (K, n_layers),
    ``perm`` (K, n_slots) — and assemble host :class:`SimReport`\\ s.

    This is the report-producing wrapper over
    :meth:`DevicePopulationPricer.price`; loops that stay on device (the
    ``engine="device"`` search) skip it and compose
    :meth:`DevicePopulationPricer.price_row` into their own jitted step,
    only materializing reports for the candidates they return.
    """
    pricer = device_pricer(net, profile, cache)
    n_layers, n_slots = len(cache.layers), int(profile.n_cores)
    if (np.ndim(cores) != 2 or np.ndim(perm) != 2
            or cores.shape[1] != n_layers or perm.shape[1] != n_slots
            or cores.shape[0] != perm.shape[0]):
        raise ValueError(
            f"genome rows must be cores (K, {n_layers}) and perm "
            f"(K, {n_slots}) for this (network, profile); got "
            f"cores {np.shape(cores)} and perm {np.shape(perm)}")
    out = pricer.price(cores, perm)
    n_logical = np.asarray(jax.device_get(cores), np.int64).sum(axis=1)
    return _assemble_reports(out, n_logical, cache,
                             pricer.weight_density)


def price_population_sharded(net: SimNetwork, profile: ChipProfile,
                             cache: PricingCache, cores, perm, *,
                             mesh=None) -> list[SimReport]:
    """Mesh-aware population pricing: the K axis sharded over a 1-D
    ``("island",)`` device mesh.

    Each device prices its own block of genome rows with the same traced
    :meth:`DevicePopulationPricer.price_row` program the single-device
    backend vmaps, inside one ``shard_map``; per-row outputs are therefore
    within float64 roundoff of ``backend="device"`` (pricing is row-
    independent).  ``mesh`` defaults to
    :func:`repro.distributed.sharding.island_mesh` over every visible
    device; K is padded up to a multiple of the island count with copies
    of row 0 and the padding is dropped from the returned reports.

    This is the report-producing wrapper; the sharded evolutionary search
    (``engine="sharded"``) composes ``price_row`` directly into its own
    per-island generation step instead (``repro.core.device_search``).
    """
    from jax.sharding import PartitionSpec
    pricer = device_pricer(net, profile, cache)
    n_layers, n_slots = len(cache.layers), int(profile.n_cores)
    if (np.ndim(cores) != 2 or np.ndim(perm) != 2
            or cores.shape[1] != n_layers or perm.shape[1] != n_slots
            or cores.shape[0] != perm.shape[0]):
        raise ValueError(
            f"genome rows must be cores (K, {n_layers}) and perm "
            f"(K, {n_slots}) for this (network, profile); got "
            f"cores {np.shape(cores)} and perm {np.shape(perm)}")
    if mesh is None:
        from repro.distributed.sharding import island_mesh
        mesh = island_mesh()
    n_islands = int(mesh.shape["island"])
    K = int(np.shape(cores)[0])
    pad = (-K) % n_islands
    cores_h = np.asarray(jax.device_get(cores), np.int32)
    perm_h = np.asarray(jax.device_get(perm), np.int32)
    if pad:
        cores_h = np.concatenate([cores_h, np.repeat(cores_h[:1], pad, 0)])
        perm_h = np.concatenate([perm_h, np.repeat(perm_h[:1], pad, 0)])
    fns = pricer.__dict__.setdefault("_sharded_price_fns", {})
    mesh_key = (n_islands, tuple(d.id for d in mesh.devices.flat))
    if mesh_key not in fns:
        spec = PartitionSpec("island")
        fns[mesh_key] = jax.jit(jax.shard_map(
            jax.vmap(pricer.price_row), mesh=mesh,
            in_specs=(spec, spec), out_specs=spec, check_vma=False))
    with jax.enable_x64(True):
        out = jax.device_get(fns[mesh_key](jnp.asarray(cores_h),
                                           jnp.asarray(perm_h)))
    if pad:
        out = {k: v[:K] for k, v in out.items()}
    n_logical = cores_h[:K].astype(np.int64).sum(axis=1)
    return _assemble_reports(out, n_logical, cache,
                             pricer.weight_density)


def _simulate_reference(net: SimNetwork, xs: np.ndarray,
                        profile: ChipProfile, part: Partition,
                        mapping: Mapping, compute=None) -> SimReport:
    """Step-major reference engine (original implementation)."""
    outputs, all_counters = net.run(xs, compute=compute)

    T = xs.shape[0]
    n_layers = len(net.layers)
    n_logical = part.total_cores
    times = np.zeros(T)
    energies = np.zeros(T)
    sum_core_synops = np.zeros(n_logical)
    sum_core_acts = np.zeros(n_logical)
    sum_core_msgs = np.zeros(n_logical)
    max_synops_steps = np.zeros(T)
    max_acts_steps = np.zeros(T)
    max_link_steps = np.zeros(T)
    stage_votes = {"memory": 0, "compute": 0, "traffic": 0, "barrier": 0}
    total_msgs = 0.0
    total_neuron_steps = 0.0

    offsets = np.concatenate([[0], np.cumsum(part.cores)]).astype(int)

    for t in range(T):
        layer_cc = [aggregate_layer(all_counters[t][l], l, part, net, profile)
                    for l in range(n_layers)]
        mem_all, act_all = [], []
        msgs_out_per_core = []
        e_events = 0.0
        for l, cc in enumerate(layer_cc):
            mem, act = core_times(cc, net.layers[l].neuron_model, profile)
            mem_all.append(mem)
            act_all.append(act)
            msgs_out_per_core.append(cc.msgs_out)
            sl = slice(offsets[l], offsets[l + 1])
            sum_core_synops[sl] += cc.synops
            sum_core_acts[sl] += cc.acts
            sum_core_msgs[sl] += cc.msgs_out
            e_events += (profile.e_fetch * cc.synops.sum()
                         + profile.e_mac * cc.macs.sum()
                         + (profile.e_decode * cc.synops.sum()
                            if cc.sparse_format else 0.0)
                         + profile.e_act * cc.acts.sum()
                         * (profile.neuron_cost(net.layers[l].neuron_model)
                            / profile.c_act))
            total_msgs += cc.msgs_out.sum()
            total_neuron_steps += cc.neurons.sum()

        traffic = route_step(part, mapping, msgs_out_per_core, profile)
        mem_cat = np.concatenate(mem_all)
        act_cat = np.concatenate(act_all)
        core_time = np.maximum(mem_cat, act_cat) + profile.t_core_fixed
        traffic_time = (profile.c_route * traffic.max_router_load
                        + profile.c_inject
                        * float(traffic.inject_per_core.max(initial=0.0)))

        if profile.synchronous:
            t_compute = float(core_time.max(initial=0.0))
            t_step = max(t_compute, traffic_time) + profile.t_barrier
            which = ("traffic" if traffic_time > t_compute else
                     ("memory" if mem_cat.max(initial=0.0)
                      >= act_cat.max(initial=0.0) else "compute"))
        else:
            per_layer = [float(np.maximum(m, a).max(initial=0.0))
                         for m, a in zip(mem_all, act_all)]
            t_step = sum(per_layer) + profile.c_msg_hop * traffic.total_hops / max(
                part.total_cores, 1)
            which = "memory"

        n_active = int(np.sum(np.concatenate(
            [cc.synops + cc.msgs_out for cc in layer_cc]) > 0)) or n_logical
        e_hops = profile.e_msg_hop * traffic.total_hops
        energies[t] = (t_step * (profile.p_idle + profile.p_core * n_active)
                       + e_events + e_hops)
        times[t] = t_step
        stage_votes[which] += 1
        syn_step = np.concatenate([cc.synops for cc in layer_cc])
        acts_step = np.concatenate([cc.acts for cc in layer_cc])
        max_synops_steps[t] = syn_step.max(initial=0.0)
        max_acts_steps[t] = acts_step.max(initial=0.0)
        max_link_steps[t] = traffic.max_router_load

    return _finish_report(
        net, part, T, times, energies, outputs,
        mean_synops=sum_core_synops / T,
        mean_acts=sum_core_acts / T,
        mean_msgs=sum_core_msgs / T,
        max_synops_steps=max_synops_steps, max_acts_steps=max_acts_steps,
        max_link_steps=max_link_steps,
        total_msgs=total_msgs, total_neuron_steps=total_neuron_steps,
        stage_votes=stage_votes)
