"""Floorline-informed partitioning & mapping optimization (paper §VI-B).

The paper's stage-2 procedure, verbatim in structure:

1. Initialize at the minimum neurocore utilization with a good heuristic
   (strided) mapping — likely memory-bound.
2. **Memory assumption**: find the core with the most synops, partition its
   layer further.  If the step helps, keep tracing down the memory slope;
   if not, *backtrack* (greater utilization without synop improvement costs
   power).
3. **Compute assumption**: same loop keyed on max activation computes.
4. **Traffic assumption**: improve the mapping (move the highest-output
   cores onto separate router paths — here: re-stride / traffic-greedy map).
5. Cycle through the assumptions; stop when out of cores, when energy
   worsens without timing benefit, or when no assumption yields improvement
   (the workload hit its true boundary for its sparsity dynamics).

The evaluator is any callable (partition, mapping) -> SimReport, so the same
optimizer drives the neuromorphic simulator and, through an adapter, the TPU
sharding hillclimb in :mod:`repro.distributed.autoshard`.  The canonical
implementation is :class:`SimEvaluator`: it builds the batched engine's
pricing cache once, prices every candidate from it (single candidates and
whole populations), and counts evaluations — the shared currency that makes
the greedy walk here and the evolutionary search in
:mod:`repro.core.search` comparable at iso-evaluations.  The move vocabulary
(:meth:`Partition.split` / :meth:`Partition.merge` plus a re-mapping of the
logical->physical placement, gated by :func:`can_split` /
``validate_partition``) is likewise shared by both optimizers.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import numpy as np

from repro.core.analytical import Bottleneck
from repro.neuromorphic.network import SimNetwork
from repro.neuromorphic.noc import Mapping, strided_mapping
from repro.neuromorphic.partition import (Partition, max_cores_for_layer,
                                          minimal_partition, validate_partition)
from repro.neuromorphic.platform import ChipProfile
from repro.neuromorphic.timestep import (SimReport, precompute_pricing,
                                         price_candidate, simulate,
                                         simulate_population)

#: Anything that prices a (partition, mapping) candidate.  Both optimizers
#: (greedy §VI-B and the evolutionary search) accept any such callable;
#: :class:`SimEvaluator` is the standard one.
Evaluator = Callable[[Partition, Mapping], SimReport]


class SimEvaluator:
    """Evaluation-counting pricing gateway shared by both optimizers.

    Wraps one (net, xs, profile) workload: the functional run and per-layer
    counter cumsums are computed once (``engine="batched"``), after which
    every candidate — single or population — is priced counter-free from the
    cache.  ``n_evals`` counts priced candidates, the budget unit for
    greedy-vs-evolutionary comparisons (``benchmarks/search_mapping.py``).

    With ``engine="reference"`` candidates are priced by the step-major
    engine (no cache); results are identical, just slower — useful for
    auditing the cache path at small scale.

    ``population_backend`` selects how :meth:`evaluate_population` prices a
    generation — one of the population backends of
    :func:`~repro.neuromorphic.timestep.simulate_population`: ``"numpy"``
    (stacked gathers + per-candidate NumPy math, bit-identical to
    ``simulate`` — the reference), ``"device"`` (the genome rows are the
    input of one jitted ``jax.vmap`` over the padded population axis that
    builds every per-core structure and prices on device,
    float64-roundoff-identical; see ``docs/simulator.md``), or
    ``"sharded"`` (the device program with the population axis sharded
    over a device mesh).

    The evaluator is also the pricing-cache and evaluation-ledger host for
    the device-resident search (``evolutionary_search(...,
    engine="device")``), which prices inside its own jitted generation
    step and charges ``n_evals`` here per generation.

    A backend failure (compile error, device OOM, runtime fault, or an
    injected one) propagates by default, so a run that exits cleanly ran
    on the backend it asked for.  ``fallback=True`` opts in to graceful
    degradation (``docs/robustness.md``): a failure is retried per
    ``retry`` and then demoted down the ``device -> numpy`` chain
    (sticky; logged; recorded in :attr:`demotions`), and the device and
    sharded search engines driven by this evaluator demote to their host
    mirrors the same way.  The backends agree at float64 roundoff, so a
    mid-run demotion perturbs a search trajectory by at most rtol=1e-9
    against a numpy-only run.  ``fault_plan`` is the deterministic
    fault-injection hook (:class:`repro.core.resilience.FaultPlan`):
    scripted backend failures and NaN pricing rows for the robustness
    suite.
    """

    def __init__(self, net: SimNetwork, xs: np.ndarray, profile: ChipProfile,
                 *, engine: str | None = None, cache=None,
                 population_backend: str = "numpy", compute=None,
                 fault_plan=None, fallback: bool = False, retry=None,
                 sparsity_profile=None):
        from repro.core.resilience import FallbackChain
        from repro.neuromorphic import timestep
        # A trained SparsityProfile is programmed onto the network ONCE,
        # here — every candidate, backend, and search engine (the device/
        # sharded engines build their pricers from this evaluator's cache)
        # then prices the profiled workload with unchanged parity.
        if sparsity_profile is not None:
            if cache is not None:
                raise ValueError("sparsity_profile cannot be combined with "
                                 "a shared cache: the cache is bound to the "
                                 "un-profiled network")
            net = sparsity_profile.apply(net)
        self.sparsity_profile = sparsity_profile
        self.net, self.xs, self.profile = net, xs, profile
        self.engine = engine or timestep.DEFAULT_ENGINE
        self.population_backend = population_backend
        #: per-layer synaptic compute backend of the functional run
        #: ("dense" / "event" / a LayerCompute instance; None -> the
        #: process default) — counters are exact across backends, so the
        #: cache and every report it prices are backend-agnostic
        self.compute = compute
        # ``cache=`` shares one PricingCache between evaluators that only
        # differ in their evaluation counters (e.g. benchmark arms)
        self.cache = (cache or precompute_pricing(net, xs, profile,
                                                  compute=compute)
                      if self.engine == "batched" else None)
        self.n_evals = 0
        self.fault_plan = fault_plan
        self.fallback = fallback
        self._chain = (FallbackChain(population_backend, retry=retry)
                       if fallback else None)

    @property
    def demotions(self) -> list:
        """Fallback-chain demotion records, oldest first (empty when the
        chain is disabled or never fired)."""
        return self._chain.demotions if self._chain is not None else []

    @property
    def active_backend(self) -> str:
        """The population backend currently in use (differs from
        ``population_backend`` after a demotion)."""
        return (self._chain.backend if self._chain is not None
                else self.population_backend)

    def __call__(self, part: Partition, mapping: Mapping) -> SimReport:
        self.n_evals += 1
        if self.cache is not None:
            return price_candidate(self.net, self.profile, self.cache,
                                   part, mapping)
        return simulate(self.net, self.xs, self.profile, part, mapping,
                        engine=self.engine, compute=self.compute)

    def evaluate_population(self, candidates) -> list[SimReport]:
        """Price a list of (partition, mapping) pairs; one stacked gather
        per layer (or one jitted program — ``population_backend="device"``
        / ``"sharded"``) when the pricing cache is live.  Backend failures
        retry, then demote down the fallback chain (see the class
        docstring); scripted :class:`FaultPlan` faults inject here."""
        cands = list(candidates)
        self.n_evals += len(cands)
        if self.cache is not None:
            def attempt(backend):
                if self.fault_plan is not None:
                    self.fault_plan.check(backend)
                return simulate_population(self.net, self.xs, self.profile,
                                           cands, cache=self.cache,
                                           backend=backend)
            if self._chain is not None:
                reports = self._chain.run(attempt)
            else:
                reports = attempt(self.population_backend)
        else:
            reports = [simulate(self.net, self.xs, self.profile, p, m,
                                engine=self.engine, compute=self.compute)
                       for p, m in cands]
        if self.fault_plan is not None:
            reports = self.fault_plan.corrupt(reports)
        return reports


@dataclasses.dataclass
class OptStep:
    """One accepted/rejected move in the iteration log (EXPERIMENTS §Perf
    mirrors this structure for the TPU hillclimb)."""

    iteration: int
    assumption: Bottleneck
    move: str
    partition: Partition
    time: float
    energy: float
    max_synops: float
    accepted: bool
    note: str = ""


@dataclasses.dataclass
class OptimizationResult:
    partition: Partition
    mapping: Mapping
    report: SimReport
    history: list[OptStep]

    @property
    def trace(self) -> list[tuple[float, float]]:
        """(max_synops, time) path of accepted steps — the floorline trace."""
        pts = [(s.max_synops, s.time) for s in self.history if s.accepted]
        return pts


def _argmax_layer(per_core: np.ndarray, part: Partition) -> int:
    """Layer owning the max-loaded core (the M0 bottleneck unit)."""
    core_layers = part.core_layer_ids()
    return int(core_layers[int(np.argmax(per_core))])


def _bottleneck_layers(per_core: np.ndarray, part: Partition,
                       tie_tol: float = 0.05) -> list[int]:
    """All layers owning a core within ``tie_tol`` of the max load.  The
    paper splits the single argmax layer; when several layers tie (uniform
    workloads) a single split cannot move the global max, so we split the
    tied set together — a strict generalization that reduces to the paper's
    move when the max is unique."""
    core_layers = part.core_layer_ids()
    mx = float(np.max(per_core))
    hot = np.asarray(per_core) >= (1.0 - tie_tol) * mx
    return sorted({int(l) for l in core_layers[hot]})


def can_split(net: SimNetwork, part: Partition, layer: int,
              profile: ChipProfile) -> bool:
    """True iff the split move is legal for ``layer``: granularity, chip
    core budget, and per-core capacities all hold after the split.  Shared
    gate for the greedy optimizer's and the evolutionary search's split
    moves."""
    if part.cores[layer] >= max_cores_for_layer(net, layer):
        return False
    if part.total_cores + 1 > profile.n_cores:
        return False
    return validate_partition(net, part.split(layer), profile)


def optimize_partitioning(
    net: SimNetwork,
    profile: ChipProfile,
    evaluate: Evaluator,
    *,
    max_iters: int = 64,
    time_improvement_tol: float = 0.01,
    energy_guard: bool = True,
    make_mapping: Callable[[Partition, ChipProfile], Mapping] = strided_mapping,
) -> OptimizationResult:
    """Run the §VI-B iterative backtracking procedure.

    ``evaluate`` is any :data:`Evaluator` — a callable
    ``(Partition, Mapping) -> SimReport`` — typically a
    :class:`SimEvaluator` so evaluations are counted and priced from one
    shared functional run.  Moves are accepted only when time improves by
    more than ``time_improvement_tol`` (relative) and, under
    ``energy_guard``, energy does not regress without a timing benefit.
    Returns the best (partition, mapping, report) plus the full accept /
    backtrack history, whose accepted prefix traces the floorline.
    """
    part = minimal_partition(net, profile)
    mapping = make_mapping(part, profile)
    best = evaluate(part, mapping)
    history: list[OptStep] = [OptStep(
        iteration=0, assumption=Bottleneck.MEMORY, move="init:minimal+strided",
        partition=part, time=best.time_per_step, energy=best.energy_per_step,
        max_synops=best.max_synops, accepted=True, note="baseline")]

    assumptions = [Bottleneck.MEMORY, Bottleneck.COMPUTE, Bottleneck.TRAFFIC]
    a_idx = 0
    stale = 0          # consecutive assumptions with no accepted move
    it = 0
    while it < max_iters and stale < len(assumptions):
        it += 1
        assumption = assumptions[a_idx]
        accepted = False
        if assumption in (Bottleneck.MEMORY, Bottleneck.COMPUTE):
            per_core = (best.per_core_synops if assumption is Bottleneck.MEMORY
                        else best.per_core_acts)
            layers = [l for l in _bottleneck_layers(per_core, part)
                      if can_split(net, part, l, profile)]
            cand_part = part
            for l in layers:
                if validate_partition(net, cand_part.split(l), profile):
                    cand_part = cand_part.split(l)
            if cand_part.cores != part.cores:
                cand_map = make_mapping(cand_part, profile)
                rep = evaluate(cand_part, cand_map)
                time_gain = (best.time_per_step - rep.time_per_step) \
                    / max(best.time_per_step, 1e-30)
                energy_ok = (not energy_guard
                             or rep.energy_per_step <= best.energy_per_step
                             or time_gain > time_improvement_tol)
                if time_gain > time_improvement_tol and energy_ok:
                    part, mapping, best = cand_part, cand_map, rep
                    accepted = True
                history.append(OptStep(
                    iteration=it, assumption=assumption,
                    move=(f"split layers {layers} -> "
                          f"{[cand_part.cores[l] for l in layers]} cores"),
                    partition=cand_part, time=rep.time_per_step,
                    energy=rep.energy_per_step, max_synops=rep.max_synops,
                    accepted=accepted,
                    note="" if accepted else "backtracked (no benefit)"))
            else:
                history.append(OptStep(
                    iteration=it, assumption=assumption, move="no split available",
                    partition=part, time=best.time_per_step,
                    energy=best.energy_per_step, max_synops=best.max_synops,
                    accepted=False, note="out of cores / granularity"))
        else:   # TRAFFIC: optimize the mapping only (synops intensity fixed)
            cand_map = _traffic_greedy_mapping(part, profile, best)
            if tuple(cand_map.phys) != tuple(mapping.phys):
                rep = evaluate(part, cand_map)
                gain = (best.time_per_step - rep.time_per_step) \
                    / max(best.time_per_step, 1e-30)
                if gain > time_improvement_tol:
                    mapping, best = cand_map, rep
                    accepted = True
                history.append(OptStep(
                    iteration=it, assumption=assumption,
                    move=f"remap ({cand_map.name})", partition=part,
                    time=rep.time_per_step, energy=rep.energy_per_step,
                    max_synops=rep.max_synops, accepted=accepted,
                    note="" if accepted else "backtracked"))
            else:
                history.append(OptStep(
                    iteration=it, assumption=assumption, move="mapping unchanged",
                    partition=part, time=best.time_per_step,
                    energy=best.energy_per_step, max_synops=best.max_synops,
                    accepted=False))
        if accepted:
            stale = 0            # keep working the same assumption
        else:
            stale += 1
            a_idx = (a_idx + 1) % len(assumptions)

    return OptimizationResult(partition=part, mapping=mapping, report=best,
                              history=history)


def _traffic_greedy_mapping(part: Partition, profile: ChipProfile,
                            report: SimReport) -> Mapping:
    """Traffic move (§VI-B): place the highest-output cores onto separate
    router paths — greedy round-robin over router tiles by descending
    message count, so hot cores never share a router's injection port."""
    from repro.neuromorphic.noc import cores_per_router, n_router_tiles

    n = part.total_cores
    cpr = cores_per_router(profile)
    n_routers = n_router_tiles(profile)
    order = np.argsort(-report.per_core_msgs_out)      # busiest first
    slots_by_router = [[r * cpr + s for s in range(cpr)]
                       for r in range(n_routers)]
    phys = [0] * n
    r = 0
    for logical in order:
        placed = False
        for _ in range(n_routers):
            if slots_by_router[r]:
                phys[int(logical)] = slots_by_router[r].pop(0)
                r = (r + 1) % n_routers
                placed = True
                break
            r = (r + 1) % n_routers
        if not placed:
            raise RuntimeError("ran out of physical slots")
    return Mapping(tuple(phys), name="traffic_greedy")
