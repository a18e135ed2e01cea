"""JAX-based neuromorphic accelerator simulator.

Implements the macro-architecture of paper Fig. 1 — neurocores with co-located
synaptic memory / neuron state / compute, connected by a 2-D mesh NoC, running
barrier-synchronized timesteps — with per-platform cost profiles standing in
for the three real accelerators characterized in the paper (AKD1000, Speck,
Loihi 2).  Functional execution and event counters are exact; times/energies
come from the cost model (relative units, matching the paper's normalized
reporting).
"""

from repro.neuromorphic.platform import (ChipProfile, akd1000_like, loihi2_like,
                                         speck_like)
from repro.neuromorphic.compute import (DenseCompute, EventCompute,
                                        LayerCompute, get_compute,
                                        register_compute)
from repro.neuromorphic.frontend import (AttnSpec, CompiledNetwork,
                                         LayerSpec, attention_probe,
                                         compile_network, excluded_params,
                                         lowering_spec)
from repro.neuromorphic.network import (BatchCounters, Router, SimLayer,
                                        SimNetwork, fc_network, make_inputs,
                                        programmed_fc_network)
from repro.neuromorphic.partition import Partition, minimal_partition
from repro.neuromorphic.noc import (Mapping, flow_structures_rows,
                                    incidence_tables, ordered_mapping,
                                    random_mapping, route_batch,
                                    strided_mapping)
from repro.neuromorphic.timestep import (DevicePopulationPricer,
                                         LayerStageTimes, PricingCache,
                                         SimReport, device_pricer,
                                         layer_stage_times,
                                         precompute_pricing,
                                         price_candidate,
                                         price_population_device, simulate,
                                         simulate_population)

__all__ = [
    "ChipProfile", "akd1000_like", "loihi2_like", "speck_like",
    "DenseCompute", "EventCompute", "LayerCompute", "get_compute",
    "register_compute",
    "AttnSpec", "CompiledNetwork", "LayerSpec", "attention_probe",
    "compile_network", "excluded_params", "lowering_spec",
    "BatchCounters", "Router", "SimLayer", "SimNetwork", "fc_network",
    "make_inputs",
    "programmed_fc_network",
    "Partition", "minimal_partition",
    "Mapping", "flow_structures_rows", "incidence_tables",
    "ordered_mapping", "random_mapping", "route_batch", "strided_mapping",
    "DevicePopulationPricer", "LayerStageTimes", "PricingCache",
    "SimReport", "device_pricer", "layer_stage_times",
    "precompute_pricing", "price_candidate", "price_population_device",
    "simulate", "simulate_population",
]
