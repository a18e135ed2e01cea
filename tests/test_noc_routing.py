"""NoC routing without per-router-pair tables.

``route_batch`` / ``route_step`` fold each layer's X-then-Y paths into
coverage tables over the grid's rows and columns.  Below is a frozen copy
of the routing they replaced — a per-core ``(n_logical, R*R)`` flow matrix
against an ``(R*R, R)`` path incidence — and the two must agree bit for bit
wherever the old tables fit.  On a 48-chip Loihi 2-class mesh (30 x 48
routers) the old flow matrix of 5,400 cores would take 84 GB; the new
routing prices it in bounded memory.

The population routing on the device, ``flow_structures_rows``, is held to
the same frozen flow matrix folded through the frozen path incidence and
hop tables, bit for bit.
"""

import dataclasses
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.neuromorphic.noc import (Mapping, flow_structures_rows,
                                    incidence_tables, ordered_mapping,
                                    random_mapping, route_batch, route_step,
                                    strided_mapping)
from repro.neuromorphic.partition import Partition
from repro.neuromorphic.platform import loihi2_like


# ------------------------------------------------------- the frozen routing

def _old_path_incidence(grid):
    rows, cols = grid
    R = rows * cols
    inc = np.zeros((R * R, R), np.float32)
    for s in range(R):
        r1, c1 = divmod(s, cols)
        for d in range(R):
            r2, c2 = divmod(d, cols)
            nodes = [s]
            step = 1 if c2 >= c1 else -1
            for c in range(c1 + step, c2 + step, step) if c1 != c2 else []:
                nodes.append(r1 * cols + c)
            step = 1 if r2 >= r1 else -1
            for r in range(r1 + step, r2 + step, step) if r1 != r2 else []:
                nodes.append(r * cols + c2)
            inc[s * R + d, nodes] = 1.0
    return inc


def _old_pair_hops(grid):
    rows, cols = grid
    r = np.arange(rows * cols)
    rr, cc = r // cols, r % cols
    return (np.abs(rr[:, None] - rr[None, :])
            + np.abs(cc[:, None] - cc[None, :])).astype(np.float32).reshape(-1)


def _old_core_flows(cores, phys, grid, n_cores_phys):
    rows, cols = grid
    R = rows * cols
    cpr = max(1, n_cores_phys // R)
    routers = np.asarray([p // cpr for p in phys])
    n_logical = int(sum(cores))
    P = np.zeros((n_logical, R * R), np.float64)
    dup = np.zeros(n_logical, np.float64)
    offsets = np.concatenate([[0], np.cumsum(cores)]).astype(int)
    for l in range(len(cores)):
        src_idx = np.arange(offsets[l], offsets[l + 1])
        dst = (routers[offsets[l + 1]:offsets[l + 2]] if l + 1 < len(cores)
               else np.asarray([0]))
        dup[src_idx] = len(dst)
        for g in src_idx:
            np.add.at(P[g], routers[g] * R + dst, 1.0)
    return P, dup


def _old_route_batch(part, mapping, msgs, prof):
    P, dup = _old_core_flows(part.cores, mapping.phys, prof.grid,
                              prof.n_cores)
    m = np.asarray(msgs, np.float64)
    flow = m @ P
    return (flow @ _old_path_incidence(prof.grid),
            flow @ _old_pair_hops(prof.grid), m * dup)


def _old_route_step(part, mapping, per_layer, prof):
    rows, cols = prof.grid
    R = rows * cols
    cpr = max(1, prof.n_cores // R)
    flow = np.zeros((R, R), np.float64)
    inject = np.zeros(part.total_cores, np.float64)
    off = np.concatenate([[0], np.cumsum(part.cores)]).astype(int)
    routers = np.asarray([p // cpr for p in mapping.phys])
    for l in range(len(part.cores)):
        src = np.arange(off[l], off[l + 1])
        msgs = np.asarray(per_layer[l], np.float64)
        dst = (routers[off[l + 1]:off[l + 2]] if l + 1 < len(part.cores)
               else np.asarray([0]))
        inject[src] += msgs * len(dst)
        np.add.at(flow, (routers[src][:, None].repeat(len(dst), 1),
                         np.broadcast_to(dst, (len(src), len(dst)))),
                  msgs[:, None])
    return (flow.reshape(-1) @ _old_path_incidence(prof.grid),
            float(flow.reshape(-1) @ _old_pair_hops(prof.grid)), inject)


# ------------------------------------------------------------------ cases

GRIDS = [((5, 6), 120), ((4, 5), 80), ((3, 3), 9), ((10, 12), 480)]


def _cases(grid, n_cores, seed):
    prof = dataclasses.replace(loihi2_like(), grid=grid, n_cores=n_cores)
    rng = np.random.default_rng(seed)
    n_layers = int(rng.integers(1, 6))
    total = int(rng.integers(n_layers, max(n_layers + 1, n_cores // 2) + 1))
    cuts = np.sort(rng.choice(np.arange(1, total), n_layers - 1,
                              replace=False)) if n_layers > 1 else []
    cores = tuple(int(c) for c in np.diff(np.concatenate(
        [[0], cuts, [total]])))
    part = Partition(cores)
    maps = [ordered_mapping(part, prof), strided_mapping(part, prof),
            random_mapping(part, prof, rng)]
    msgs = rng.integers(0, 40, (7, total)).astype(np.float64)
    msgs[rng.random(msgs.shape) < 0.3] = 0.0
    return prof, part, maps, msgs


@pytest.mark.quick
@pytest.mark.parametrize("grid,n_cores", GRIDS)
@pytest.mark.parametrize("seed", range(3))
def test_route_batch_is_bit_identical_to_the_frozen_routing(grid, n_cores,
                                                            seed):
    prof, part, maps, msgs = _cases(grid, n_cores, seed)
    for mapping in maps:
        got = route_batch(part, mapping, msgs, prof)
        loads, hops, inject = _old_route_batch(part, mapping, msgs, prof)
        assert np.array_equal(got.router_loads, loads)
        assert np.array_equal(got.total_hops, hops)
        assert np.array_equal(got.inject_per_core, inject)


@pytest.mark.quick
@pytest.mark.parametrize("grid,n_cores", GRIDS)
def test_route_step_is_bit_identical_to_the_frozen_routing(grid, n_cores):
    prof, part, maps, msgs = _cases(grid, n_cores, 11)
    off = np.concatenate([[0], np.cumsum(part.cores)]).astype(int)
    for mapping in maps:
        for t in range(msgs.shape[0]):
            per_layer = [msgs[t, off[l]:off[l + 1]]
                         for l in range(len(part.cores))]
            got = route_step(part, mapping, per_layer, prof)
            loads, hops, inject = _old_route_step(part, mapping, per_layer,
                                                  prof)
            assert np.array_equal(got.router_loads, loads)
            assert got.total_hops == hops
            assert np.array_equal(got.inject_per_core, inject)


@pytest.mark.quick
@pytest.mark.parametrize("grid,n_cores", GRIDS)
def test_flow_structures_rows_are_bit_identical_to_the_frozen_fold(grid,
                                                                   n_cores):
    """The device population routing equals the frozen flow matrix folded
    through the frozen path incidence and hop tables: ``PL = P @ inc``,
    ``ph = P @ hops``, and the duplication factors, with zero rows on the
    padded cores."""
    rows, cols = grid
    cpr = max(1, n_cores // (rows * cols))
    inc = _old_path_incidence(grid).astype(np.float64)
    hops = _old_pair_hops(grid).astype(np.float64)
    inc3, hops2 = incidence_tables(grid)
    for seed in (0, 11):                # seed 11 is a single-layer genome
        _, part, maps, _ = _cases(grid, n_cores, seed)
        n = part.total_cores
        n_pad = n + 3
        L = len(part.cores)
        lid = np.zeros(n_pad, np.int32)
        lid[:n] = np.repeat(np.arange(L), part.cores)
        alive = np.zeros(n_pad, np.float64)
        alive[:n] = 1.0
        for mapping in maps:
            P, dup = _old_core_flows(part.cores, mapping.phys, grid,
                                      n_cores)
            router = np.zeros(n_pad, np.int32)
            router[:n] = np.asarray(mapping.phys) // cpr
            with jax.enable_x64(True):
                PL, ph, dup_d = (np.asarray(a) for a in flow_structures_rows(
                    jnp.asarray(lid), jnp.asarray(router),
                    jnp.asarray(alive), L, jnp.asarray(inc3),
                    jnp.asarray(hops2)))
            assert np.array_equal(PL[:n], P @ inc)
            assert np.array_equal(ph[:n], P @ hops)
            assert np.array_equal(dup_d[:n], dup)
            assert not (PL[n:].any() or ph[n:].any() or dup_d[n:].any())


@pytest.mark.quick
def test_a_48_chip_mesh_routes_in_bounded_memory():
    """5,400 cores in 18 layers on a (30, 48) router grid, 64 steps: the
    old flow matrix alone would be 5,400 x 1,440**2 float64 words."""
    prof = dataclasses.replace(loihi2_like(), grid=(30, 48), n_cores=5760)
    cores = (24, 672, 12, 336, 780, 24, 252, 300, 300, 300, 300, 300, 300,
             300, 300, 300, 300, 300)
    part = Partition(cores)
    assert part.total_cores == 5400
    rng = np.random.default_rng(5)
    msgs = rng.integers(0, 24, (64, part.total_cores)).astype(np.float64)
    tracemalloc.start()
    try:
        for mapping in (ordered_mapping(part, prof),
                        Mapping(tuple(rng.permutation(5760)[:5400]))):
            got = route_batch(part, mapping, msgs, prof)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert peak < 64e6, peak
    assert got.router_loads.shape == (64, 1440)
    # every message is injected once per destination and delivered once
    n_dst = np.array([c for c in cores[1:]] + [1], np.float64)
    per_core = np.repeat(n_dst, cores)
    assert np.array_equal(got.inject_per_core, msgs * per_core)
    assert np.all(got.router_loads.sum(axis=1)
                  >= (msgs * per_core).sum(axis=1))
