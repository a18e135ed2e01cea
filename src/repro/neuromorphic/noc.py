"""Network-on-chip model: router-shared core placement + XY-routed congestion.

Mirrors the paper's §V-F traffic mechanism: several neurocores share each NoC
router tile (as on Loihi), so an *ordered* mapping that places a layer's
(equally busy) cores on consecutive slots concentrates its injection load on
a few routers — "the highest output neurocores ... are physically close to
one another and create congestion on their shared NoC routers".  A *strided*
mapping spreads same-layer cores across router paths (Fig. 8).

Messages from every core of layer l are duplicated (unicast per destination)
to every core of layer l+1 (broadcast, §III-C); the last layer's outputs
route to the chip I/O port at router 0.  Router load counts injections,
transits, and deliveries; dimension-ordered (X-then-Y) routing on the router
grid.

:func:`route_batch` / :func:`route_step` price one candidate without any
per-core or per-router-pair table: a layer's messages are summed by source
router, its next layer's cores counted by destination router, and the
X-then-Y paths of all (source, destination) pairs folded into two small
coverage tables per layer, a row table over the grid's columns and a
column table over its rows, so memory grows with the grid and not with its
square.  A population is routed on the device by
:func:`flow_structures_rows`, one candidate at a time inside the jitted
population pricer; it folds per-layer destination counts through the
``(R, R, R)`` path-incidence tensor of :func:`incidence_tables`, which the
grids of today's searches hold.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

# jnp is only touched by the device-resident flow-structure path
# (:func:`flow_structures_rows`); the host paths below stay pure NumPy.
import jax.numpy as jnp

from repro import tracing
from repro.neuromorphic.partition import Partition
from repro.neuromorphic.platform import ChipProfile


@dataclasses.dataclass(frozen=True)
class Mapping:
    """logical core index -> physical core slot."""

    phys: tuple[int, ...]
    name: str = "custom"

    def __post_init__(self):
        if len(set(self.phys)) != len(self.phys):
            raise ValueError("mapping assigns two logical cores to one slot")


def ordered_mapping(part: Partition, profile: ChipProfile) -> Mapping:
    """Sequential placement — the congestion-prone Loihi-1 heuristic [27]."""
    n = part.total_cores
    if n > profile.n_cores:
        raise ValueError("partition exceeds physical cores")
    return Mapping(tuple(range(n)), name="ordered")


def strided_mapping(part: Partition, profile: ChipProfile) -> Mapping:
    """Strided placement: consecutive logical cores land on different
    routers, so same-layer cores use disjoint router paths."""
    n = part.total_cores
    if n > profile.n_cores:
        raise ValueError("partition exceeds physical cores")
    n_routers = n_router_tiles(profile)
    cpr = cores_per_router(profile)
    order = [r + n_routers * s for s in range(cpr) for r in range(n_routers)]
    return Mapping(tuple(int(_router_slot_to_core(o, profile)) for o in order[:n]),
                   name="strided")


def random_mapping(part: Partition, profile: ChipProfile,
                   rng: np.random.Generator) -> Mapping:
    """Uniform random placement — population-seeding diversity for the
    evolutionary mapping search (:mod:`repro.core.search`)."""
    n = part.total_cores
    if n > profile.n_cores:
        raise ValueError("partition exceeds physical cores")
    phys = rng.permutation(profile.n_cores)[:n]
    return Mapping(tuple(int(p) for p in phys), name="random")


def cores_per_router(profile: ChipProfile) -> int:
    rows, cols = profile.grid
    return max(1, profile.n_cores // (rows * cols))


def n_router_tiles(profile: ChipProfile) -> int:
    rows, cols = profile.grid
    return rows * cols


def _router_slot_to_core(order_idx: int, profile: ChipProfile) -> int:
    """order_idx encodes (slot within router, router) -> physical core id."""
    n_routers = n_router_tiles(profile)
    slot, router = order_idx // n_routers, order_idx % n_routers
    return router * cores_per_router(profile) + slot


@functools.lru_cache(maxsize=16)
def _path_incidence(grid: tuple[int, int]) -> np.ndarray:
    """(R*R, R) matrix: entry[(src*R+dst), node] = 1 if the X-then-Y route
    from src to dst touches router ``node`` (inject/transit/deliver)."""
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


@functools.lru_cache(maxsize=16)
def _pair_hops(grid: tuple[int, int]) -> np.ndarray:
    """(R*R,) Manhattan hop counts between router pairs."""
    rows, cols = grid
    R = rows * cols
    r = np.arange(R)
    rr, cc = r // cols, r % cols
    return (np.abs(rr[:, None] - rr[None, :])
            + np.abs(cc[:, None] - cc[None, :])).astype(np.float32).reshape(-1)


@dataclasses.dataclass
class NocTraffic:
    """One timestep's routed traffic."""

    router_loads: np.ndarray      # packets touching each router
    total_hops: float             # link traversals (for hop energy)
    inject_per_core: np.ndarray   # packets injected by each logical core

    @property
    def max_router_load(self) -> float:
        return float(self.router_loads.max(initial=0.0))


@dataclasses.dataclass
class NocTrafficBatch:
    """Routed traffic for ALL timesteps at once (time-major)."""

    router_loads: np.ndarray      # (T, R) packets touching each router
    total_hops: np.ndarray        # (T,) link traversals
    inject_per_core: np.ndarray   # (T, n_logical) injected packets

    @property
    def max_router_load(self) -> np.ndarray:
        """(T,) busiest-router load per step."""
        return self.router_loads.max(axis=1, initial=0.0)


@functools.lru_cache(maxsize=16)
def incidence_tables(grid: tuple[int, int]) -> tuple[np.ndarray, np.ndarray]:
    """Per-grid routing geometry in the shapes the device path consumes:
    ``inc3[src, dst, node]`` is the (R, R, R) path-incidence tensor and
    ``hops2[src, dst]`` the (R, R) Manhattan hop matrix — float64 reshaped
    copies of the lru-cached flat tables of :func:`_path_incidence` and
    :func:`_pair_hops`."""
    rows, cols = grid
    R = rows * cols
    inc3 = _path_incidence(grid).astype(np.float64).reshape(R, R, R)
    hops2 = _pair_hops(grid).astype(np.float64).reshape(R, R)
    return inc3, hops2


def flow_structures_rows(lid, router, alive, n_layers: int, inc3, hops2):
    """ONE candidate's routing structures, built entirely on device.

    The population routing, for a genome that never leaves the
    accelerator: given the candidate's padded per-core layer ids ``lid``
    (Ncap,), router ids ``router`` (Ncap,), and float live-core mask
    ``alive`` (Ncap,), returns the ``(PL, ph, dup)`` triple — per-core
    router-load incidence ``msgs @ PL``, hop factors ``msgs @ ph``, unicast
    duplication — as ``(Ncap, R)`` / ``(Ncap,)`` / ``(Ncap,)`` jnp arrays.
    Pure jnp and shape-static, so it traces into the jitted population
    pricer and the device generation step (no host round-trip).

    Every intermediate is an exact small-integer count in float64 (layer
    destination-router counts folded through the integer incidence/hop
    tables), so the results are bit-identical to a per-core
    ``(n_logical, R*R)`` flow matrix folded through the path incidence and
    hop tables — asserted against a frozen copy of that routing by
    ``tests/test_noc_routing.py``.

    ``n_layers`` is static; ``inc3``/``hops2`` come from
    :func:`incidence_tables` (callers pass them so they become jit
    constants).  Dead slots must carry in-range ``lid``/``router`` values
    (the scatter adds their ``alive == 0`` contribution harmlessly); their
    output rows are zeroed.
    """
    R = inc3.shape[0]
    # cnt[l, r]: live cores of layer l sitting on router r
    cnt = jnp.zeros((n_layers, R), jnp.float64).at[lid, router].add(alive)
    io_row = jnp.zeros((1, R), jnp.float64).at[0, 0].set(1.0)
    # dest[l]: destination-router core counts for a source core of layer l
    # (next layer's placement; the last layer exits at the router-0 I/O port)
    dest = jnp.concatenate([cnt[1:], io_row], axis=0)            # (L, R)
    # fold per-layer dest counts through the geometry once: L x R x R work
    # instead of a per-core (Ncap, R, R) gather
    M = jnp.einsum("ld,sdr->lsr", dest, inc3)                    # (L, R, R)
    phL = dest @ hops2.T                                         # (L, R)
    PL = M[lid, router] * alive[:, None]                         # (Ncap, R)
    ph = phL[lid, router] * alive                                # (Ncap,)
    dup = dest.sum(axis=1)[lid] * alive                          # (Ncap,)
    return PL, ph, dup


@dataclasses.dataclass(frozen=True)
class _LayerRoutes:
    """One layer's routing structure under one (partition, mapping)."""

    gather: np.ndarray       # (cores, S) 0/1: core -> its router
    rows: np.ndarray         # (S,) grid row of each source router
    cols: np.ndarray         # (S,) grid column of each source router
    row_cover: np.ndarray    # (cols, cols): [c1, c] loads on row r1
    col_cover: np.ndarray    # (rows, rows, cols): [r1, r, c2]
    hops: np.ndarray         # (S,) link traversals per source message
    dup: int                 # destination cores per message


def _layer_routes(src: np.ndarray, dest: np.ndarray,
                  grid: tuple[int, int]) -> _LayerRoutes:
    """Fold the X-then-Y paths from each router of ``src`` (one per core)
    to every core of ``dest`` (destination routers, one per core) into
    coverage tables.  A path from (r1, c1) to (r2, c2) touches row r1 from
    c1 to c2 and then column c2 from beyond r1 to r2, so summed over the
    destinations, router (r1, c) carries what every destination column at
    or past c (from c1) sends, and router (r, c2) off row r1 what the
    destinations of column c2 at or past r (from r1) receive."""
    rows, cols = grid
    cnt = np.zeros((rows, cols), np.float64)
    np.add.at(cnt, (dest // cols, dest % cols), 1.0)
    col_cnt, row_cnt = cnt.sum(axis=0), cnt.sum(axis=1)
    ci, ri = np.arange(cols), np.arange(rows)
    # row coverage: c > c1 takes the columns >= c, c < c1 those <= c
    c_ge = np.cumsum(col_cnt[::-1])[::-1]
    c_le = np.cumsum(col_cnt)
    row_cover = np.where(ci[None, :] > ci[:, None], c_ge[None, :],
                         np.where(ci[None, :] < ci[:, None], c_le[None, :],
                                  col_cnt.sum()))
    # column coverage off the source row, per destination column
    r_ge = np.cumsum(cnt[::-1], axis=0)[::-1]
    r_le = np.cumsum(cnt, axis=0)
    above = ri[None, :, None] > ri[:, None, None]
    below = ri[None, :, None] < ri[:, None, None]
    col_cover = (np.where(above, r_ge[None], 0.0)
                 + np.where(below, r_le[None], 0.0))
    uniq, inv = np.unique(src, return_inverse=True)
    gather = np.zeros((src.size, uniq.size), np.float64)
    gather[np.arange(src.size), inv] = 1.0
    r1, c1 = uniq // cols, uniq % cols
    hops = (np.abs(r1[:, None] - ri[None, :]) @ row_cnt
            + np.abs(c1[:, None] - ci[None, :]) @ col_cnt)
    return _LayerRoutes(gather=gather, rows=r1, cols=c1,
                        row_cover=row_cover, col_cover=col_cover,
                        hops=hops, dup=int(dest.size))


@functools.lru_cache(maxsize=64)
def _routes(cores: tuple[int, ...], phys: tuple[int, ...],
            grid: tuple[int, int], n_cores_phys: int) -> tuple:
    """Per-(partition, mapping) :class:`_LayerRoutes` of every layer; the
    last layer's messages go to the chip I/O port at router 0."""
    cpr = max(1, n_cores_phys // (grid[0] * grid[1]))
    routers = np.asarray(phys, np.int64) // cpr
    off = np.concatenate([[0], np.cumsum(cores)]).astype(int)
    out = []
    for l in range(len(cores)):
        dest = (routers[off[l + 1]:off[l + 2]] if l + 1 < len(cores)
                else np.zeros(1, np.int64))
        out.append(_layer_routes(routers[off[l]:off[l + 1]], dest, grid))
    return tuple(out)


def _route(part: Partition, mapping: Mapping, msgs: np.ndarray,
           profile: ChipProfile) -> NocTrafficBatch:
    """Route a (T, n_logical) message-count matrix.  Counts are integers
    in float64, so every sum is exact whatever its order."""
    rows, cols = profile.grid
    m = np.asarray(msgs, np.float64)
    T = m.shape[0]
    loads = np.zeros((T, rows, cols), np.float64)
    hops = np.zeros(T, np.float64)
    inject = np.empty_like(m)
    off = 0
    for lr in _routes(part.cores, mapping.phys, profile.grid,
                      profile.n_cores):
        m_l = m[:, off:off + lr.gather.shape[0]]
        inject[:, off:off + lr.gather.shape[0]] = m_l * lr.dup
        off += lr.gather.shape[0]
        by_src = m_l @ lr.gather                         # (T, S)
        on_grid = np.zeros((T, rows, cols), np.float64)
        on_grid[:, lr.rows, lr.cols] = by_src
        loads += on_grid @ lr.row_cover                  # along source rows
        loads += np.einsum("ta,arc->trc", on_grid.sum(axis=2),
                           lr.col_cover)                 # down columns
        hops += by_src @ lr.hops
    return NocTrafficBatch(router_loads=loads.reshape(T, rows * cols),
                           total_hops=hops, inject_per_core=inject)


def route_batch(part: Partition, mapping: Mapping, msgs_out: np.ndarray,
                profile: ChipProfile) -> NocTrafficBatch:
    """Route every timestep's messages at once.  ``msgs_out`` is the
    (T, n_logical) per-core message-count matrix in logical core order;
    each message is unicast to every core of the next layer, the last
    layer's to router 0.  Results are bit-identical to T
    :func:`route_step` calls."""
    with tracing.span("price.route"):
        return _route(part, mapping, msgs_out, profile)


def route_step(part: Partition, mapping: Mapping,
               msgs_out_per_core: list[np.ndarray],
               profile: ChipProfile) -> NocTraffic:
    """Route one timestep's messages.  ``msgs_out_per_core[l]`` holds message
    counts per core of layer l; each message is unicast-duplicated to every
    core of layer l+1; the final layer exits at router 0."""
    m = np.concatenate([np.asarray(a, np.float64).reshape(-1)
                        for a in msgs_out_per_core])[None, :]
    b = _route(part, mapping, m, profile)
    return NocTraffic(router_loads=b.router_loads[0],
                      total_hops=float(b.total_hops[0]),
                      inject_per_core=b.inject_per_core[0])
