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
square.  The population pricers (:func:`flow_matrix_population`,
:func:`router_incidence_population`, :func:`flow_structures_rows`) keep
per-pair path incidence tables of ``R**2`` rows, which the grids of today's
searches hold.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading

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


@functools.lru_cache(maxsize=64)
def _flow_matrix(cores: tuple[int, ...], phys: tuple[int, ...],
                 grid: tuple[int, int],
                 n_cores_phys: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-(partition, mapping) routing structure, independent of the
    per-step message counts.

    Returns ``(P, dup)`` where ``P`` is an (n_logical, R*R) matrix such that
    ``msgs @ P`` is the flattened router->router flow tensor (entry
    ``[core, src*R+dst]`` counts how many destination cores of the next
    layer sit on router ``dst``), and ``dup`` is the per-core unicast
    duplication factor (number of destination cores)."""
    rows, cols = grid
    R = rows * cols
    cpr = max(1, n_cores_phys // R)
    routers = np.asarray([p // cpr for p in phys])
    n_logical = int(sum(cores))
    P = np.zeros((n_logical, R * R), np.float64)
    dup = np.zeros(n_logical, np.float64)
    offsets = np.concatenate([[0], np.cumsum(cores)]).astype(int)
    n_layers = len(cores)
    for l in range(n_layers):
        src_idx = np.arange(offsets[l], offsets[l + 1])
        if l + 1 < n_layers:
            dst_routers = routers[offsets[l + 1]:offsets[l + 2]]
        else:
            dst_routers = np.asarray([0])        # chip I/O port
        dup[src_idx] = len(dst_routers)
        for g in src_idx:
            np.add.at(P[g], routers[g] * R + dst_routers, 1.0)
    return P, dup


# ---------------------------------------------------------------- population

#: Bytes-keyed LRU of per-candidate ``(P, dup)`` routing structures.  The
#: evolutionary search carries survivors between generations, so most of a
#: generation's genomes were already routed; keying by the raw genome bytes
#: (core counts + expressed physical slots) lets :func:`flow_matrix_population`
#: skip their scatter entirely.  Guarded by a lock so population pricing can
#: be driven from worker threads.
_FLOW_CACHE: collections.OrderedDict = collections.OrderedDict()
_FLOW_CACHE_MAX = 4096
_FLOW_CACHE_LOCK = threading.Lock()


def flow_cache_clear() -> None:
    """Drop the population flow-matrix cache (tests / memory pressure)."""
    with _FLOW_CACHE_LOCK:
        _FLOW_CACHE.clear()


def flow_matrix_population(cores_rows, phys_rows, grid: tuple[int, int],
                           n_cores_phys: int, n_pad: int, *,
                           cache: bool = True,
                           ) -> tuple[np.ndarray, np.ndarray]:
    """Batched :func:`_flow_matrix`: all candidates' routing structures in
    one shot.

    Args:
      cores_rows: per-candidate layer core counts — sequence of K int
        sequences, each of length ``n_layers``.
      phys_rows: per-candidate *expressed* physical slot assignments —
        sequence of K int sequences, row k of length ``sum(cores_rows[k])``.
      n_pad: logical-core padding width (>= every candidate's total cores).

    Returns ``(P_stack, dup_stack)``: a ``(K, n_pad, R*R)`` float32 tensor
    whose k-th leading slice equals ``_flow_matrix``'s ``P`` for candidate k
    (zero rows beyond its ``n_logical``), and the ``(K, n_pad)`` float64
    duplication factors (zero on padding).  Cache misses are built with a
    single ``np.add.at`` scatter over the stacked tensor; hits are pasted
    from the bytes-keyed LRU.  Entries are exact small-integer counts, so
    float32 storage is lossless.  ``cache=False`` skips storing the raw
    matrices (:func:`router_incidence_population` only ever re-reads the
    much smaller folded form, so caching the dense ``P`` for it would
    waste most of the LRU's memory on dead entries).
    """
    rows, cols = grid
    R = rows * cols
    cpr = max(1, n_cores_phys // R)
    cores_rows = [np.asarray(c, np.int32) for c in cores_rows]
    phys_rows = [np.asarray(p, np.int32) for p in phys_rows]
    K = len(cores_rows)
    if K != len(phys_rows):
        raise ValueError("cores_rows and phys_rows disagree on K")

    P_stack = np.zeros((K, n_pad, R * R), np.float32)
    dup_stack = np.zeros((K, n_pad), np.float64)
    keys = []
    misses = []
    with _FLOW_CACHE_LOCK:
        for k, (cores, phys) in enumerate(zip(cores_rows, phys_rows)):
            key = (grid, n_cores_phys, cores.tobytes(), phys.tobytes())
            keys.append(key)
            hit = _FLOW_CACHE.get(key)
            if hit is not None:
                _FLOW_CACHE.move_to_end(key)
                P_k, dup_k = hit
                P_stack[k, :P_k.shape[0]] = P_k
                dup_stack[k, :dup_k.shape[0]] = dup_k
            else:
                misses.append(k)

    if misses:
        k_idx, core_idx, flat_idx = [], [], []
        for k in misses:
            cores, phys = cores_rows[k], phys_rows[k]
            routers = phys // cpr
            off = np.concatenate([[0], np.cumsum(cores)]).astype(int)
            n_layers = len(cores)
            for l in range(n_layers):
                src = np.arange(off[l], off[l + 1])
                if l + 1 < n_layers:
                    dst_r = routers[off[l + 1]:off[l + 2]]
                else:
                    dst_r = np.zeros(1, np.int32)     # chip I/O port
                dup_stack[k, off[l]:off[l + 1]] = len(dst_r)
                k_idx.append(np.full(src.size * dst_r.size, k, np.intp))
                core_idx.append(np.repeat(src, dst_r.size))
                flat_idx.append((routers[src][:, None] * R
                                 + dst_r[None, :]).reshape(-1))
        np.add.at(P_stack,
                  (np.concatenate(k_idx), np.concatenate(core_idx),
                   np.concatenate(flat_idx)), 1.0)
        if cache:
            with _FLOW_CACHE_LOCK:
                for k in misses:
                    n_logical = int(cores_rows[k].sum())
                    _FLOW_CACHE[keys[k]] = (P_stack[k, :n_logical].copy(),
                                            dup_stack[k, :n_logical].copy())
                    _FLOW_CACHE.move_to_end(keys[k])
                while len(_FLOW_CACHE) > _FLOW_CACHE_MAX:
                    _FLOW_CACHE.popitem(last=False)
    return P_stack, dup_stack


def router_incidence_population(cores_rows, phys_rows, grid: tuple[int, int],
                                n_cores_phys: int, n_pad: int,
                                ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Path-incidence-folded :func:`flow_matrix_population`.

    Returns ``(PL, ph, dup)``: ``PL`` is ``(K, n_pad, R)`` float64 with
    ``PL = P @ path_incidence`` (so a candidate's per-router loads are
    ``msgs @ PL`` — the ``(T, R*R)`` flow tensor never materializes), ``ph``
    is ``(K, n_pad)`` float64 with ``ph = P @ pair_hops`` (total hops are
    ``msgs @ ph``), and ``dup`` the duplication factors.  Because every
    entry of ``P``, the incidence, and the hop vector is a small exact
    integer, the fold is exact: ``msgs @ (P @ inc) == (msgs @ P) @ inc``
    bit-for-bit in float64.  Folded rows are LRU-cached by genome bytes
    alongside the raw flow matrices.
    """
    rows, cols = grid
    R = rows * cols
    cores_rows = [np.asarray(c, np.int32) for c in cores_rows]
    phys_rows = [np.asarray(p, np.int32) for p in phys_rows]
    K = len(cores_rows)
    PL = np.zeros((K, n_pad, R), np.float64)
    ph = np.zeros((K, n_pad), np.float64)
    dup = np.zeros((K, n_pad), np.float64)
    keys, misses = [], []
    with _FLOW_CACHE_LOCK:
        for k, (cores, phys) in enumerate(zip(cores_rows, phys_rows)):
            key = ("fold", grid, n_cores_phys, cores.tobytes(),
                   phys.tobytes())
            keys.append(key)
            hit = _FLOW_CACHE.get(key)
            if hit is not None:
                _FLOW_CACHE.move_to_end(key)
                PL_k, ph_k, dup_k = hit
                n = PL_k.shape[0]
                PL[k, :n], ph[k, :n], dup[k, :n] = PL_k, ph_k, dup_k
            else:
                misses.append(k)
    if misses:
        P_m, dup_m = flow_matrix_population(
            [cores_rows[k] for k in misses], [phys_rows[k] for k in misses],
            grid, n_cores_phys, n_pad, cache=False)
        inc = _path_incidence(grid).astype(np.float64)
        hops_vec = _pair_hops(grid).astype(np.float64)
        PL_m = P_m.astype(np.float64) @ inc           # (M, n_pad, R)
        ph_m = P_m.astype(np.float64) @ hops_vec      # (M, n_pad)
        with _FLOW_CACHE_LOCK:
            for j, k in enumerate(misses):
                n = int(cores_rows[k].sum())
                PL[k], ph[k], dup[k] = PL_m[j], ph_m[j], dup_m[j]
                _FLOW_CACHE[keys[k]] = (PL_m[j, :n].copy(),
                                        ph_m[j, :n].copy(),
                                        dup_m[j, :n].copy())
                _FLOW_CACHE.move_to_end(keys[k])
            while len(_FLOW_CACHE) > _FLOW_CACHE_MAX:
                _FLOW_CACHE.popitem(last=False)
    return PL, ph, dup


@functools.lru_cache(maxsize=16)
def incidence_tables(grid: tuple[int, int]) -> tuple[np.ndarray, np.ndarray]:
    """Per-grid routing geometry in the shapes the device path consumes:
    ``inc3[src, dst, node]`` is the (R, R, R) path-incidence tensor and
    ``hops2[src, dst]`` the (R, R) Manhattan hop matrix — float64 reshaped
    views of the lru-cached flat tables of the population pricers."""
    rows, cols = grid
    R = rows * cols
    inc3 = _path_incidence(grid).astype(np.float64).reshape(R, R, R)
    hops2 = _pair_hops(grid).astype(np.float64).reshape(R, R)
    return inc3, hops2


def flow_structures_rows(lid, router, alive, n_layers: int, inc3, hops2):
    """ONE candidate's routing structures, built entirely on device.

    The array-native analog of :func:`router_incidence_population` for a
    genome that never leaves the accelerator: given the candidate's padded
    per-core layer ids ``lid`` (Ncap,), router ids ``router`` (Ncap,), and
    float live-core mask ``alive`` (Ncap,), returns the same
    ``(PL, ph, dup)`` triple — per-core router-load incidence ``msgs @ PL``,
    hop factors ``msgs @ ph``, unicast duplication — as ``(Ncap, R)`` /
    ``(Ncap,)`` / ``(Ncap,)`` jnp arrays.  Pure jnp and shape-static, so it
    traces into the jitted population pricer and the device generation step
    (no host round-trip, no byte-keyed cache).

    Every intermediate is an exact small-integer count in float64 (layer
    destination-router counts folded through the integer incidence/hop
    tables), so the results are bit-identical to the host-built structures
    of :func:`router_incidence_population` — asserted by
    ``tests/test_device_search.py``.

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
