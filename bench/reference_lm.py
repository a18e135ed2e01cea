"""Plain reference of a ``simulate`` request on a routed-expert LM share
(kind ``simulate_lm``).

Independent of the program.  It takes the neuron models, operand
precisions and the counting of one layer's input events from
``bench/reference.py`` and adds what a Nemotron-H share needs there:

- the routed gate of an up-projection (a layer spec's ``router``): sigmoid
  scores of its router neurons' pre-activations, the ``top_k`` largest of
  all ``n_experts`` renormalised to one and scaled; a held expert's
  messages are scaled by its weight, or silenced off the top ``top_k``;
  shared experts message unscaled; router neurons never message;
- route pricing that walks the X-then-Y path of each (source router,
  destination router) pair a layer uses, with no table over all router
  pairs, so a 48-chip mesh of 1,440 routers prices in little memory;
- ties.  With ``ties=True`` the stack also runs in float64 (the shadow),
  following the float32 run's routes.  A message is a tie where the value
  that decides it (a ReLU's pre-activation, an SSM's state) lies within
  ``reference.TIE_MARGIN`` times the float32 run's largest rounding error
  (that layer and step) of zero; :func:`counter_bounds` gives the counters
  with every tie left out and with every tie sent.  A routing step is a
  tie where the shadow's lowest chosen and highest unchosen router
  pre-activations lie that close, and one of the two experts is held
  here: :func:`forward` lists it as ``(layer, step, chosen, unchosen)``,
  and ``force`` runs the stack with such steps routed the other way.
"""

from __future__ import annotations

import numpy as np

from bench import reference

#: routing ties of one request beyond which no alternative is run
MAX_ROUTE_TIES = 3


# ------------------------------------------------------------- functional

def _live_rows(spec: dict):
    """``(rows, None)`` where every neuron reads the same nonzero rows (a
    dense or row-cut layer), else ``(None, mask)``; kept on the spec."""
    if "_nz" not in spec:
        nz = spec["weights"] != 0
        rows = nz.any(axis=1)
        spec["_nz"] = ((rows.astype(np.float32), None)
                       if (nz == rows[:, None]).all()
                       else (None, nz.astype(np.float32)))
    return spec["_nz"]


def _events(spec: dict, events: np.ndarray) -> dict:
    """One layer's synaptic counters of its input events (the same
    counters as ``reference._events``, cheaper where every neuron reads
    the same rows)."""
    rows, mask = _live_rows(spec)
    n = spec["weights"].shape[1]
    T = events.shape[0]
    macs = (np.broadcast_to((events @ rows)[:, None], (T, n))
            if mask is None else events @ mask)
    fetches = np.broadcast_to(events.sum(axis=1, keepdims=True), (T, n))
    return dict(msgs_in=events.sum(axis=1), macs=macs, fetches=fetches,
                acts_evented=(macs > 0).astype(np.float32))


def _select(router: dict, pre: np.ndarray, force: dict) -> np.ndarray:
    """(T, top_k) chosen experts: the largest router pre-activations (the
    sigmoid keeps their order), ties to the lower id; ``force`` maps a
    step to a (chosen, unchosen) swap."""
    r = np.asarray(pre, np.float64)[:, -router["n_experts"]:]
    top = np.argsort(-r, axis=1, kind="stable")[:, :router["top_k"]]
    for t, (a, b) in force.items():
        top[t][top[t] == a] = b
    return top


def _gate(router: dict, pre: np.ndarray, top: np.ndarray,
          dtype) -> np.ndarray:
    """(T, n) per-neuron message scale of the chosen experts ``top``."""
    r = np.asarray(pre, np.float64)[:, -router["n_experts"]:]
    s = np.take_along_axis(1.0 / (1.0 + np.exp(-r)), top, axis=1)
    w = np.zeros_like(r)
    np.put_along_axis(w, top, s / s.sum(axis=1, keepdims=True)
                      * router["scale"], axis=1)
    T, width = r.shape[0], router["width"]
    return np.concatenate(
        [np.repeat(w[:, list(router["held"])], width, axis=1),
         np.ones((T, router["n_shared"] * width)),
         np.zeros((T, router["n_experts"]))], axis=1).astype(dtype)


def _route_ties(router: dict, pre: np.ndarray, pre64: np.ndarray,
                top: np.ndarray) -> list[tuple[int, int, int]]:
    """(step, chosen, unchosen) where the shadow's lowest chosen and
    highest unchosen router pre-activations lie within rounding, and one
    of the two experts is held here."""
    E = router["n_experts"]
    r, r64 = pre[:, -E:].astype(np.float64), pre64[:, -E:]
    err = np.abs(r - r64).max(axis=1)
    ulp = np.finfo(np.float32).eps * np.abs(r64).max(axis=1)
    out = []
    held = set(router["held"])
    for t in range(r.shape[0]):
        chosen = np.zeros(E, bool)
        chosen[top[t]] = True
        a = int(np.flatnonzero(chosen)[np.argmin(r64[t, chosen])])
        b = int(np.flatnonzero(~chosen)[np.argmax(r64[t, ~chosen])])
        if (r64[t, a] - r64[t, b] <= reference.TIE_MARGIN
                * max(err[t], ulp[t]) and (a in held or b in held)):
            out.append((t, a, b))
    return out


def _decider(spec: dict, pre: np.ndarray, y: np.ndarray) -> np.ndarray:
    """The value whose being zero or not decides a neuron's message."""
    return pre if spec["neuron_model"] == "relu" else y


def forward(layers: list[dict], xs: np.ndarray, *,
            contract: str = "float32", ties: bool = False,
            force: dict | None = None):
    """Run the share over a (T, in) stream.  Returns the (T, out) outputs,
    per layer the counters of ``reference.forward`` (with ``tie``,
    ``tie_in`` and ``events`` where ``ties``), and the routing ties as
    (layer, step, chosen, unchosen).  ``force`` maps (layer, step) to a
    (chosen, unchosen) swap."""
    force = force or {}
    cur = np.asarray(xs, np.float32)
    cur64 = np.asarray(xs, np.float64)
    tie_in = np.zeros_like(cur)
    counters, route_ties = [], []
    for i, spec in enumerate(layers):
        events = (cur != 0).astype(np.float32)
        pre = np.asarray(reference._pre(spec, cur, contract), np.float32)
        y = reference._neurons(spec, pre)
        z = _decider(spec, pre, y)
        router = spec.get("router")
        live = np.ones(y.shape, bool)
        if router is not None:
            top = _select(router, pre, {t: ab for (l, t), ab in
                                        force.items() if l == i})
            gate = _gate(router, pre, top, np.float32)
            y = y * gate
            live = gate != 0
        cnt = _events(spec, events)
        cnt["msgs_out"] = (y != 0).astype(np.float32)
        if ties:
            pre64 = reference._pre(spec, cur64, "float64")
            y64 = reference._neurons(spec, pre64)
            z64 = _decider(spec, pre64, y64)
            if router is not None:
                route_ties += [(i,) + rt for rt in
                               _route_ties(router, pre, pre64, top)]
                y64 = y64 * _gate(router, pre64, top, np.float64)
            err = np.abs(z - z64).max(axis=1, keepdims=True)
            ulp = np.finfo(np.float32).eps * np.abs(z64).max(
                axis=1, keepdims=True)
            tie = ((np.abs(z64) <= reference.TIE_MARGIN
                    * np.maximum(err, ulp)) & live).astype(np.float32)
            cnt.update(tie=tie, tie_in=tie_in, events=events)
            tie_in = tie
            cur64 = y64
        counters.append(cnt)
        cur = y
    return cur, counters, route_ties


def counter_bounds(layers: list[dict], counters: list[dict]):
    """The counters of a ``forward(..., ties=True)`` run with every message
    tie left out, and with every one sent: (low, high)."""
    low, high = [], []
    for spec, cnt in zip(layers, counters):
        tie, tie_in = cnt["tie"], cnt["tie_in"]
        if not (tie.any() or tie_in.any()):
            low.append(cnt)
            high.append(cnt)
            continue
        lo = _events(spec, cnt["events"] * (1.0 - tie_in))
        hi = _events(spec, np.maximum(cnt["events"], tie_in))
        lo["msgs_out"] = cnt["msgs_out"] * (1.0 - tie)
        hi["msgs_out"] = np.maximum(cnt["msgs_out"], tie)
        low.append(lo)
        high.append(hi)
    return low, high


# ---------------------------------------------------------------- routing

_ROUTES: dict = {}


def _walk(src: int, dst: int, cols: int, row: np.ndarray, n: float) -> int:
    """Add ``n`` to every router of the X-then-Y path from ``src`` to
    ``dst`` in ``row`` (a flat (R,) view); return the path's hops."""
    r1, c1 = divmod(src, cols)
    r2, c2 = divmod(dst, cols)
    row[r1 * cols + min(c1, c2): r1 * cols + max(c1, c2) + 1] += n
    if r2 > r1:
        row[(r1 + 1) * cols + c2: r2 * cols + c2 + 1: cols] += n
    elif r2 < r1:
        row[r2 * cols + c2: (r1 - 1) * cols + c2 + 1: cols] += n
    return abs(r1 - r2) + abs(c1 - c2)


def routes(cores, phys, chip: dict) -> list[tuple]:
    """Per layer: the (cores, S) map of its cores to its S source routers,
    the (S, R) routers that one message of each source touches on its way
    to every core of the next layer (the last layer's to router 0), the
    (S,) hops of one such message, and the number of destination cores."""
    key = (tuple(cores), tuple(phys), tuple(chip["grid"]), chip["n_cores"])
    if key in _ROUTES:
        return _ROUTES[key]
    rows, cols = chip["grid"]
    R = rows * cols
    router = np.asarray(phys) // max(1, chip["n_cores"] // R)
    start = np.concatenate([[0], np.cumsum(cores)]).astype(int)
    out = []
    for l in range(len(cores)):
        src = router[start[l]:start[l + 1]]
        dst = (router[start[l + 1]:start[l + 2]] if l + 1 < len(cores)
               else np.zeros(1, int))
        srcs, where = np.unique(src, return_inverse=True)
        dsts, n_dst = np.unique(dst, return_counts=True)
        touch = np.zeros((srcs.size, R))
        hops = np.zeros(srcs.size)
        for i, s in enumerate(srcs):
            for d, n in zip(dsts, n_dst):
                hops[i] += n * _walk(int(s), int(d), cols, touch[i],
                                     float(n))
        gather = np.zeros((src.size, srcs.size))
        gather[np.arange(src.size), where] = 1.0
        out.append((gather, touch, hops, dst.size))
    _ROUTES[key] = out
    return out


# ---------------------------------------------------------------- pricing

def price(layers: list[dict], counters: list[dict], chip: dict, cores,
          phys, *, dtype=np.float64) -> dict:
    """Price one (partition, placement) of a functional run, as
    ``reference.price`` does, with the routing of :func:`routes`."""
    if not chip["synchronous"]:
        raise ValueError("the reference prices synchronous chips only")
    f = lambda a: np.asarray(a, dtype)
    T = counters[0]["macs"].shape[0]
    n_logical = int(sum(cores))
    if len(phys) != n_logical or len(set(phys)) != n_logical:
        raise ValueError("placement does not match the partition")
    mem, act, syn, acts, msgs = [], [], [], [], []
    e_events = np.zeros(T, dtype)
    for spec, cnt, c in zip(layers, counters, cores):
        b = reference._bounds(reference.n_neurons(spec), c)

        def seg(m):
            m = f(m)
            return np.stack([m[:, lo:hi].sum(axis=1)
                             for lo, hi in zip(b[:-1], b[1:])], axis=1)
        sparse = (spec.get("weight_format")
                  or chip["default_format_fc"]) == "sparse"
        s_macs = seg(cnt["macs"])
        s_syn = s_macs if sparse else seg(cnt["fetches"])
        s_acts = f(np.diff(b))[None, :].repeat(T, axis=0)
        s_msgs = seg(cnt["msgs_out"])
        m_in = f(cnt["msgs_in"])[:, None]
        if sparse:
            m = (m_in * f(chip["c_msg_recv"] + chip["c_decode_msg"])
                 + s_syn * f(chip["c_fetch"] + chip["c_decode_word"]
                             + chip["c_mac"]))
        else:
            m = m_in * f(chip["c_msg_recv"]) + s_syn * f(chip["c_fetch"]
                                                         + chip["c_mac"])
        cost = reference.NEURON_COST[spec["neuron_model"]]
        mem.append(m)
        act.append(s_acts * f(chip["c_act"] * cost))
        e_events = e_events + (
            f(chip["e_fetch"]) * s_syn.sum(axis=1)
            + f(chip["e_mac"]) * s_macs.sum(axis=1)
            + (f(chip["e_decode"]) * s_syn.sum(axis=1) if sparse else 0.0)
            + f(chip["e_act"]) * s_acts.sum(axis=1) * f(cost))
        syn.append(s_syn)
        acts.append(s_acts)
        msgs.append(s_msgs)
    mem, act = np.concatenate(mem, 1), np.concatenate(act, 1)
    syn, acts, msgs = (np.concatenate(a, 1) for a in (syn, acts, msgs))

    loads = np.zeros((T, chip["grid"][0] * chip["grid"][1]), dtype)
    total_hops = np.zeros(T, dtype)
    inject = np.zeros_like(msgs)
    off = 0
    for gather, touch, hops, n_dst in routes(cores, phys, chip):
        m = msgs[:, off:off + gather.shape[0]]
        inject[:, off:off + gather.shape[0]] = m * n_dst
        off += gather.shape[0]
        by_src = m @ f(gather)
        loads += by_src @ f(touch)
        total_hops += by_src @ f(hops)

    core_time = np.maximum(mem, act) + f(chip["t_core_fixed"])
    max_load = loads.max(axis=1)
    traffic_time = (f(chip["c_route"]) * max_load
                    + f(chip["c_inject"]) * inject.max(axis=1))
    t_compute = core_time.max(axis=1)
    times = np.maximum(t_compute, traffic_time) + f(chip["t_barrier"])
    traffic_bound = traffic_time > t_compute
    mem_bound = mem.max(axis=1) >= act.max(axis=1)
    votes = {"memory": int((~traffic_bound & mem_bound).sum()),
             "compute": int((~traffic_bound & ~mem_bound).sum()),
             "traffic": int(traffic_bound.sum()), "barrier": 0}
    n_active = ((syn + msgs) > 0).sum(axis=1).astype(dtype)
    n_active[n_active == 0] = n_logical
    energies = (times * (f(chip["p_idle"]) + f(chip["p_core"]) * n_active)
                + e_events + f(chip["e_msg_hop"]) * total_hops)
    best = max(votes.values())
    return dict(
        time_per_step=float(times.mean()),
        energy_per_step=float(energies.mean()),
        times=times, energies=energies,
        max_synops=float(syn.max(axis=1).mean()),
        max_acts=float(acts.max(axis=1).mean()),
        max_link_load=float(max_load.mean()),
        n_cores_active=n_logical,
        per_core_synops=syn.sum(axis=0) / T,
        per_core_acts=acts.sum(axis=0) / T,
        per_core_msgs_out=msgs.sum(axis=0) / T,
        msgs_total=float(msgs.sum()) / T,
        bottleneck_stage=next(k for k in ("memory", "compute", "traffic",
                                          "barrier") if votes[k] == best))
