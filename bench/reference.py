"""Plain reference of what a ``simulate`` request answers.

Written from the model's description, with nothing taken from the
program: the functional run of a feed-forward stack (fc and SAME-padded
strided conv layers; ReLU, IF, sigma-delta ReLU and SSM neurons; delta
reconstruction) with its exact event counters, and the barrier-synchronized
timestep cost model that prices those counters on a chip (per-core memory
and compute stages, X-then-Y routed NoC congestion, barrier, energies).

Precision is a parameter, so the same code is the reference and the
control.  ``contract="float32"`` contracts float32 operands in float32;
``"bfloat16"`` rounds both operands to bfloat16 first and accumulates in
float32, as one MXU pass does.  Neuron state is float32 either way.
``price(..., dtype=np.float64)`` is the reference pricing; ``np.float32``
its control.

An SSM neuron messages whenever its state is not exactly zero, so a state
within rounding of zero may message in one correct float32 run and not in
another.  With ``ties=True``, :func:`forward` also runs the stack in
float64 and marks such messages; :func:`counter_bounds` then gives the
counters with every tie left out and with every tie sent, between which
any correct run's counters, and by monotony its prices, lie.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

NEURON_COST = {"relu": 1.0, "if": 1.2, "sd_relu": 2.5, "ssm": 6.0}
#: a message is a tie where the float64 state lies within this many times
#: the float32 run's largest rounding error (that layer and step) of zero
TIE_MARGIN = 8.0


# ------------------------------------------------------------- functional

def _operand(a: np.ndarray, contract: str) -> np.ndarray:
    a = np.asarray(a, np.float32)
    if contract == "float32":
        return a
    if contract == "bfloat16":
        return a.astype(ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown contraction precision {contract!r}")


def conv_out_hw(spec: dict) -> tuple[int, int]:
    h, w = spec["in_hw"]
    return h // spec["stride"], w // spec["stride"]


def n_neurons(spec: dict) -> int:
    if spec["kind"] == "fc":
        return int(spec["weights"].shape[1])
    oh, ow = conv_out_hw(spec)
    return int(spec["weights"].shape[3] * oh * ow)


def _conv(x: np.ndarray, w: np.ndarray, spec: dict) -> np.ndarray:
    """SAME-padded strided conv of (T, cin*h*w) channel-major rows with a
    (kh, kw, cin, cout) kernel, one tap at a time: (T, cout*oh*ow), in the
    operands' precision."""
    kh, kw, cin, cout = w.shape
    h, wd = spec["in_hw"]
    s = spec["stride"]
    oh, ow = conv_out_hw(spec)
    T = x.shape[0]
    img = x.reshape(T, cin, h, wd)
    pad_h = max((oh - 1) * s + kh - h, 0)
    pad_w = max((ow - 1) * s + kw - wd, 0)
    img = np.pad(img, ((0, 0), (0, 0), (pad_h // 2, pad_h - pad_h // 2),
                       (pad_w // 2, pad_w - pad_w // 2)))
    out = np.zeros((T, oh, ow, cout), np.result_type(x, w))
    for dy in range(kh):
        for dx in range(kw):
            tap = img[:, :, dy:dy + s * oh:s, dx:dx + s * ow:s]
            rows = tap.transpose(0, 2, 3, 1).reshape(-1, cin)
            out += (rows @ w[dy, dx]).reshape(T, oh, ow, cout)
    return out.transpose(0, 3, 1, 2).reshape(T, -1)


def _pre(spec: dict, x_eff: np.ndarray, contract: str) -> np.ndarray:
    """One layer's synaptic input over all T steps; ``"float64"`` is the
    shadow run's."""
    if contract == "float64":
        x, w = np.asarray(x_eff, np.float64), spec["weights"].astype(
            np.float64)
    else:
        x, w = _operand(x_eff, contract), _operand(spec["weights"], contract)
    return x @ w if spec["kind"] == "fc" else _conv(x, w, spec)


def _events(spec: dict, events: np.ndarray) -> dict:
    """The synaptic counters of one layer's input events."""
    w = spec["weights"]
    wnz = (w != 0).astype(np.float32)
    if spec["kind"] == "fc":
        macs = events @ wnz
        fetches = np.repeat(events.sum(axis=1, keepdims=True),
                            w.shape[1], axis=1)
    else:
        macs = _conv(events, wnz, spec)
        fetches = _conv(events, np.ones_like(wnz), spec)
    return dict(msgs_in=events.sum(axis=1), macs=macs, fetches=fetches,
                acts_evented=(macs > 0).astype(np.float32))


def _neurons(spec: dict, pre: np.ndarray) -> np.ndarray:
    model = spec["neuron_model"]
    T, n = pre.shape
    y = np.empty_like(pre)
    one = pre.dtype.type
    if model == "relu":
        return np.maximum(pre, 0.0)
    if model == "if":
        thr = np.float32(max(spec["threshold"], 1e-6))
        v = np.zeros(n, np.float32)
        for t in range(T):
            v = v + pre[t]
            spikes = (v >= thr).astype(np.float32)
            v = v - thr * spikes
            y[t] = spikes
        return y
    if model == "sd_relu":
        thr = np.float32(max(spec["threshold"], 1e-9))
        sent = np.zeros(n, np.float32)
        for t in range(T):
            delta = np.maximum(pre[t], 0.0) - sent
            q = np.where(np.abs(delta) >= thr, np.round(delta / thr) * thr,
                         0.0).astype(np.float32)
            sent = sent + q
            y[t] = q
        return y
    if model == "ssm":
        a = one(np.float32(spec["decay"]))
        x = np.zeros(n, pre.dtype)
        for t in range(T):
            x = a * x + pre[t]
            y[t] = x
        return y
    raise ValueError(f"unknown neuron model {model!r}")


def forward(layers: list[dict], xs: np.ndarray, *,
            contract: str = "float32", ties: bool = False):
    """Run the stack over a (T, in_size) message stream.

    Returns the (T, out) outputs and, per layer, the event counters:
    ``msgs_in`` (T,), and (T, n) maps ``macs``, ``fetches``, ``msgs_out``
    and ``acts_evented``, flat in channel-major order for conv layers.
    With ``ties``, a stack of SSM layers also gets per layer the (T, n) map
    ``tie`` of its messages that lie within rounding (:data:`TIE_MARGIN`)
    of none, and the (T, in) map ``tie_in`` of its input's."""
    shadow = ties and all(s["neuron_model"] == "ssm" and not s["sends_deltas"]
                          for s in layers)
    cur = np.asarray(xs, np.float32)
    cur64 = np.asarray(xs, np.float64)
    tie_in = np.zeros_like(cur)
    upstream_deltas = False
    counters = []
    for spec in layers:
        events = (cur != 0).astype(np.float32)
        x_eff = np.cumsum(cur, axis=0) if upstream_deltas else cur
        y = _neurons(spec, np.asarray(_pre(spec, x_eff, contract),
                                      np.float32))
        cnt = _events(spec, events)
        cnt["msgs_out"] = (y != 0).astype(np.float32)
        if ties:
            tie = np.zeros_like(y)
            if shadow:
                y64 = _neurons(spec, _pre(spec, cur64, "float64"))
                err = np.abs(y - y64).max(axis=1, keepdims=True)
                ulp = np.finfo(np.float32).eps * np.abs(y64).max(
                    axis=1, keepdims=True)
                tie = (np.abs(y64) <= TIE_MARGIN * np.maximum(err, ulp)
                       ).astype(np.float32)
                cur64 = y64
            cnt.update(tie=tie, tie_in=tie_in, events=events)
            tie_in = tie
        counters.append(cnt)
        upstream_deltas = spec["sends_deltas"] or spec["neuron_model"] == \
            "sd_relu"
        cur = y
    return cur, counters


def counter_bounds(layers: list[dict], counters: list[dict]):
    """The counters of a ``forward(..., ties=True)`` run with every tie
    left out, and with every tie sent: (low, high)."""
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


# ---------------------------------------------------------------- pricing

def _fits(spec: dict, c: int, chip: dict) -> bool:
    w = spec["weights"]
    if spec["kind"] == "fc":
        per_core_w = w.shape[0] * -(-w.shape[1] // c)
    else:
        per_core_w = w.shape[0] * w.shape[1] * w.shape[2] * -(-w.shape[3]
                                                               // c)
    return (-(-n_neurons(spec) // c) <= chip["neurons_per_core"]
            and per_core_w <= chip["synapses_per_core"])


def max_cores(spec: dict) -> int:
    """Split granularity: fc by neuron, conv by output channel."""
    return int(spec["weights"].shape[3]) if spec["kind"] == "conv" \
        else n_neurons(spec)


def feasible(layers: list[dict], cores, chip: dict) -> bool:
    """The per-core neuron and synapse capacities hold for every layer and
    the cores fit on the chip."""
    return (len(cores) == len(layers) and sum(cores) <= chip["n_cores"]
            and all(1 <= c <= max_cores(s) and _fits(s, c, chip)
                    for s, c in zip(layers, cores)))


def minimal_cores(layers: list[dict], chip: dict) -> tuple[int, ...]:
    """Fewest cores per layer that meet the per-core capacities."""
    cores = []
    for spec in layers:
        c = next((c for c in range(1, max_cores(spec) + 1)
                  if _fits(spec, c, chip)), None)
        if c is None:
            raise ValueError(f"layer {spec['name']} fits at no split")
        cores.append(c)
    if sum(cores) > chip["n_cores"]:
        raise ValueError("the network needs more cores than the chip has")
    return tuple(cores)


def _bounds(n: int, c: int) -> np.ndarray:
    """Contiguous equal neuron ranges of the model: float64 linspace
    boundaries truncated to integers."""
    return np.linspace(0, n, c + 1).astype(int)


def _route_nodes(src: int, dst: int, cols: int) -> list[int]:
    """Routers an X-then-Y route from ``src`` to ``dst`` touches."""
    r1, c1 = divmod(src, cols)
    r2, c2 = divmod(dst, cols)
    nodes = [src]
    step = 1 if c2 >= c1 else -1
    nodes += [r1 * cols + c for c in range(c1 + step, c2 + step, step)] \
        if c1 != c2 else []
    step = 1 if r2 >= r1 else -1
    nodes += [r * cols + c2 for r in range(r1 + step, r2 + step, step)] \
        if r1 != r2 else []
    return nodes


def price(layers: list[dict], counters: list[dict], chip: dict, cores,
          phys, *, dtype=np.float64) -> dict:
    """Price one (partition, placement) of a functional run.

    ``cores`` gives each layer's core count; ``phys`` the physical slot of
    each logical core in layer order.  Only barrier-synchronized chips are
    modelled here.  Returns the report's fields as a dict."""
    if not chip["synchronous"]:
        raise ValueError("the reference prices synchronous chips only")
    f = lambda a: np.asarray(a, dtype)
    T = counters[0]["macs"].shape[0]
    rows, cols = chip["grid"]
    R = rows * cols
    cpr = max(1, chip["n_cores"] // R)
    router = [int(p) // cpr for p in phys]
    n_logical = int(sum(cores))
    if len(phys) != n_logical or len(set(phys)) != n_logical:
        raise ValueError("placement does not match the partition")

    mem, act, syn, acts, msgs = [], [], [], [], []
    e_events = np.zeros(T, dtype)
    for spec, cnt, c in zip(layers, counters, cores):
        b = _bounds(n_neurons(spec), c)
        seg = lambda m: np.stack([f(m)[:, lo:hi].sum(axis=1)
                                  for lo, hi in zip(b[:-1], b[1:])], axis=1)
        sparse = (spec.get("weight_format")
                  or (chip["default_format_conv"] if spec["kind"] == "conv"
                      else chip["default_format_fc"])) == "sparse"
        s_macs = seg(cnt["macs"])
        s_syn = s_macs if sparse else seg(cnt["fetches"])
        s_acts = seg(np.ones_like(cnt["macs"]))
        s_msgs = seg(cnt["msgs_out"])
        m_in = f(cnt["msgs_in"])[:, None]
        if sparse:
            m = (m_in * f(chip["c_msg_recv"] + chip["c_decode_msg"])
                 + s_syn * f(chip["c_fetch"] + chip["c_decode_word"]
                             + chip["c_mac"]))
        else:
            m = m_in * f(chip["c_msg_recv"]) + s_syn * f(chip["c_fetch"]
                                                         + chip["c_mac"])
        cost = NEURON_COST[spec["neuron_model"]]
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

    # every message of a core is unicast to each core of the next layer;
    # the last layer's go to the I/O port at router 0
    flow = np.zeros((n_logical, R, R), dtype)      # per source core
    dup = np.zeros(n_logical, dtype)
    start = np.concatenate([[0], np.cumsum(cores)]).astype(int)
    for l in range(len(cores)):
        dst = (router[start[l + 1]:start[l + 2]] if l + 1 < len(cores)
               else [0])
        for g in range(start[l], start[l + 1]):
            dup[g] = len(dst)
            for d in dst:
                flow[g, router[g], d] += 1
    touch = np.zeros((R, R, R), dtype)
    hops = np.zeros((R, R), dtype)
    for s in range(R):
        for d in range(R):
            touch[s, d, _route_nodes(s, d, cols)] = 1
            hops[s, d] = abs(s // cols - d // cols) + abs(s % cols - d % cols)
    pair_flow = msgs @ flow.reshape(n_logical, R * R)            # (T, R*R)
    loads = pair_flow @ touch.reshape(R * R, R)                  # (T, R)
    total_hops = pair_flow @ hops.reshape(R * R)                 # (T,)
    inject = msgs * dup[None, :]

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
