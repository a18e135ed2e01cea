"""The benchmark harness: resolves a cell of ``BENCHMARK.json`` to its
files by name, sets it up, measures one window and prints one result line.

Everything that belongs to one configuration, traffic mix, cell or metric
lives in a file of its own, found by the name in ``BENCHMARK.json``:

- ``configs[].file``: the configuration (network, chip, precision);
- ``bench/traffic/<traffic>.json``: the traffic mix, whose ``kind`` names
  the request module ``bench/kinds/<kind>.py``;
- ``bench/limits/<cell>.json``: the numbers ``correct`` compares, each
  with its limit;
- ``bench/metrics/<metric>.py``: one reader per metric, ``read(run)``
  returning a number, or None where the run holds nothing to read.

Adding a cell, a configuration, a traffic mix or a metric takes new files
and entries only.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- resolution

def load_json(path: str):
    with open(path) as f:
        return json.load(f)


_MODULES: dict = {}


def load_module(root: str, rel: str):
    """Import a module of the benchmark by its path under ``root``."""
    path = os.path.join(root, rel)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file {rel} under {root}")
    if path not in _MODULES:
        name = "bench_file_" + rel.replace("/", "_").replace(".", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def applies(metric: dict, cell: str, end_to_end: list[dict]) -> bool:
    """A metric with ``workloads`` belongs to those cells; a per-layer one
    without belongs to every cell that reports the metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    if moves is None:
        return True
    return any(m["name"] == moves and applies(m, cell, end_to_end)
               for m in end_to_end)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    kind: object
    end_to_end: list
    per_layer: list
    root: str = ROOT

    def reader(self, metric: str):
        return load_module(self.root, f"bench/metrics/{metric}.py").read


def resolve(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r}; cells: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    e2e = bench["end_to_end"]
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=load_json(os.path.join(root, "bench", "limits",
                                      name + ".json")),
        kind=load_module(root, f"bench/kinds/{traffic['kind']}.py"),
        end_to_end=[m for m in e2e if applies(m, name, e2e)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name, e2e)],
        root=root)


# ------------------------------------------------------------ measurement

@dataclasses.dataclass
class Request:
    index: int
    start: float
    end: float
    work: dict
    ok: bool


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers read it."""

    cell: Cell
    seed: int
    traced: bool
    state: dict
    setup_s: float
    window_s: float
    requests: list
    spans: list                  # (name, start, end) host spans
    trace: dict | None           # bench.trace.reduce() of a traced run
    device: dict
    #: process age in seconds when the devices were found, the cell was
    #: built and the warm-up ended
    setup_marks: dict = dataclasses.field(default_factory=dict)

    @property
    def done(self) -> list:
        return [r for r in self.requests if r.ok]

    def span_s(self, name: str) -> list[float]:
        return [e - s for n, s, e in self.spans if n == name]


class Spans:
    """Host spans kept in memory; in a traced run each one is also a
    ``TraceAnnotation`` in the profiler's trace."""

    def __init__(self, traced: bool):
        self.traced = traced
        self.spans: list = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        with (jax.profiler.TraceAnnotation(name) if self.traced
              else contextlib.nullcontext()):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))


class CompileClock:
    """Counts JAX's backend compiles, from its monitoring events, so that
    compiles inside the window show."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.compiles += 1


def process_age_s() -> float:
    """Seconds since this process started, from the kernel's record."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    start = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - start


def compile_cache_dir(root: str) -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` where set, else a fixed directory in
    the checkout: the path is part of the cache's key."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".jax_cache"))


def enable_compile_cache(root: str) -> str:
    import jax
    path = compile_cache_dir(root)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def find_devices(cell: Cell, require_chip: bool, err) -> list | None:
    """The devices the cell runs on, or None (with the reason on
    ``err``) where JAX finds no TPU or fewer chips than the cell asks."""
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        print(f"bench: JAX found no TPU (platform {devs[0].platform})",
              file=err)
        return None
    if len(devs) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} chips, JAX sees "
              f"{len(devs)}", file=err)
        return None
    return devs


def _memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def measure(cell: Cell, seed: int, seconds: float, traced: bool,
            devs: list, err) -> tuple[Run, list, int]:
    """Set up, warm up and measure one window.  Returns the run, the
    window's answers (None for a failed request) and the compiles counted
    inside the window."""
    import jax
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    spans = Spans(traced)
    marks = dict(devices=process_age_s())
    state = cell.kind.setup(cell, seed)
    marks["built"] = process_age_s()
    cell.kind.warmup(state, spans)
    marks["warm"] = process_age_s()
    spans.spans.clear()

    log_dir = None
    if traced:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # Level 1 keeps the benchmark's spans and the runtime's main events;
        # at the default level, the runtime's host events of a window of
        # many small requests took minutes to convert when the trace
        # stopped.
        opts.host_tracer_level = 1
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    compiles0 = clock.compiles
    setup_s = process_age_s()
    answers, requests = [], []
    with spans("bench.window"):
        t_start = time.perf_counter()
        i = 0
        while True:
            with spans("bench.client"):
                payload = cell.kind.payload(state, i)
            t0 = time.perf_counter()
            try:
                ans, work = cell.kind.request(state, payload, spans, traced)
                ok = True
            except Exception:                # noqa: BLE001 - counted
                traceback.print_exc(file=err)
                ans, work, ok = None, {}, False
            t1 = time.perf_counter()
            requests.append(Request(i, t0, t1, work, ok))
            answers.append(ans)
            i += 1
            if t1 - t_start >= seconds:
                break
    window_s = t1 - t_start
    compiles = clock.compiles - compiles0

    trace = None
    if traced:
        from bench import trace as trace_mod
        t = time.perf_counter()
        jax.profiler.stop_trace()
        t_stop = time.perf_counter() - t
        t = time.perf_counter()
        try:
            trace = trace_mod.reduce(trace_mod.load(
                trace_mod.find_xplane(log_dir)))
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        print(f"bench: trace stopped in {t_stop:.3f} s, read in "
              f"{time.perf_counter() - t:.3f} s", file=err)
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs),
                  memory_peak_bytes=_memory_peak(devs[:cell.chips]))
    if trace is not None:
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
    run = Run(cell=cell, seed=seed, traced=traced, state=state,
              setup_s=setup_s, window_s=window_s, requests=requests,
              spans=spans.spans, trace=trace, device=device,
              setup_marks=marks)
    return run, answers, compiles


def metrics(run: Run) -> dict:
    out = {}
    for m in (run.cell.per_layer if run.traced else run.cell.end_to_end):
        value = run.cell.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = dict(value=float(value), unit=m["unit"])
    return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             require_chip: bool = True, out=None, err=None) -> int:
    """One run of one cell: the result line goes to ``out`` last; the
    numbers compared go to ``err`` last."""
    out = out or sys.stdout
    err = err or sys.stderr
    devs = find_devices(cell, require_chip, err)
    if devs is None:
        return 2
    print(f"bench: compile cache {enable_compile_cache(cell.root)}",
          file=err)
    run, answers, compiles = measure(cell, seed, seconds, traced, devs, err)
    failed = sum(not r.ok for r in run.requests)
    print(f"bench: setup_s={run.setup_s!r} window_s={run.window_s!r} "
          f"requests={len(run.requests)} failed={failed}", file=err)
    print("bench: set-up reached " + ", ".join(
        f"{k} at {v:.3f} s" for k, v in run.setup_marks.items()), file=err)
    print(f"bench: compiles inside the window: {compiles}", file=err)
    values = metrics(run)

    good = [a for a in answers if a is not None]
    t = time.perf_counter()
    numbers = cell.kind.check(run.state, good, seed) if good else {}
    print(f"bench: check took {time.perf_counter() - t:.3f} s", file=err)
    checks = {k: dict(value=float(numbers.get(k, float("inf"))),
                      limit=float(limit))
              for k, limit in cell.limits.items()}
    correct = (bool(good) and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = dict(correct=correct, attempted=len(run.requests),
                  failed=failed, metrics=values, device=run.device)
    if run.trace is not None:
        result["breakdown"] = dict(device_ops=run.trace["device_ops"],
                                   idle_gaps=run.trace["idle_gaps"])
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}", file=err)
    err.flush()
    print(json.dumps(result), file=out)
    out.flush()
    return 0
