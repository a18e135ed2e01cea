"""The harness finds every cell's files by name, refuses to run without a
chip, and drives each request kind at a tiny size on the CPU."""

import glob
import io
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.conftest import tiny

ROOT = harness.ROOT


def _bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("name", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves_to_its_files(name):
    cell = harness.resolve(name)
    assert cell.chips in (1, 4)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.reader(m["name"]))
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    assert cell.limits and all(v >= 0 for v in cell.limits.values())


def test_a_cell_added_as_files_is_found_without_edits(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _bench()
    # a configuration, a traffic mix, a cell, its limits and a per-layer
    # metric, each a new file and a new entry
    cfg = harness.load_json(os.path.join(ROOT, "bench/configs/s5_loihi2.json"))
    cfg["name"] = "s5_narrow"
    cfg["network"]["sizes"] = [512, 1024, 512]
    (root / "bench/configs/s5_narrow.json").write_text(json.dumps(cfg))
    traffic = harness.load_json(
        os.path.join(ROOT, "bench/traffic/sim.t64.d010.json"))
    (root / "bench/traffic/sim.t32.d050.json").write_text(
        json.dumps(dict(traffic, steps=32, density=0.5)))
    (root / "bench/limits/s5n.sim.json").write_text(
        json.dumps({"out_gap": 1e-4}))
    (root / "bench/metrics/requests.sim.py").write_text(
        "def read(run):\n    return len(run.requests)\n")
    bench["configs"].append(dict(name="s5_narrow", source="x",
                                 file="bench/configs/s5_narrow.json",
                                 reduced=[], why="x"))
    bench["workloads"].append(dict(name="s5n.sim", config="s5_narrow",
                                   traffic="sim.t32.d050", chips=1, why="x"))
    for m in bench["end_to_end"]:
        if m["name"] == "sim_steps_per_s":
            m["workloads"].append("s5n.sim")
    bench["per_layer"].append(dict(name="requests.sim", unit="req",
                                   better="higher", source="host_clock",
                                   layer="network", moves="sim_steps_per_s"))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.resolve("s5n.sim", root=str(root))
    assert cell.config["network"]["sizes"] == [512, 1024, 512]
    assert cell.traffic["density"] == 0.5
    assert [m["name"] for m in cell.end_to_end] == ["sim_steps_per_s",
                                                    "setup_s"]
    assert "requests.sim" in [m["name"] for m in cell.per_layer]
    assert cell.reader("requests.sim")(
        harness.Run(cell, 0, True, {}, 0.0, 1.0, [1, 2], [], None, {})) == 2
    # a per-layer metric without a cell list follows the metric it moves
    assert "requests.sim" in [m["name"] for m in
                              harness.resolve("s5.sim",
                                              root=str(root)).per_layer]
    assert "requests.sim" not in [m["name"] for m in
                                  harness.resolve("s5.search",
                                                  root=str(root)).per_layer]


def _command(root, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "s5.sim", "--seed",
         "2147483659", "--seconds", "1", "--trace", "0", *extra],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_command_on_cpu_fails_and_prints_no_result():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_command_without_the_program_fails(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = _command(str(tmp_path))
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_each_kind_runs_a_window_at_tiny_size(tiny_cell):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(tiny_cell, 2**31 + 11, 0.5, False,
                          require_chip=False, out=out, err=err)
    assert rc == 0, err.getvalue()
    res = json.loads(out.getvalue().splitlines()[-1])
    assert res["correct"], err.getvalue()
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in tiny_cell.end_to_end}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(tiny_cell.limits)
    assert res["device"]["platform"] == "cpu"
    last = err.getvalue().strip().splitlines()[-len(tiny_cell.limits):]
    assert all(line.startswith("check ") for line in last)


def test_a_traced_run_reads_its_trace_within_the_traced_window(monkeypatch):
    from bench import flops
    monkeypatch.setattr(flops, "peak", lambda kind: 1e12)   # no CPU peak
    cell = tiny("s5.sim")
    out, err = io.StringIO(), io.StringIO()
    assert harness.run_cell(cell, 2**31 + 13, 0.3, True, require_chip=False,
                            out=out, err=err) == 0, err.getvalue()
    res = json.loads(out.getvalue().splitlines()[-1])
    assert res["correct"], err.getvalue()
    assert 0.3 <= res["device"]["window_s"] < 5.0
    assert set(res["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert {"functional_ms.sim", "pricing_ms.sim", "mfu.sim"} <= \
        set(res["metrics"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_a_window_whose_requests_all_fail_is_not_correct(monkeypatch):
    cell = tiny("s5.sim")

    def broken(*_args, **_kw):
        raise RuntimeError("request failed")
    monkeypatch.setattr(cell.kind, "warmup", lambda state, span: None)
    monkeypatch.setattr(cell.kind, "request", broken)
    out, err = io.StringIO(), io.StringIO()
    assert harness.run_cell(cell, 7, 0.2, False, require_chip=False,
                            out=out, err=err) == 0
    res = json.loads(out.getvalue().splitlines()[-1])
    assert not res["correct"] and res["failed"] == res["attempted"] >= 1
    assert all(c["value"] == float("inf") for c in res["checks"].values())


def _configs():
    return sorted(glob.glob(os.path.join(ROOT, "bench", "configs", "*.json")))


@pytest.mark.parametrize("path", _configs(), ids=os.path.basename)
def test_a_configuration_differs_from_its_source_only_where_reduced(path):
    config = harness.load_json(path)
    entry = {c["file"]: c for c in _bench()["configs"]}.get(
        os.path.relpath(path, ROOT))
    published = config.get("published")
    if published is None:
        # no published sizes: each size run is stated as assumed
        assert config["assumed"] and (entry is None or entry["reduced"] == [])
        return
    changed = sorted(k for k in published
                     if published[k] != config["network"].get(k))
    assert changed == sorted(config["reduced"])
    assert entry is None or sorted(entry["reduced"]) == changed


def test_the_pilotnet_configuration_builds_at_its_published_widths():
    from bench import reference, workload
    config = harness.load_json(
        os.path.join(ROOT, "bench/configs/pilotnet_loihi2.json"))
    layers, in_size = workload.build_layers(config, 3, ROOT)
    assert in_size == 64 * 200 * 3
    assert [s["weights"].shape for s in layers] == [
        (5, 5, 3, 24), (5, 5, 24, 36), (5, 5, 36, 48), (3, 3, 48, 64),
        (3, 3, 64, 64), (12800, 100), (100, 50), (50, 10), (10, 1)]
    assert sum(reference.n_neurons(s) for s in layers) == config["neurons"]
    assert sum(reference.minimal_cores(layers, config["chip"])) == 43
