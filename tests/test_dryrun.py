"""Dry-run launcher: representative cells lower+compile in a subprocess
(512 placeholder devices env is set by the module itself) on a reduced mesh
with SMOKE configs; artifact fields asserted.  The full 64-cell production
sweep is `python -m repro.launch.dryrun --all` — its committed results live
in experiments/dryrun/ and EXPERIMENTS.md."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CELLS = [
    ("granite-3-2b", "train_4k", "2,4"),            # dense TP
    ("kimi-k2-1t-a32b", "train_4k", "2,2,2"),       # MoE EP a2a, multipod
    ("phi3-medium-14b", "train_4k", "2,4"),         # context-parallel attn
    ("gemma2-2b", "decode_32k", "2,4"),             # windowed flash-decode
    ("mamba2-1.3b", "long_500k", "2,4"),            # SSM state decode
    ("whisper-base", "decode_32k", "2,2,2"),        # enc-dec cross cache
]


@pytest.mark.slow
@pytest.mark.parametrize("arch,shape,mesh", CELLS)
def test_dryrun_cell_smoke(arch, shape, mesh, tmp_path):
    env = {**os.environ, "PYTHONPATH": "src"}
    out = str(tmp_path)
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", shape, "--smoke", "--mesh-shape", mesh, "--out", out],
        capture_output=True, text=True, cwd=REPO, env=env,
        timeout=900)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    arts = [f for f in os.listdir(out) if f.endswith(".json")]
    assert len(arts) == 1
    rec = json.load(open(os.path.join(out, arts[0])))
    assert rec["ok"]
    assert rec["hlo_cost"]["flops"] > 0
    assert rec["roofline"]["dominant"] in ("memory", "compute", "traffic")
    assert "argument_bytes" in rec["memory_analysis"]
    if mesh.count(",") == 2 or arch != "mamba2-1.3b":
        # every sharded cell must actually communicate
        assert rec["hlo_cost"]["collective_bytes"] > 0


def test_production_sweep_artifacts_complete():
    """The committed production sweep must cover every assigned cell on
    both meshes (skips per DESIGN.md applied)."""
    d = os.path.join(REPO, "experiments", "dryrun")
    if not os.path.isdir(d):
        pytest.skip("production sweep not present")
    from repro.configs import registry
    missing = []
    for arch, shape in registry.all_cells():
        for mesh in ("pod", "multipod"):
            p = os.path.join(d, f"{arch}__{shape}__{mesh}.json")
            if not os.path.exists(p):
                missing.append((arch, shape, mesh))
                continue
            rec = json.load(open(p))
            assert rec["ok"], (arch, shape, mesh)
    assert not missing, missing
