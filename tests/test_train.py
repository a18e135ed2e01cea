"""Training loop: convergence, checkpoint/restart determinism, fault
recovery, elastic re-meshing (subprocess with 8 placeholder devices),
straggler detection, schedules and optimizers."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models.layers import single_device_mesh
from repro.train import data as data_lib
from repro.train import optim, schedules
from repro.train.loop import StragglerMonitor, Trainer, TrainerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mk_trainer(tmp, steps=12, resume=False, ckpt_every=4, seed=0):
    cfg = registry.get("granite-3-2b").smoke()
    data = data_lib.SyntheticLM(data_lib.LMTaskConfig(
        vocab_size=cfg.vocab_size, seq_len=32, global_batch=4, seed=1))
    opt = optim.adamw(schedules.constant(2e-3))
    tcfg = TrainerConfig(steps=steps, log_every=4, ckpt_every=ckpt_every,
                         ckpt_dir=tmp, resume=resume, seed=seed)
    return Trainer(cfg, single_device_mesh(), opt, data, tcfg)


def test_trainer_converges(tmp_path):
    t = _mk_trainer(str(tmp_path), steps=20)
    hist = t.run()
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_checkpoint_resume_bit_identical(tmp_path):
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    # continuous 12-step run
    t_full = _mk_trainer(d1, steps=12, ckpt_every=100)
    full = t_full.run()
    # interrupted run: 8 steps, then resume to 12
    t1 = _mk_trainer(d2, steps=8, ckpt_every=8)
    t1.run()
    t2 = _mk_trainer(d2, steps=12, resume=True, ckpt_every=100)
    resumed = t2.run()
    a = next(h for h in full if h["step"] == 12)
    b = next(h for h in resumed if h["step"] == 12)
    np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-5)


def test_fault_recovery(tmp_path):
    t = _mk_trainer(str(tmp_path), steps=12, ckpt_every=4)
    calls = {"n": 0}

    def fault(step):
        if step == 6 and calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("injected node failure")
    t.fault_hook = fault
    hist = t.run()
    assert hist[-1]["step"] == 12          # recovered and finished
    assert calls["n"] == 1


@pytest.mark.quick
def test_straggler_monitor():
    m = StragglerMonitor(factor=3.0)
    for i in range(10):
        assert not m.record(i, 0.1)
    assert m.record(10, 1.0)               # 10x slower -> flagged
    assert len(m.events) == 1


_ELASTIC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
import numpy as np
import jax
sys.path.insert(0, "src")
from repro.configs import registry
from repro.launch.mesh import make_mesh
from repro.train import data as data_lib, optim, schedules
from repro.train.loop import Trainer, TrainerConfig

ckpt = sys.argv[1]
phase = sys.argv[2]
mesh = make_mesh((2, 4) if phase == "a" else (4, 2), ("data", "model"))
cfg = registry.get("granite-3-2b").smoke()
data = data_lib.SyntheticLM(data_lib.LMTaskConfig(
    vocab_size=cfg.vocab_size, seq_len=32, global_batch=8, seed=1))
opt = optim.adamw(schedules.constant(2e-3))
steps = 6 if phase == "a" else 12
tcfg = TrainerConfig(steps=steps, log_every=2, ckpt_every=6,
                     ckpt_dir=ckpt, resume=(phase == "b"))
t = Trainer(cfg, mesh, opt, data, tcfg)
hist = t.run()
print("RESULT", json.dumps(hist[-1]))
"""


@pytest.mark.slow
def test_elastic_remesh(tmp_path):
    """Train on (2,4) mesh, checkpoint, resume on (4,2): the checkpoint is
    resharded on load and training continues (loss stays finite+decreasing)."""
    ckpt = str(tmp_path / "ck")
    env = {**os.environ, "PYTHONPATH": "src"}
    r1 = subprocess.run([sys.executable, "-c", _ELASTIC, ckpt, "a"],
                        capture_output=True, text=True, cwd=REPO,
                        env=env, timeout=600)
    assert r1.returncode == 0, r1.stderr[-2000:]
    l1 = json.loads(r1.stdout.split("RESULT", 1)[1])
    r2 = subprocess.run([sys.executable, "-c", _ELASTIC, ckpt, "b"],
                        capture_output=True, text=True, cwd=REPO,
                        env=env, timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    l2 = json.loads(r2.stdout.split("RESULT", 1)[1])
    assert l2["step"] == 12 and np.isfinite(l2["loss"])
    assert l2["loss"] < l1["loss"] + 0.5


@pytest.mark.quick
def test_wsd_schedule_shape():
    fn = schedules.wsd(1.0, warmup=10, stable=50, decay=40)
    s = lambda i: float(fn(jnp.int32(i)))
    assert s(0) < 0.2
    assert abs(s(30) - 1.0) < 1e-6          # stable plateau
    assert s(99) < 0.1                      # decayed


def test_adafactor_reduces_loss():
    cfg = registry.get("granite-3-2b").smoke()
    data = data_lib.SyntheticLM(data_lib.LMTaskConfig(
        vocab_size=cfg.vocab_size, seq_len=32, global_batch=4, seed=1))
    opt = optim.adafactor(schedules.constant(2e-2))
    tcfg = TrainerConfig(steps=16, log_every=4)
    t = Trainer(cfg, single_device_mesh(), opt, data, tcfg)
    hist = t.run()
    assert hist[-1]["loss"] < hist[0]["loss"]


@pytest.mark.quick
def test_adafactor_state_is_factored():
    cfg = registry.get("granite-3-2b").smoke()
    from repro.models import lm
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    opt = optim.adafactor(schedules.constant(1e-2), min_dim_factored=32)
    st = opt.init(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    n_state = sum(x.size for x in jax.tree.leaves(st))
    # factored second moments: far below Adam's 3x params (m+v+master);
    # small 3-d attention tensors stay unfactored in the smoke config
    assert n_state < 0.5 * n_params
