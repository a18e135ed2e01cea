"""int8 error-feedback gradient compression: quantizer properties +
convergence equivalence on a real multi-device (subprocess) DP run."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.distributed import collectives as C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@given(st.integers(0, 2**32 - 1), st.floats(0.1, 100.0))
@settings(max_examples=25, deadline=None)
def test_quantize_bounded_error(seed, scale):
    x = (jax.random.normal(jax.random.PRNGKey(seed), (64,)) * scale)
    q, s = C.quantize_int8(x)
    err = np.abs(np.asarray(C.dequantize_int8(q, s) - x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_accumulates():
    """With error feedback, the running SUM of compressed estimates tracks
    the true sum (bounded error), even for tiny gradients that always
    quantize to zero individually."""
    x = jnp.full((16,), 1e-3)
    err = jnp.zeros((16,))
    tot = jnp.zeros((16,))
    mesh = jax.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    step = jax.jit(jax.shard_map(
        lambda e: C.compressed_psum_mean(x, e, ("data",)),
        mesh=mesh, in_specs=(jax.sharding.PartitionSpec(),),
        out_specs=(jax.sharding.PartitionSpec(),) * 2, check_vma=False))
    for _ in range(50):
        g, err = step(err)
        tot = tot + g
    np.testing.assert_allclose(np.asarray(tot), 50e-3, rtol=0.15)


_DP_RUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src")
from repro.configs import registry
from repro.launch.mesh import make_mesh
from repro.train import data as data_lib, optim, schedules
from repro.train.loop import Trainer, TrainerConfig

compress = sys.argv[1] == "1"
mesh = make_mesh((4, 1), ("data", "model"))
cfg = registry.get("granite-3-2b").smoke()
data = data_lib.SyntheticLM(data_lib.LMTaskConfig(
    vocab_size=cfg.vocab_size, seq_len=32, global_batch=8, seed=5))
opt = optim.adamw(schedules.constant(2e-3))
tcfg = TrainerConfig(steps=15, log_every=15, compress_grads=compress)
t = Trainer(cfg, mesh, opt, data, tcfg)
hist = t.run()
print("LOSS", hist[-1]["loss"])
"""


@pytest.mark.slow
def test_compressed_dp_matches_exact():
    env = {**os.environ, "PYTHONPATH": "src"}
    losses = {}
    for flag in ("0", "1"):
        r = subprocess.run([sys.executable, "-c", _DP_RUN, flag],
                           capture_output=True, text=True, cwd=REPO,
                           env=env, timeout=900)
        assert r.returncode == 0, r.stderr[-2000:]
        losses[flag] = float(r.stdout.split("LOSS", 1)[1])
    # int8 + error feedback must track the exact DP run closely
    assert abs(losses["1"] - losses["0"]) < 0.15 * abs(losses["0"]) + 0.1, \
        losses
