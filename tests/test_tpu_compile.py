"""Ahead-of-time compiles of the main path's Pallas kernels for one TPU
v5e chip, at the widths ``chip_smoke.py`` runs them, plus CPU checks of
the smoke script and of the compile-cache location.

The TPU compiler compiles for a described ``v5e:2x2`` topology with no
chip attached.  Only one process at a time may load its library, so the
topology is described inside a module-scoped fixture, never at import:
every pytest-xdist worker then collects the same tests, and only the
worker given this file loads the library.  A compile that passes says the
chip's compiler accepts the kernel; it says nothing about results or time.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.kernels import (event_matmul, event_matmul_pair,
                           sigma_delta_encode)
from repro.kernels.flash_attn import flash_attention
from repro.kernels.sigma_delta.ops import window_reconstruct
from repro.launch import mesh as launch_mesh
from repro.neuromorphic import precompute_pricing
from repro.neuromorphic.compute import _dense_conv

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = chip_smoke.STEPS

#: (rows, fan-in, fan-out) of the S5 stack's fc layers
FC_SHAPES = [(T, a, b) for a, b in zip(chip_smoke.S5_SIZES,
                                       chip_smoke.S5_SIZES[1:])]
#: (steps, fan-in, width) of the distinct layers of the Nemotron 3 Nano
#: share at published widths (Mamba in / state, MoE up / down, attention
#: qkv / scores / values, out-projections)
NEMOTRON_SHAPES = [(64, 2688, 772), (64, 772, 256), (64, 256, 2688),
                   (64, 2688, 18688), (64, 18688, 2688), (64, 2688, 512),
                   (64, 512, 16384), (64, 16384, 256)]


def _conv_shapes():
    """(im2col rows, cin * 9, cout) of the sigma-delta conv net's layers."""
    h, w = chip_smoke.CONV_HW
    cin, out = 2, []
    for c in chip_smoke.CONV_CHANNELS:
        h, w = h // 2, w // 2
        out.append((T * h * w, cin * 9, c))
        cin = c
    return out


#: widest delta stream reconstructed in the sigma-delta net (conv1's input)
SD_WIDTH = chip_smoke.CONV_CHANNELS[0] * (chip_smoke.CONV_HW[0] // 2) ** 2


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: an entry written for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:      # noqa: BLE001 - any failure skips
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


def _compile(sharding, fn, shapes, **static):
    """Compile ``fn`` for the described chip from (shape, dtype) pairs, None
    for an absent operand, and return the compiled HLO text."""
    args = [None if sd is None else
            jax.ShapeDtypeStruct(*sd, sharding=sharding) for sd in shapes]
    return fn.lower(*args, **static).compile().as_text()


def _assert_mosaic(text):
    assert "tpu_custom_call" in text, "kernel lowered without Mosaic"


def _pair_shapes(m, k, n, occ):
    f32 = jnp.float32
    shapes = [((m, k), f32), ((m, k), f32), ((k, n), f32), ((k, n), f32)]
    if occ:
        shapes.append(((-(-k // 128), -(-n // 128)), jnp.bool_))
    return shapes


@pytest.mark.parametrize("m,k,n,occ", [
    *[(m, k, n, True) for m, k, n in FC_SHAPES],
    (*FC_SHAPES[1], False),
    *[(m, k, n, True) for m, k, n in _conv_shapes()],
    *[(m, k, n, True) for m, k, n in NEMOTRON_SHAPES],
])
def test_event_matmul_pair_compiles(one_chip, m, k, n, occ):
    _assert_mosaic(_compile(one_chip, event_matmul_pair,
                            _pair_shapes(m, k, n, occ), interpret=False))


def test_event_matmul_conv_rows_compiles(one_chip):
    m, k, n = _conv_shapes()[-1]
    _assert_mosaic(_compile(one_chip, event_matmul,
                            [((m, k), jnp.float32), ((k, n), jnp.float32)],
                            interpret=False))


def test_sigma_delta_encode_compiles(one_chip):
    shape = ((T, SD_WIDTH), jnp.float32)
    _assert_mosaic(_compile(one_chip, sigma_delta_encode, [shape, shape],
                            theta=0.1, interpret=False))


@pytest.mark.parametrize("layer", range(len(chip_smoke.CONV_CHANNELS)))
def test_dense_conv_program_compiles(one_chip, layer):
    """The dense backend's one program a conv layer, at the sigma-delta
    net's widths: layer 0 takes values and their event mask, the later
    layers a delta stream they rebuild on the device."""
    h = chip_smoke.CONV_HW[0] >> layer
    cin = (2, *chip_smoke.CONV_CHANNELS)[layer]
    w = ((3, 3, cin, chip_smoke.CONV_CHANNELS[layer]), jnp.float32)
    x = ((T, cin * h * h), jnp.float32)
    mask = None if layer else ((T, cin * h * h), jnp.bool_)
    text = _compile(one_chip, _dense_conv, [x, mask, None, w, w],
                    hw=(h, h), stride=2)
    assert "convolution" in text


@pytest.mark.parametrize("window", [8, 32, 128])
def test_window_reconstruct_compiles(one_chip, window):
    _assert_mosaic(_compile(
        one_chip, window_reconstruct,
        [((T, SD_WIDTH), jnp.float32), ((SD_WIDTH,), jnp.float32)],
        window=window, interpret=False))


def test_flash_attention_compiles(one_chip):
    q = ((1, 256, 8, 128), jnp.float32)
    kv = ((1, 256, 2, 128), jnp.float32)
    _assert_mosaic(_compile(one_chip, flash_attention, [q, kv, kv],
                            causal=True, interpret=False))


# ------------------------------------------------------- the smoke on CPU

def test_smoke_phases_at_tiny_size():
    """Every phase of chip_smoke.py passes on the CPU at a tiny size."""
    net, xs, prof = chip_smoke.s5_workload(sizes=(16, 32, 32, 16), steps=8)
    cache = precompute_pricing(net, xs, prof)
    assert chip_smoke.phase_device()["platform"] == "cpu"
    b = chip_smoke.phase_functional(net, xs, prof)
    assert b["output_rel_err"] <= chip_smoke.OUTPUT_RTOL
    relu = chip_smoke.s5_workload(sizes=(16, 32, 32, 16), steps=8,
                                  neuron_model="relu")
    assert chip_smoke.phase_functional(*relu)["msgs_per_step"] > 0
    c = chip_smoke.phase_sigma_delta(*chip_smoke.sigma_delta_workload(
        in_hw=(8, 8), channels=(4, 8), steps=16), window=8)
    assert np.isfinite(c["event_window_time_rel"])
    d = chip_smoke.phase_pricing(net, xs, prof, cache, population=8)
    assert d["candidates"] == 8 and d["max_rel_err"] <= chip_smoke.F64_RTOL
    e = chip_smoke.phase_search(net, xs, prof, cache, population=8,
                                generations=2)
    assert e["best_time"] <= e["seed_best_time"]
    s = chip_smoke.phase_sharded(net, xs, prof, cache, n_islands=1,
                                 population=8, generations=2)
    assert s["islands"] == 1 and s["max_rel_err"] == 0.0


def test_smoke_refuses_the_cpu(capsys):
    """Without a TPU the smoke exits non-zero and prints no result; the
    event backend's kernel check refuses the host gather path."""
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
    net, xs, _ = chip_smoke.s5_workload(sizes=(16, 32), steps=8)
    with pytest.raises(AssertionError, match="not the Pallas kernels"):
        chip_smoke.check_event_kernels_compiled(net, xs)


def test_smoke_alone_fails(tmp_path):
    """Copied into a directory without the rest of the repository, the
    script fails and prints no result."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env={**env, "JAX_PLATFORMS": "cpu"},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


# ---------------------------------------------------- compile-cache place

_CACHE_PROBE = r"""
import json, sys
import jax, jax.numpy as jnp
from repro.launch.mesh import enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
if sys.argv[1] == "compile":
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)).block_until_ready()
print(json.dumps([path, jax.config.jax_compilation_cache_dir]))
"""


def _cache_probe(env, mode):
    env = {**env, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.path.join(REPO, "src")}
    r = subprocess.run([sys.executable, "-c", _CACHE_PROBE, mode], env=env,
                       cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_compile_cache_uses_environment_dir(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
    path, configured = _cache_probe(env, "compile")
    assert path == configured == str(tmp_path / "cc")
    assert os.listdir(tmp_path / "cc")


def test_compile_cache_defaults_to_checkout_dir():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    path, configured = _cache_probe(env, "config")
    assert path == configured == launch_mesh.COMPILE_CACHE_DIR \
        == os.path.join(REPO, ".jax_cache")
