"""Production meshes, the pre-import host-device-count switch and the
persistent compilation cache location.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — and since the
``--devices`` flag landed, this module does not even import jax at module
scope: :func:`force_host_device_count` must run *before* the first jax
import anywhere in the process (XLA reads
``--xla_force_host_platform_device_count`` exactly once, at backend init),
so the benchmark drivers import ``repro.launch.mesh`` alone, apply the
flag, and only then import the jax-heavy modules.  The dry-run launcher
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` the same way;
smoke tests and benchmarks see the real single CPU device.

Mesh axes:
  single pod:  (16, 16)      ("data", "model")   = 256 chips (one v5e pod)
  multi-pod:   (2, 16, 16)   ("pod", "data", "model") = 512 chips

`model` carries TP/SP (and MoE expert-FF); `data` carries DP and MoE EP
(expert parallelism stays on intra-pod ICI); `pod` is pure DP over the
inter-pod links (DCI), which only see gradient reduce-scatters.

The sharded evolutionary search uses the separate 1-D ``("island",)`` mesh
of :func:`repro.distributed.sharding.island_mesh` (``docs/distributed.md``).
"""

from __future__ import annotations

import os
import sys

import numpy as np

_FORCE_FLAG = "--xla_force_host_platform_device_count"

#: fixed in-checkout cache directory, used when the environment names none
COMPILE_CACHE_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and no
    other directory is set.  Otherwise the cache lives in ``.jax_cache/``
    at the root of this checkout: the path is part of the cache key, so it
    must not move between runs.  Call from an entry point, never at import.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def forced_host_device_count() -> int | None:
    """The count currently requested via XLA_FLAGS, or None."""
    for tok in os.environ.get("XLA_FLAGS", "").split():
        if tok.startswith(_FORCE_FLAG + "="):
            try:
                return int(tok.split("=", 1)[1])
            except ValueError:
                return None
    return None


def force_host_device_count(n: int) -> None:
    """Request ``n`` CPU placeholder devices for this process, BEFORE jax.

    Rewrites ``XLA_FLAGS`` (replacing any prior
    ``--xla_force_host_platform_device_count``).  XLA reads the flag once,
    when the backend initializes on first jax import — so this raises a
    clear :class:`RuntimeError` if jax is already in ``sys.modules`` and
    the flag would silently not take effect.  Idempotent: a repeated call
    with the count already in force is a no-op (so module-level pre-parse
    hooks and argparse handlers can both call it).
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    if forced_host_device_count() == n:
        return
    if any(m == "jax" or m.startswith("jax.") for m in sys.modules):
        raise RuntimeError(
            f"force_host_device_count({n}) must run before jax is first "
            "imported: XLA reads --xla_force_host_platform_device_count "
            "once, at backend init, so setting it now would have no "
            "effect.  Pass --devices N to `python -m benchmarks.run` / "
            "`python -m benchmarks.search_mapping` (they apply it before "
            "importing jax), or export XLA_FLAGS="
            f"'{_FORCE_FLAG}={n}' before starting python.")
    flags = [t for t in os.environ.get("XLA_FLAGS", "").split()
             if not t.startswith(_FORCE_FLAG)]
    flags.append(f"{_FORCE_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)


def apply_devices_flag(argv) -> int | None:
    """Pre-argparse scan of ``argv`` for ``--devices N`` / ``--devices=N``.

    Benchmark entry points call this at module import time (before their
    jax-importing imports run) so the flag can take effect; the later
    argparse pass keeps ``--devices`` for ``--help`` and validation.
    Returns the applied count, or None when the flag is absent."""
    n = None
    for i, tok in enumerate(argv):
        if tok == "--devices" and i + 1 < len(argv):
            n = argv[i + 1]
        elif tok.startswith("--devices="):
            n = tok.split("=", 1)[1]
    if n is None:
        return None
    try:
        count = int(n)
    except ValueError:
        raise SystemExit(f"--devices expects an integer, got {n!r}")
    force_host_device_count(count)
    return count


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices but only {len(devices)} are "
            "visible — launch via repro.launch.dryrun (it sets "
            "XLA_FLAGS=--xla_force_host_platform_device_count=512 before "
            "importing jax)")
    return jax.make_mesh(shape, axes, (jax.sharding.AxisType.Auto,) * len(shape),
                         devices=devices[:n])


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh for tests / elastic restarts (e.g. (2,4) on 8 CPU
    placeholder devices)."""
    import jax
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(f"mesh {shape} needs {n} devices, "
                           f"have {len(devices)}")
    return jax.make_mesh(shape, axes, (jax.sharding.AxisType.Auto,) * len(shape),
                         devices=devices[:n])
