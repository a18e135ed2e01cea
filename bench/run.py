"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell of ``BENCHMARK.json`` to its files, builds its inputs
and weights from ``--seed``, warms up every shape the window uses, measures
for ``--seconds`` seconds and prints one JSON object as the last line of
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics, or with ``--trace 1`` its per-layer
metrics read from a profiler trace of the window), ``device`` and, last,
``checks``: each number compared with the plain reference beside its
limit.  The same numbers are the last lines of standard error.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# One host thread for NumPy's BLAS, set before NumPy loads: the load comes
# from one client, and a pool sized to whatever cores the host has free
# spreads the host-bound cells' runs.
for _var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
    os.environ[_var] = "1"

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    return harness.run_cell(cell, args.seed, args.seconds, bool(args.trace))


if __name__ == "__main__":
    raise SystemExit(main())
