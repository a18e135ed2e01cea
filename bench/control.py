"""Readings that set the limits of a cell's ``correct``.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--requests N]

For each seed, in one process: set the cell up, run ``N`` requests through
the timed path (default: as many as a run checks), and print one JSON line
with the numbers the cell compares, for the program (``program``) and for
the control (``control``): the plain reference at the next lower precision
put in the program's place.  A limit lies above every program reading and
below every control reading.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
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
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--requests", type=int, default=None)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    if harness.find_devices(cell, True, sys.stderr) is None:
        return 2
    harness.enable_compile_cache(cell.root)
    n = args.requests or int(cell.traffic["check_sample"])
    spans = harness.Spans(False)
    for seed in (int(s) for s in args.seeds.split(",")):
        state = cell.kind.setup(cell, seed)
        answers = [cell.kind.request(state, cell.kind.payload(state, i),
                                     spans, False)[0] for i in range(n)]
        print(json.dumps(dict(
            cell=cell.name, seed=seed,
            program=cell.kind.check(state, answers, seed),
            control=cell.kind.control(state, answers, seed))), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
