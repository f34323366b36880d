#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` on several seeds of one cell,
in one process: the readings each limit in bench/workloads/<cell>.json is
set from.  With ``--control 1`` the control (the plain reference in float8)
is put in the program's place and judged by the same comparison and the
cell's own limits, so ``correct`` has to come out false.

    python bench/control.py --workload r1-doc-decode --seconds 10 \
        --seeds 11,12,13 --control 1

Prints one JSON line per seed.  Needs the cell's chips, as bench/run.py.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import cell  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    a = ap.parse_args()
    for seed in (int(s) for s in a.seeds.split(",")):
        try:
            out = cell.run(BENCH.parent, a.workload, seed, a.seconds, False,
                           time.perf_counter(), control=bool(a.control))
        except cell.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          **{k: v["value"] for k, v in out["checks"].items()},
                          "metrics": {k: v["value"] for k, v
                                      in out["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
