#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload r1-doc-decode --seed 1234 \
        --seconds 40 --trace 0

The cells, configurations and metrics are named in BENCHMARK.json at the
root of the checkout; each cell's files are under bench/ (see
bench/harness/cell.py).  The last line of standard output is one JSON
object: correct, attempted, failed, metrics, device, (breakdown), checks.
Exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from harness import cell  # noqa: E402

if __name__ == "__main__":
    sys.exit(cell.main(sys.argv[1:], T_START))
