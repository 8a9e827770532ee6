#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cells, their configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the root of the checkout; this script finds their
files by those names.  It runs on the machine it is started on, exits 3
without a result where JAX finds no TPU or fewer chips than the cell asks
for, and 2 where the program (``src/``) is not beside it.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path
    bench = Path(__file__).resolve().parent
    sys.path[:0] = [str(bench), str(bench.parent / "src")]
    from harness.main import main
    sys.exit(main(t_start=T_START))
