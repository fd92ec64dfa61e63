#!/usr/bin/env python3
"""Chip benchmark entry point: one run of one cell.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
      --seconds <run_seconds> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the repository root. The last line of standard output
is the run's JSON result. Without a TPU (or with fewer chips than the cell
asks for) it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if __name__ == "__main__":
    import harness
    sys.exit(harness.run(t_process=T_PROCESS))
