#!/usr/bin/env python3
"""Run one cell of the benchmark once:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine with a TPU.  See
``benchlib/harness.py``; the cells are listed in ``BENCHMARK.json``.
"""
import time

T_START = time.perf_counter()

import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from benchlib import harness  # noqa: E402

if __name__ == "__main__":
    harness.main(sys.argv[1:], T_START)
