#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Each call is one process that owns the chip: it sets up the cell named in
``BENCHMARK.json``, warms up every shape the cell uses, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object. With ``--trace 1`` it also profiles a
short steady stretch after the window and reports the per-layer metrics.
Without a TPU (or with fewer chips than the cell needs) it exits 3 and prints
no result.
"""

import time

T_START = time.time()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
