#!/usr/bin/env python3
"""``bench/control.py`` for the cells whose traffic names the
``trainer_first_client`` generator: the same command line, runs and output,
with the control and the planted faults read by that generator's
``control_readings`` (the first client's own steps).

    python3 bench/control_first_client.py --workload <name> --seeds 1,2,3 \\
        --controls 1,2,3 --seconds 5 --out <name>.readings.jsonl

The benchmark's own runs never run this.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import control  # noqa: E402
from harness import spec  # noqa: E402


def control_readings(cell, seed: int, rep) -> dict:
    return spec.generator(cell).control_readings(cell, seed, rep)


if __name__ == "__main__":
    control.control_readings = control_readings
    sys.exit(control.main())
