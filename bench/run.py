#!/usr/bin/env python3
"""Run one benchmark cell and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine with the chips the cell asks
for; see ``bench/harness.py``.
"""

import time

T_START = time.perf_counter()  # set-up is timed from here

import sys  # noqa: E402

import pathsetup  # noqa: E402,F401  (the harness and the program)
from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
