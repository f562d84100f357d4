#!/usr/bin/env python3
"""The benchmark's command: one process, one cell, one run.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on the machine that holds the chips the cell
asks for. The last line of the output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced, ``breakdown``).
It exits non-zero and prints no result off a TPU, with fewer chips than the
cell asks for, or where the program is not in the checkout."""

import time

T_START = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark.harness.loop import main

    sys.exit(main(None, T_START))
