#!/usr/bin/env python3
"""Check ``BENCHMARK.json`` and the files it names against the contract, in
the sandbox, before any chip time is spent: ``python benchmark/check_manifest.py``.
Prints one line for each breach and exits 1 if there is any."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark.harness import manifest

    found = manifest.check()
    for line in found:
        print(line)
    print(f"BENCHMARK.json: {len(found)} breach(es) of the contract")
    sys.exit(1 if found else 0)
