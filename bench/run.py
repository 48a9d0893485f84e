#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (configuration, traffic mix, limits, metrics) is found by name
from ``BENCHMARK.json``.  A run sets up (weights and inputs from the seed,
compilation or the compile cache, warm-up of the cell's own shapes),
measures for ``--seconds``, frees the program, checks a seeded sample of
what the window produced against a plain reference, and prints one JSON
object as its last line.  ``--trace 1`` records the window with the JAX
profiler and reports the per-layer metrics instead of the end-to-end
ones.  Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""
import os
import sys
import time

T_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

if __name__ == "__main__":
    from bench.harness.core import main
    sys.exit(main(sys.argv[1:], T_START))
