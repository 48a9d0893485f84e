"""Benchmark driver: one table per paper table.

    PYTHONPATH=src python -m benchmarks.run            # all tables
    PYTHONPATH=src python -m benchmarks.run table6     # one table
    PYTHONPATH=src python -m benchmarks.run --fast     # smaller budgets
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    args = [a for a in sys.argv[1:]]
    fast = "--fast" in args
    args = [a for a in args if not a.startswith("--")]
    budget = 6.0 if fast else 12.0

    from . import (table5_characteristics, table6_polybench,
                   table7_resources, table8_multislice, table9_plans,
                   table10_solver_time)
    jobs = {
        "table5": lambda: table5_characteristics.run(),
        "table6": lambda: table6_polybench.run(budget=budget),
        "table7": lambda: table7_resources.run(budget=budget),
        "table8": lambda: table8_multislice.run(budget=budget),
        "table9": lambda: table9_plans.run(budget=budget),
        "table10": lambda: table10_solver_time.run(
            budget=10.0 if fast else 20.0),
    }
    selected = args or list(jobs)
    t_all = time.monotonic()
    for name in selected:
        if name not in jobs:
            raise SystemExit(f"unknown table {name!r}; have {list(jobs)}")
        t0 = time.monotonic()
        jobs[name]().show()
        print(f"[{name} done in {time.monotonic() - t0:.1f}s]\n",
              flush=True)
    print(f"[all benchmarks done in {time.monotonic() - t_all:.1f}s]")


if __name__ == "__main__":
    main()
