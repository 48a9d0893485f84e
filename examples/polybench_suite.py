"""Optimize + execute the full executable PolyBench suite.

    PYTHONPATH=src python examples/polybench_suite.py [--scale N] [--impl I]

For each kernel: solve the Prometheus NLP, lower the plan through the
codegen subsystem (one fused Pallas kernel per task), validate against the
reference oracle, and report model GF/s plus measured wall time.
"""
import argparse
import time

from repro.codegen import (allclose, enable_compile_cache, plan_executor,
                           random_inputs, reference_executor)
from repro.core import THREE_SLICE, SolverOptions, polybench, solve

EXECUTABLE = ["3mm", "2mm", "gemm", "atax", "bicg", "mvt", "gesummv",
              "gemver", "madd", "2-madd", "3-madd"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=1,
                    help="dataset scale (1 = paper medium)")
    ap.add_argument("--budget", type=float, default=10.0)
    ap.add_argument("--impl", default=None,
                    choices=("xla", "pallas_interpret", "pallas"),
                    help="kernel implementation (default: auto)")
    args = ap.parse_args()
    enable_compile_cache()

    print(f"{'kernel':10s} {'GF/s(model)':>12s} {'solver_s':>9s} "
          f"{'exec_ms':>8s} {'lowered':>12s} {'validated':>9s}")
    for name in EXECUTABLE:
        g = polybench.build(name, scale=args.scale)
        plan = solve(g, THREE_SLICE,
                     SolverOptions(time_budget_s=args.budget))
        exe = plan_executor(g, plan, impl=args.impl)
        ins = random_inputs(g, seed=0)
        out = exe(ins)                          # compile + warm up
        for v in out.values():
            v.block_until_ready()               # drain async dispatch
        t0 = time.monotonic()
        out = exe(ins)
        for v in out.values():
            v.block_until_ready()
        exec_ms = (time.monotonic() - t0) * 1e3
        kinds = {lw.kind for lw in exe.lowerings().values()}
        lowered = "+".join(sorted(kinds))
        ok = "-"
        if args.scale == 1:          # numeric validation at medium sizes
            ref = reference_executor(g)(ins)
            ok = all(allclose(out[k], ref[k]) for k in ref)
        print(f"{name:10s} {plan.gflops:12.1f} "
              f"{plan.solver_seconds:9.2f} {exec_ms:8.2f} {lowered:>12s} "
              f"{str(ok):>9s}")


if __name__ == "__main__":
    main()
