"""Run a small traced workload and dump its observability artifacts.

Produces, from one ``PlanEngine`` session with span tracing enabled:

* a Chrome-trace / Perfetto JSON file (``--trace``) — load it at
  https://ui.perfetto.dev or ``chrome://tracing`` to see the request
  path (submit, admission, resolve, execute, sync, fallback), the solver
  phases (fuse/enumerate/chunk-merge), store load/save and the frontend
  trace — one virtual thread row per recording thread;
* a Prometheus text-exposition file (``--metrics``) — the same numbers
  ``PlanEngine.stats()`` reports, in scrape format.

Both artifacts are validated after writing (the trace re-loaded as JSON
and checked for complete events, the exposition parsed line by line);
a validation failure exits nonzero, which is how CI asserts the export
round-trip.

Usage:
    PYTHONPATH=src python scripts/obs_dump.py \
        --trace obs_trace.json --metrics obs_metrics.txt [--requests 8]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parent.parent
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))

from benchmarks.bench_obs import (_workload, validate_chrome_trace,
                                  validate_exposition)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace", default="obs_trace.json",
                    help="Chrome-trace JSON output path")
    ap.add_argument("--metrics", default="obs_metrics.txt",
                    help="Prometheus text exposition output path")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--budget", type=float, default=2.0,
                    help="solver time budget (seconds)")
    args = ap.parse_args()

    from repro.core.solver import SolverOptions
    from repro.obs import configure, dump_chrome_trace, tracer
    from repro.serve import PlanEngine, ServeConfig

    configure(enabled=True)
    tracer().clear()
    fn, fn_args = _workload()
    eng = PlanEngine(sc=ServeConfig())
    tf = eng.register_function(
        "w", fn, fn_args, solver_opts=SolverOptions(time_budget_s=args.budget))
    if tf is None:
        print("obs_dump: trace/solve failed (degraded mode)", file=sys.stderr)
        return 1
    for _ in range(max(1, args.requests)):
        eng.submit("w", fn_args)

    spans = tracer().snapshot()
    dump_chrome_trace(spans, args.trace)
    text = eng.metrics.expose()
    with open(args.metrics, "w") as f:
        f.write(text)
    eng.shutdown()
    configure(enabled=False)

    # round-trip validation: re-read what was written, as a consumer would
    with open(args.trace) as f:
        doc = json.load(f)
    trace_problems = validate_chrome_trace(doc)
    with open(args.metrics) as f:
        expo_problems = validate_exposition(f.read())

    cats = sorted({s.cat for s in spans})
    print(f"obs_dump: {len(spans)} spans ({', '.join(cats)}) "
          f"-> {args.trace}")
    print(f"obs_dump: {len(text.strip().splitlines())} exposition lines "
          f"-> {args.metrics}")
    problems = [f"trace: {p}" for p in trace_problems] \
        + [f"exposition: {p}" for p in expo_problems]
    if problems:
        for p in problems:
            print(f"obs_dump: INVALID {p}", file=sys.stderr)
        return 1
    print("obs_dump: round-trip valid")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
