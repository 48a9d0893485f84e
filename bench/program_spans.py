#!/usr/bin/env python3
"""The program's own spans in traced runs of a cell: what the host was
doing while the device waited.

    python3 bench/program_spans.py --workload <name> --seeds 1,2 \
        --seconds <s>

For each seed, one traced run as ``bench/run.py --trace 1`` makes it,
whose reduced trace also keeps the program's ``repro.*`` spans
(``bench.harness.spans``), and one JSON line: the cell's per-layer
metrics with the span metrics of its entry (``submit_host_ms.plan``,
``decode_host_ms.model``), the idle gaps named by the innermost harness
or program span, the count, summed and longest duration of each program
span name, and the longest program spans with their args.  Python's
garbage collections of generations 1 and 2 are traced too, as
``bench.gc`` spans, so a gap they cover is named by them.  Runs on the
chip; exits 2 without one.  The benchmark's own runs never call it.
"""
import collections
import gc
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

#: The span metrics each entry's spans feed.
SPAN_METRICS = {"plan_engine": ["submit_host_ms.plan"],
                "model_engine": ["decode_host_ms.model"]}


def reduce_dir(trace_dir: str, chips: int):
    """``xplane.reduce_dir`` with the program's spans attached."""
    from jax.profiler import ProfileData

    from bench.harness import spans, xplane
    planes = list(ProfileData.from_file(xplane.trace_file(trace_dir)).planes)
    return spans.attach(xplane.reduce(planes, chips), planes)


def trace_gc():
    """Each collection of generation 1 or 2 as a ``bench.gc`` host span
    (a collection starts and stops on one thread); returns the callback
    it installed in ``gc.callbacks``."""
    from jax.profiler import TraceAnnotation
    open_spans = {}

    def on_gc(phase, info):
        if info["generation"] < 1:
            return
        key = threading.get_ident()
        if phase == "start":
            ann = TraceAnnotation("bench.gc", generation=info["generation"])
            ann.__enter__()
            open_spans[key] = ann
        elif key in open_spans:
            open_spans.pop(key).__exit__(None, None, None)

    gc.callbacks.append(on_gc)
    return on_gc


def summary(seed: int, result: dict, kept) -> dict:
    names: dict = collections.defaultdict(lambda: [0, 0.0, 0.0])
    for sp in kept:
        dur = sp.end_s - sp.start_s
        agg = names[sp.name]
        agg[0] += 1
        agg[1] += dur
        agg[2] = max(agg[2], dur)
    longest = sorted(kept, key=lambda sp: sp.start_s - sp.end_s)[:12]
    return {"seed": seed, "correct": result["correct"],
            "attempted": result["attempted"],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "busy_s": result["device"]["busy_s"],
            "window_s": result["device"]["window_s"],
            "idle_gaps": result["breakdown"]["idle_gaps"],
            "spans": dict(sorted(names.items())),
            "longest": [[sp.name, sp.start_s, sp.end_s - sp.start_s,
                         sp.args] for sp in longest]}


def main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/program_spans.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench.harness import core, xplane
    c = core.cell(args.workload)
    c["per_layer"] = c["per_layer"] + [
        {"name": n, "unit": "ms"} for n in SPAN_METRICS[c["config"]["entry"]]]
    core.prepare_environment()
    try:
        device = core.device_info(c["workload"]["chips"])
    except SystemExit as e:
        core.log(str(e))
        return 2
    core.enable_compile_cache()
    trace_gc()
    # the harness reduces the trace through this attribute after the
    # window, then deletes the trace
    xplane.reduce_dir = reduce_dir
    for seed in [int(s) for s in args.seeds.split(",") if s]:
        run = core.Run(c, seed, args.seconds, True, device)
        result = core.measure(run, time.perf_counter())
        print(json.dumps(summary(seed, result, run.trace.spans)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
