#!/usr/bin/env python3
"""The knee of an open-loop cell: one set-up, then the cell's own
generator at each offered rate in turn.

    python3 bench/sweep.py --workload <name> --seed <n> --seconds <s> \
        --rates 500,1000,2000

For each rate one JSON line on stdout: the rate offered, requests
completed per second, p50 and p95 latency (from the due time, as the
cell measures it), how much later the last quarter's median latency is
than the first quarter's, the backlog (requests not yet answered when
the last request was due: about rate x latency when the server keeps
up, a growing share of the offered ones when it does not), and the
generator's lateness.  The knee is the highest rate whose completions
keep up with no growing backlog; a cell's mix fixes its rate from it.
Rates go in the order given, and the sweep stops after the first that
the server does not keep up with (fewer than 95% of the offered
requests per second completed, or a last quarter twice as late as the
first): past the knee the queue, and the answers it holds on the device,
only grow.  Runs on the chip; exits 2 without
one.  The benchmark's own runs never sweep.
"""
import json
import os
import sys
import time

T_START = time.perf_counter()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def summary(rate: float, seconds: float, seed: int, rec) -> dict:
    import numpy as np

    from bench.harness.stats import percentile
    from bench.traffic.open_poisson import arrivals
    due = arrivals(rate, seconds, seed)
    lat = np.asarray(rec.latencies, np.float64)
    q = max(len(lat) // 4, 1)
    done = due[:len(lat)] + lat
    return {"rate_per_s": rate, "attempted": rec.attempted,
            "completed": rec.completed, "failed": rec.failed,
            "completed_per_s": rec.completed / rec.window_s,
            "p50_ms": percentile(lat, 50) * 1e3,
            "p95_ms": percentile(lat, 95) * 1e3,
            "growth": float(np.median(lat[-q:]) / np.median(lat[:q])),
            "backlog": int((done > due[-1]).sum()),
            "lateness_p95_ms": percentile(rec.lateness, 95) * 1e3}


def main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/sweep.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from bench.harness import core
    c = core.cell(args.workload)
    core.prepare_environment()
    try:
        device = core.device_info(c["workload"]["chips"])
    except SystemExit as e:
        core.log(str(e))
        return 2
    core.enable_compile_cache()
    run = core.Run(c, args.seed, args.seconds, False, device)
    entry = core.entry_module(run.config).Entry(run.config, run.mix,
                                                run.seed, run)
    gen = core.traffic_module(run.mix)
    entry.setup()
    core.log(f"setup {time.perf_counter() - T_START:.3f}s")
    try:
        for rate in (float(r) for r in args.rates.split(",") if r):
            mix = dict(run.mix, rate_per_s=rate)
            rec = gen.drive(entry, mix, args.seed, args.seconds, run.annotate)
            s = summary(rate, args.seconds, args.seed, rec)
            print(json.dumps(s), flush=True)
            if s["completed_per_s"] < 0.95 * rate or s["growth"] > 2.0:
                break
    finally:
        entry.close()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
