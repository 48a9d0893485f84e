#!/usr/bin/env python3
"""Readings that a cell's limits are set from: the program's and the
control's, seed by seed, in one process.

    python3 bench/control.py --workload <name> --seeds 1,2,3 \
        --seconds <s> [--control-seeds 1,2,3]

For each seed it makes a whole run of the cell (set-up, a window of
``--seconds``, the check) and prints one JSON line with the program's
reading of each number the check compares.  For each control seed it
also puts the reference, computed one precision below what the
configuration states (the cell's limits file names it), in the program's
place, over the same sample, and prints that reading too.  The benchmark's
own runs never run the control.  Runs on the chip; exits 2 without one.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def readings(c: dict, seed: int, seconds: float, control: bool,
             device: dict) -> dict:
    from bench.harness import core
    run = core.Run(c, seed, seconds, False, device)
    result = core.measure(run, time.perf_counter())
    out = {"seed": seed, "setup_s": run.setup_s,
           "attempted": result["attempted"], "failed": result["failed"],
           "correct": result["correct"],
           "program": {k: v["value"] for k, v in result["checked"].items()}}
    if control:
        checks = run.entry.control(run.record)
        out["control"] = {k: v["value"] for k, v in checks.items()}
        # the same rule that decides ``correct`` in a run
        out["control_correct"] = core.judge(checks)
    return out


def main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
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
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds + sorted(controls - set(seeds)):
        print(json.dumps(readings(c, seed, args.seconds, seed in controls,
                                  device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
