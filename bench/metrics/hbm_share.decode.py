"""hbm_share.decode: HBM bytes the decode steps of the window need (every
weight once per step, keys and values at the positions each row attends
to, the new position written; ``bench.harness.work``) over the device
time of the decode program's executions in the profiler trace, as a share
of the chip's HBM bandwidth, in %.  Every decode step the program runs is
counted, the one after each request's last token included."""
from bench.harness import work
from bench.harness.core import log

PROGRAM = "decode_step"


def read(run):
    t, rec = run.trace, run.record
    if t is None or not rec.batches:
        return None
    n, secs = t.module_seconds(lambda m: PROGRAM in m)
    new, rows = run.mix["new_tokens"], run.mix["batch"]
    if n != len(rec.batches) * new or secs <= 0:
        log(f"hbm_share.decode: {n} executions of {PROGRAM} in the trace, "
            f"{len(rec.batches) * new} expected; not read")
        return None
    nbytes = sum(work.decode_step_bytes(run.config,
                                        [b["prompt"] + k] * rows)
                 for b in rec.batches for k in range(1, new + 1))
    return 100.0 * nbytes / secs / run.peaks["hbm_bytes_per_s"]
