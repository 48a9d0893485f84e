"""collective_share.train: device time of the collective operations
(all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute,
and their -start/-done halves) over device busy time, in % (profiler
trace).  The collectives are counted on the first chip, busy time is the
mean over the chips.  A reduce-scatter is compiled as a fusion that calls
one, so the entry names the step's collective operations from its
compiled program (``entry.ops``); names that say so are counted too."""
import re

NAMED = re.compile(r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                   r"collective-permute)")


def read(run):
    t = run.trace
    if t is None or t.busy_s <= 0:
        return None
    ops = getattr(run.entry, "ops", None) or set()
    n, secs = t.op_seconds(lambda mod, op: op in ops
                           or NAMED.match(op) is not None)
    if n == 0:
        return None
    return 100.0 * secs / t.busy_s
