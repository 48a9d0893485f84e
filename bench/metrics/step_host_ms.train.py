"""step_host_ms.train: the host time of a training step in which the
device does not wait for a result: the mean over the window's
``repro.train/step`` spans of their duration less their
``repro.train/sync`` children (waiting for the step's metrics), in ms
(the program's spans, as the entry kept them).  Read only when there is
one step span for each step the window started."""
from bench.harness.core import log


def read(run):
    host = getattr(run.entry, "step_host_s", None)
    if not host:
        return None
    if len(host) != run.record.attempted:
        log(f"step_host_ms.train: {len(host)} step spans in the window, "
            f"{run.record.attempted} steps; not read")
        return None
    return 1e3 * sum(host) / len(host)
