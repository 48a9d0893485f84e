"""decode_host_ms.model: the host time per token of the decode loop, in
which the device waits for the next decode step: the mean over the
window's ``repro.decode/token`` spans of their duration less the time
under their ``repro.decode/sync`` children (waiting for the sampled
token), in ms (profiler trace, host plane; the program's spans as
``bench.harness.spans`` keeps them).  Read only when there is one token
span for each token of each batch the window served."""
from bench.harness import spans
from bench.harness.core import log


def read(run):
    kept = getattr(run.trace, "spans", None)
    if kept is None:
        return None
    host = spans.self_seconds(kept, "repro.decode/token",
                              "repro.decode/sync")
    want = len(run.record.batches) * run.mix["new_tokens"]
    if not host or len(host) != want:
        log(f"decode_host_ms.model: {len(host)} token spans in the window, "
            f"{want} tokens served; not read")
        return None
    return 1e3 * sum(host) / len(host)
