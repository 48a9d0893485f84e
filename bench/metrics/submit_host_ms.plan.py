"""submit_host_ms.plan: the host time the serving front adds per request:
the mean over the window's ``repro.request/submit`` spans of their
duration less the time under their ``repro.request/sync`` children (the
device sync of a timed run), in ms (profiler trace, host plane; the
program's spans as ``bench.harness.spans`` keeps them).  Read only when
there is one submit span for each request the window attempted."""
from bench.harness import spans
from bench.harness.core import log


def read(run):
    kept = getattr(run.trace, "spans", None)
    if kept is None:
        return None
    host = spans.self_seconds(kept, "repro.request/submit",
                              "repro.request/sync")
    if not host or len(host) != run.record.attempted:
        log(f"submit_host_ms.plan: {len(host)} submit spans in the window, "
            f"{run.record.attempted} requests attempted; not read")
        return None
    return 1e3 * sum(host) / len(host)
