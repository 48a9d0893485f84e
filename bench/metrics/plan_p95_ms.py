"""plan_p95_ms: 95th percentile of request latency over every request of
the window, in ms (host clock).  Open loop: from the request's due time to
its outputs being ready; closed loop: from send to ready.  A failed
request counts as infinitely late."""
from bench.harness.stats import percentile


def read(run):
    lat = run.record.latencies
    return percentile(lat, 95) * 1e3 if lat else None
