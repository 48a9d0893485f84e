"""The knee sweep's summary of one rate, on a made-up record."""
import pytest

from bench.harness.record import Record
from bench.sweep import summary
from bench.traffic.open_poisson import arrivals


def test_summary_reads_backlog_and_growth():
    rate, seconds, seed = 100.0, 1.0, 7
    due = arrivals(rate, seconds, seed)
    n = len(due)
    # every request answered 1 ms after it was due, except the last
    # quarter, which waits until 50 ms past the last arrival
    lat = [0.001] * (n - n // 4) + [due[-1] + 0.05 - d
                                    for d in due[n - n // 4:]]
    rec = Record(window_s=1.0, attempted=n, completed=n, latencies=lat,
                 lateness=[0.0] * n)
    s = summary(rate, seconds, seed, rec)
    assert s["attempted"] == s["completed"] == n == 100
    assert s["backlog"] >= n // 4
    assert s["growth"] > 10
    assert s["p50_ms"] == pytest.approx(1.0)
    assert s["lateness_p95_ms"] == 0.0


def test_summary_of_a_server_that_keeps_up():
    rate, seconds, seed = 100.0, 1.0, 8
    n = len(arrivals(rate, seconds, seed))
    rec = Record(window_s=1.0, attempted=n, completed=n,
                 latencies=[0.002] * n, lateness=[1e-4] * n)
    s = summary(rate, seconds, seed, rec)
    # in flight at the last arrival: about rate x latency, and the last
    assert s["backlog"] <= 3 and s["growth"] == pytest.approx(1.0)
    assert s["completed_per_s"] == pytest.approx(100.0)
