"""Open loop with Poisson arrivals at a fixed rate.

Mix keys: ``rate_per_s``.  The arrival gaps are the same set for every
seed (the exponential distribution's quantiles at ``(k + 0.5) / n`` for
``n = rate * seconds`` requests) and the seed orders them, so every seed
offers the same number of requests over the same span, in its own
pattern.  The sender sleeps until each request is due and records how
late it was; a second thread waits for the answers in order.  Latency is
from the due time to the answer being ready, so a stall counts against
every request behind it.  Answers not ready a minute after the window
closes count as failed.
"""
from __future__ import annotations

import queue
import threading
import time

import numpy as np

from bench.harness.core import log
from bench.harness.record import Record

GRACE_S = 60.0


def arrivals(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of every request."""
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    np.random.default_rng([int(seed), 2]).shuffle(gaps)
    return np.cumsum(gaps)


def drive(entry, mix: dict, seed: int, seconds: float, annotate) -> Record:
    due = arrivals(mix["rate_per_s"], seconds, seed)
    rec = Record(attempted=len(due))
    clock = time.perf_counter
    pending: queue.Queue = queue.Queue()
    done_at = [0.0]

    def collect():
        while True:
            item = pending.get()
            if item is None:
                return
            i, t_due, fut = item
            if fut is None:
                rec.failed += 1
                rec.latencies.append(float("inf"))
                continue
            left = max(t0 + seconds + GRACE_S - clock(), 0.001)
            try:
                with annotate("bench.wait"):
                    entry.wait(i, fut, timeout=left)
            except Exception as exc:          # late past grace, or failed
                rec.failed += 1
                rec.latencies.append(float("inf"))
                log(f"request {i} failed: {exc!r}")
                continue
            now = clock()
            rec.completed += 1
            rec.latencies.append(now - t_due)
            done_at[0] = now

    waiter = threading.Thread(target=collect, name="bench-collect",
                              daemon=True)
    t0 = clock()
    waiter.start()
    for i, d in enumerate(due):
        t_due = t0 + float(d)
        wait = t_due - clock()
        if wait > 0:
            time.sleep(wait)
        sent = clock()
        rec.lateness.append(max(sent - t_due, 0.0))
        with annotate("bench.submit"):
            try:
                fut = entry.send(i)
            except Exception as exc:          # refused at admission
                fut = None
                log(f"request {i} refused: {exc!r}")
        pending.put((i, t_due, fut))
    pending.put(None)
    waiter.join(seconds + GRACE_S + 5.0)
    if waiter.is_alive():
        raise RuntimeError("answers still outstanding past the grace time")
    rec.window_s = max(done_at[0], clock() if not rec.completed else 0) - t0
    return rec
