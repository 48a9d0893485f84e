"""Closed loop: one client sends a request, waits until its answer is
ready, and sends the next, until ``seconds`` have passed.

Mix keys: ``clients`` (1: the only kind this generator drives).  Request
``i`` carries the entry's input ``i`` (the entry cycles its seeded input
sets), so every seed does the same work.  Latency is from send to ready.
"""
from __future__ import annotations

import time

from bench.harness.core import log
from bench.harness.record import Record


def drive(entry, mix: dict, seed: int, seconds: float, annotate) -> Record:
    if mix.get("clients", 1) != 1:
        raise ValueError("closed_loop drives one client")
    rec = Record()
    clock = time.perf_counter
    t0 = clock()
    i = 0
    while clock() - t0 < seconds:
        sent = clock()
        with annotate("bench.request"):
            try:
                entry.request(i)
            except Exception as exc:          # counted, never fatal
                rec.failed += 1
                rec.latencies.append(float("inf"))
                log(f"request {i} failed: {exc!r}")
            else:
                rec.completed += 1
                rec.latencies.append(clock() - sent)
        i += 1
    rec.window_s = clock() - t0
    rec.attempted = i
    return rec
