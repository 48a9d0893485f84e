"""Training steps, back to back (closed loop).

Mix keys: ``global_batch`` (sequences per step) and ``seq_len`` (tokens
per sequence).  Step ``i`` trains on whole packed rows of ids drawn from
the seed over the configuration's vocabulary (``batch``): no padding, the
labels the ids shifted by one.  Only ids come from the seed, so every
seed does the same work.  The window closes at the end of the last step
that started within ``seconds``.  ``new_tokens`` counts the tokens of the
steps completed; a step whose loss is not finite is ``failed``.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench.harness.record import Record


def batch(seed: int, index: int, rows: int, length: int,
          vocab: int) -> tuple[np.ndarray, np.ndarray]:
    """(tokens, labels) of step ``index``: (rows, length) int32 each."""
    rng = np.random.default_rng([int(seed), 5, index])
    ids = rng.integers(0, vocab, size=(rows, length + 1), dtype=np.int32)
    return ids[:, :-1], ids[:, 1:]


def drive(entry, mix: dict, seed: int, seconds: float, annotate) -> Record:
    rows, length = mix["global_batch"], mix["seq_len"]
    rec = Record()
    clock = time.perf_counter
    t0 = clock()
    i = 0
    while clock() - t0 < seconds:
        tokens, labels = batch(seed, i, rows, length, entry.vocab)
        sent = clock()
        with annotate("bench.step"):
            loss = entry.step(tokens, labels)
        rec.latencies.append(clock() - sent)
        if math.isfinite(loss):
            rec.completed += 1
            rec.new_tokens += rows * length
        else:
            rec.failed += 1
        i += 1
    rec.window_s = clock() - t0
    rec.attempted = i
    rec.optimized = rec.completed
    return rec
