"""Offline batches: whole batches of prompts, back to back (closed loop).

Mix keys: ``batch`` (requests per batch), ``prompt_lens`` (batch ``i``
has prompts of ``prompt_lens[i % len]`` tokens), ``new_tokens`` (greedy
tokens per request).  Only token ids come from the seed, so every seed
does the same work.  The window closes at the end of the last batch that
started within ``seconds``, so no batch is cut.
"""
from __future__ import annotations

import time

import numpy as np

from bench.harness.record import Record


def prompts(seed: int, index: int, batch: int, length: int,
            vocab: int) -> np.ndarray:
    """Token ids of batch ``index``: (batch, length) int32."""
    rng = np.random.default_rng([int(seed), 1, index])
    return rng.integers(0, vocab, size=(batch, length), dtype=np.int32)


def plan(mix: dict, n_batches: int) -> list[tuple[int, int]]:
    """(batch index, prompt length) of the first ``n_batches`` batches."""
    lens = mix["prompt_lens"]
    return [(i, lens[i % len(lens)]) for i in range(n_batches)]


def drive(entry, mix: dict, seed: int, seconds: float, annotate) -> Record:
    batch, new = mix["batch"], mix["new_tokens"]
    rec = Record()
    clock = time.perf_counter
    t0 = clock()
    i = 0
    while clock() - t0 < seconds:
        _, length = plan(mix, i + 1)[i]
        ids = prompts(seed, i, batch, length, entry.vocab)
        start = clock() - t0
        with annotate("bench.generate"):
            tokens = entry.generate(ids, new)
        rec.batches.append({"index": i, "prompt": length, "start": start,
                            "end": clock() - t0, "tokens": tokens})
        i += 1
    rec.window_s = clock() - t0
    rec.attempted = rec.completed = rec.optimized = batch * i
    rec.prompt_tokens = batch * sum(b["prompt"] for b in rec.batches)
    rec.new_tokens = batch * new * i
    return rec
