"""Seeded samples of a run's answers, for the check."""
from __future__ import annotations

import numpy as np


class Reservoir:
    """A uniform sample of ``k`` items from a stream of unknown length,
    drawn from the seed (Vitter's algorithm R)."""

    def __init__(self, seed: int, k: int):
        self.k = k
        self.rng = np.random.default_rng([int(seed), 3])
        self.items: dict = {}
        self._slots: list = []
        self.seen = 0

    def offer(self, key, value) -> None:
        i = self.seen
        self.seen += 1
        if i < self.k:
            self._slots.append(key)
            self.items[key] = value
            return
        j = int(self.rng.integers(0, i + 1))
        if j < self.k:
            del self.items[self._slots[j]]
            self._slots[j] = key
            self.items[key] = value
