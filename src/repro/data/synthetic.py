"""Deterministic synthetic LM data pipeline.

Order-1 Markov token stream with a fixed transition structure: learnable
(loss drops well below the uniform entropy) and fully reproducible per
(seed, host, step), so elastic restarts re-produce the identical stream —
the property the checkpoint-restart tests rely on.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab: int
    seq_len: int
    global_batch: int
    seed: int = 0
    n_hosts: int = 1
    host_id: int = 0

    @property
    def host_batch(self) -> int:
        assert self.global_batch % self.n_hosts == 0
        return self.global_batch // self.n_hosts


#: Share of each transition that follows the token's sparse successors; the
#: rest is spread uniformly over the vocabulary.
_SPARSE_MASS = 0.9


def _transition(vocab: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Row-stochastic (prev token) -> token transition, stored sparsely:
    each token's ``k`` successors and the cumulative share of
    ``_SPARSE_MASS`` each takes.  A dense (vocab, vocab) table would not fit
    a host at a real model's vocabulary (151936^2 x 8 bytes = 185 GB)."""
    rng = np.random.default_rng(seed + 1234)
    k = min(8, vocab)
    succ = rng.integers(0, vocab, size=(vocab, k), dtype=np.int32)
    cum = np.cumsum(rng.dirichlet(np.ones(k), size=vocab), axis=1)
    return succ, cum * _SPARSE_MASS


class SyntheticLM:
    def __init__(self, cfg: DataConfig):
        self.cfg = cfg
        self._succ, self._cum = _transition(cfg.vocab, cfg.seed)

    def batch(self, step: int) -> tuple[np.ndarray, np.ndarray]:
        """(tokens, labels) of shape (host_batch, seq_len) int32."""
        cfg = self.cfg
        rng = np.random.default_rng(
            (cfg.seed * 1_000_003 + step) * 4096 + cfg.host_id)
        b, s = cfg.host_batch, cfg.seq_len
        toks = np.empty((b, s + 1), np.int32)
        toks[:, 0] = rng.integers(0, cfg.vocab, size=b)
        u = rng.random((b, s))
        uniform = ((u - _SPARSE_MASS) / (1 - _SPARSE_MASS)
                   * cfg.vocab).astype(np.int32).clip(0, cfg.vocab - 1)
        for t in range(s):
            prev = toks[:, t]
            j = (self._cum[prev] > u[:, t:t + 1]).argmax(axis=1)
            toks[:, t + 1] = np.where(u[:, t] < _SPARSE_MASS,
                                      self._succ[prev, j], uniform[:, t])
        return toks[:, :-1].copy(), toks[:, 1:].copy()
