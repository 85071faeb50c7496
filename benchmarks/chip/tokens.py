"""Seeded training batches: the zipf-plus-planted-bigram token stream.

A copy of the program's ``data/synthetic.py`` ``LMStream`` semantics, kept
here so that the benchmark owns its traffic. Step ``s`` of seed ``seed``
draws from ``numpy.random.default_rng((seed, s))`` exactly as ``LMStream``
does (same uniforms in the same order, same successor table), but draws
them in one call per step, so a 4096-token row costs milliseconds.
"""
from __future__ import annotations

import numpy as np


class TokenStream:
    def __init__(self, vocab: int, seed: int, *, alpha: float, p_bigram: float):
        self.vocab, self.seed, self.p_bigram = vocab, seed, p_bigram
        rng = np.random.default_rng(seed)
        self.succ = rng.integers(0, vocab, size=(vocab,), dtype=np.int32)
        w = np.arange(1, vocab + 1, dtype=np.float64) ** (-alpha)
        cdf = (w / w.sum()).cumsum()
        self.cdf = cdf / cdf[-1]

    def batch(self, step: int, batch: int, seq: int) -> dict:
        """``{"tokens", "labels"}`` int32 [batch, seq] of step ``step``."""
        rng = np.random.default_rng((self.seed, step))
        # LMStream's order: batch uniforms for the first tokens, then per
        # position batch uniforms for "follow?" and batch for the fresh draw
        u = rng.random(batch * (1 + 2 * seq))
        first, rest = u[:batch], u[batch:].reshape(seq, 2, batch)
        fresh = self.cdf.searchsorted(rest[:, 1], side="right").astype(np.int32)
        follow = rest[:, 0] < self.p_bigram
        toks = np.empty((batch, seq + 1), np.int32)
        toks[:, 0] = self.cdf.searchsorted(first, side="right")
        succ = self.succ
        for t in range(seq):
            toks[:, t + 1] = np.where(follow[t], succ[toks[:, t]], fresh[t])
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
