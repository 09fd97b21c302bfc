"""Token batches of the benchmark, made from the run's seed.

Partition ``j`` of step ``t`` is a pure function of (seed, t, j): a Zipf
unigram draw with a 30 % chance of repeating the previous token, so that a
model's loss falls as it trains.  The recipe is copied from the program's
``repro.data.pipeline.SyntheticData``; the benchmark keeps its own copy so
that the program under test receives only generated inputs.
"""

from __future__ import annotations

import numpy as np


class TokenBatches:
    """``batch(step)`` -> {"tokens", "labels"}: int32 (k, mb, seq)."""

    def __init__(self, *, vocab: int, k: int, mb: int, seq: int, seed: int):
        self.vocab, self.k, self.mb, self.seq, self.seed = vocab, k, mb, seq, seed

    def partition(self, step: int, j: int) -> np.ndarray:
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, step, j, 0xBE7C]))
        toks = np.minimum(rng.zipf(1.3, (self.mb, self.seq)), self.vocab - 1)
        rep = rng.uniform(size=(self.mb, self.seq)) < 0.3
        toks[:, 1:] = np.where(rep[:, 1:], toks[:, :-1], toks[:, 1:])
        return toks.astype(np.int32)

    def batch(self, step: int) -> dict[str, np.ndarray]:
        toks = np.stack([self.partition(step, j) for j in range(self.k)])
        return {"tokens": toks, "labels": toks.copy()}
