"""The benchmark's one traffic generator: seeded synthetic LM batches.

A copy of the training data stream the program ships with, so that the
inputs a cell is measured on cannot change with the program.  Each worker's
rows come from a worker-specific token marginal (``heterogeneity`` > 0
skews each worker's slice of the vocabulary), so the per-worker gradients
differ: the regime in which EF-BV's control variates matter.  Row t + 1 of
a sequence is ``(3 * t + noise + offset) mod V``.

Every call to :meth:`SyntheticLM.batch` draws fresh rows from
``(seed, step)``: no two steps of a run see the same rows.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np


@dataclasses.dataclass
class SyntheticLM:
    vocab: int
    seq_len: int
    global_batch: int
    n_workers: int = 1
    seed: int = 0
    heterogeneity: float = 0.5  # 0 = iid workers, 1 = disjoint vocab slices

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        self._offsets = rng.integers(0, self.vocab, size=self.n_workers)

    def _gen_rows(self, rng, w: int, count: int) -> np.ndarray:
        """``count`` bigram-structured sequences from worker w's marginal."""
        S, V = self.seq_len, self.vocab
        span = max(int(V * (1.0 - self.heterogeneity)), V // 16)
        base = rng.integers(0, span, size=(count, 1))
        start = (base + self._offsets[w]) % V
        noise = rng.integers(0, 7, size=(count, S))
        seqs = np.zeros((count, S), np.int64)
        seqs[:, 0] = start[:, 0]
        for t in range(1, S):
            seqs[:, t] = (seqs[:, t - 1] * 3 + noise[:, t] + self._offsets[w]) % V
        return seqs

    def batch(self, step: int) -> Dict[str, np.ndarray]:
        """Global batch of one step: worker-major rows of tokens, and the
        next-token labels (-1 on the last position: no loss there)."""
        per_w = self.global_batch // self.n_workers
        rng = np.random.default_rng((self.seed, step))
        rows = [self._gen_rows(rng, w, per_w) for w in range(self.n_workers)]
        tokens = np.concatenate(rows, 0).astype(np.int32)
        labels = np.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1).astype(np.int32)
        labels[:, -1] = -1
        return {"tokens": tokens, "labels": labels}
