"""The token rows a training run consumes, made again from the seed.

A copy of the arithmetic of the program's synthetic corpus (a seeded
affine Markov chain over the first ``vocab_cap`` ids with one token in ten
drawn at random), written out here so that the reference never takes a
batch from the program: host step ``s`` of a run reads rows
``0 .. global_batch - 1`` of stream step ``s``.
"""
from __future__ import annotations

import numpy as np

VOCAB_CAP = 32768


class TokenRows:
    def __init__(self, seed: int, vocab_size: int):
        self.seed = seed
        self.vocab = min(vocab_size, VOCAB_CAP)
        rng = np.random.RandomState(seed)
        self.a = int(rng.randint(1, self.vocab // 2) * 2 + 1)
        self.c = int(rng.randint(1, self.vocab))

    def row(self, step: int, row: int, n: int) -> np.ndarray:
        rng = np.random.RandomState(
            (self.seed * 1_000_003 + step * 8191 + row) % (2 ** 31 - 1))
        start = rng.randint(self.vocab)
        noise = rng.randint(0, self.vocab, size=n)
        out = np.empty(n, np.int64)
        t = start
        for i in range(n):
            t = (self.a * t + self.c) % self.vocab
            out[i] = t if noise[i] % 10 else noise[i]
        return out

    def batch(self, step: int, global_batch: int, seq_len: int):
        """(tokens, labels) int32 arrays of shape (global_batch, seq_len)."""
        rows = np.stack([self.row(step, b, seq_len + 1)
                         for b in range(global_batch)])
        return rows[:, :-1].astype(np.int32), rows[:, 1:].astype(np.int32)
