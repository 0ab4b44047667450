"""The benchmark's traffic generator, driven by the parameters of a
traffic file.

``bigram_batches``: training rows of a seeded, learnable stream. Each seed
plants one successor table; a token follows it with probability
``bigram_order`` and is uniform otherwise. Batch ``t`` depends only on
(seed, t).
"""
from __future__ import annotations

import numpy as np

_TOKEN_TAG = 0x70CE


def bigram_batches(vocab: int, rows: int, seq: int, seed: int, steps: int,
                   bigram_order: float) -> list:
    """``steps`` batches of {"tokens", "labels"}: (rows, seq) int32."""
    trans = np.random.default_rng((seed, _TOKEN_TAG)).permutation(vocab)
    out = []
    for step in range(steps):
        rng = np.random.default_rng((seed, _TOKEN_TAG, step))
        toks = np.empty((rows, seq + 1), np.int32)
        toks[:, 0] = rng.integers(0, vocab, size=rows)
        follow = rng.random(size=(rows, seq)) < bigram_order
        rand_next = rng.integers(0, vocab, size=(rows, seq))
        for t in range(seq):
            toks[:, t + 1] = np.where(follow[:, t], trans[toks[:, t]], rand_next[:, t])
        out.append({"tokens": toks[:, :-1].copy(), "labels": toks[:, 1:].copy()})
    return out
