"""The benchmark's own token generator: a seeded sparse markov teacher.

Each token has ``successors`` likely followers; with probability
``noise`` a position is drawn uniformly instead.  Every row of a pool is
drawn at once, one position at a time, so a pool of thousands of rows
costs one short numpy loop over the sequence.  The same seed gives the
same pool; ``stream`` separates pools drawn from one seed (the check's
rows and the window's rows never coincide).
"""
from __future__ import annotations

import numpy as np

MASK64 = (1 << 64) - 1


def _rng(seed: int, *words: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & MASK64, *words])


def markov_pool(seed: int, stream: int, rows: int, seq: int, vocab: int,
                successors: int = 4, noise: float = 0.1) -> np.ndarray:
    """``(rows, seq)`` int32 tokens in ``[0, vocab)``."""
    succ = _rng(seed, 7).integers(0, vocab, size=(vocab, successors))
    rng = _rng(seed, 11, stream)
    toks = np.empty((rows, seq), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=rows)
    choice = rng.integers(0, successors, size=(rows, seq))
    noisy = rng.random(size=(rows, seq)) < noise
    rand = rng.integers(0, vocab, size=(rows, seq))
    for t in range(1, seq):
        toks[:, t] = np.where(noisy[:, t], rand[:, t],
                              succ[toks[:, t - 1], choice[:, t]])
    return toks


def batches(seed: int, stream: int, count: int, batch: int, seq: int,
            vocab: int, **kw) -> np.ndarray:
    """``(count, batch, seq)``: ``count`` batches of distinct rows."""
    return markov_pool(seed, stream, count * batch, seq, vocab,
                       **kw).reshape(count, batch, seq)
