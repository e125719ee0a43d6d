"""Deterministic synthetic token rows for next-token workloads.

Recipe: every token of the vocabulary has a few candidate successors,
drawn once from the seed, and a row is a walk over that table: the next
token is the current token's candidate ``j`` with the fixed, skewed
probability ``weights[j]``. A model can learn the table (the best
achievable loss is the entropy of ``weights``), members that learn it
faster score higher, and nothing of size vocabulary-squared is ever
built. Numpy's Philox counter RNG from a fixed seed, as the image
stand-ins use (data/synthetic.py): stable across processes and
platforms, no files.
"""

from __future__ import annotations

import numpy as np


def make_token_rows(
    n_train: int,
    n_val: int,
    positions: int,
    vocab: int,
    seed: int = 0,
    weights: tuple = (0.6, 0.25, 0.1, 0.05),
) -> dict:
    """Rows of ``positions + 1`` contiguous tokens, ids in ``[0, vocab)``;
    ``x`` is a row without its last token and ``y`` without its first
    (the next token of every position). int32 throughout."""
    rng = np.random.Generator(np.random.Philox(seed))
    successors = rng.integers(0, vocab, size=(vocab, len(weights)), dtype=np.int64)
    cum = np.cumsum(np.asarray(weights, np.float64))

    def split(n, salt):
        r = np.random.Generator(np.random.Philox([seed, salt]))
        rows = np.empty((n, positions + 1), np.int32)
        rows[:, 0] = r.integers(0, vocab, size=n)
        choice = np.minimum((r.random((n, positions))[..., None] > cum).sum(axis=-1), len(weights) - 1)
        for p in range(positions):
            rows[:, p + 1] = successors[rows[:, p], choice[:, p]]
        return rows[:, :-1], rows[:, 1:]

    train_x, train_y = split(n_train, 1)
    val_x, val_y = split(n_val, 2)
    return {
        "train_x": train_x,
        "train_y": train_y,
        "val_x": val_x,
        "val_y": val_y,
        "n_classes": vocab,
    }
