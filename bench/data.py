"""Token rows of the benchmark's traffic, made from the run's seed.

The generator is the seeded bigram walk of the repository's synthetic text
corpus, kept here so that the traffic a cell measures cannot change with the
program: row ``i`` of a table is a pure function of ``(seed, i)``. The table
is built once, in set-up, and served by row index, as a tokenized corpus on
disk would be.
"""
from __future__ import annotations

import numpy as np

BRANCHES = 4          # successors per token in the bigram table
NOISE = 0.05          # share of tokens drawn uniformly instead


def make_rows(n: int, seq_len: int, vocab: int, seed: int) -> np.ndarray:
    """``[n, seq_len + 1]`` int32 token rows; row ``i`` depends on
    ``(seed, i)`` alone."""
    succ = np.random.default_rng(seed).integers(
        0, vocab, size=(vocab, BRANCHES), dtype=np.int64)
    toks = np.empty((n, seq_len + 1), dtype=np.int32)
    branch = np.empty((n, seq_len), dtype=np.int64)
    noise = np.empty((n, seq_len), dtype=bool)
    rand = np.empty((n, seq_len), dtype=np.int64)
    for i in range(n):
        rng = np.random.default_rng((seed, i))
        toks[i, 0] = rng.integers(0, vocab)
        branch[i] = rng.integers(0, BRANCHES, size=seq_len)
        noise[i] = rng.random(seq_len) < NOISE
        rand[i] = rng.integers(0, vocab, size=seq_len)
    for t in range(seq_len):
        nxt = succ[toks[:, t], branch[:, t]]
        toks[:, t + 1] = np.where(noise[:, t], rand[:, t], nxt)
    return toks


class TokenRows:
    """A fixed table of token rows, served by index: the dataset the
    training loop reads (``len``, ``batch(idx)``)."""

    def __init__(self, n: int, seq_len: int, vocab: int, seed: int):
        self.rows = make_rows(n, seq_len, vocab, seed)
        self._index = {r.tobytes(): i for i, r in enumerate(self.rows)}

    def __len__(self) -> int:
        return len(self.rows)

    def batch(self, idx) -> dict:
        r = self.rows[np.asarray(idx, dtype=np.int64)]
        return {"tokens": r[:, :-1], "labels": r[:, 1:]}

    def row_ids(self, tokens: np.ndarray, labels: np.ndarray) -> np.ndarray:
        """Index of each fed row (``[..., seq_len]`` tokens and labels) in
        the table, or -1 where a row is not one of the table's."""
        t = np.asarray(tokens).reshape(-1, tokens.shape[-1])
        lab = np.asarray(labels).reshape(-1, labels.shape[-1])
        out = np.full(len(t), -1, dtype=np.int64)
        for j, (a, b) in enumerate(zip(t, lab)):
            full = np.concatenate([a, b[-1:]]).astype(np.int32)
            i = self._index.get(full.tobytes(), -1)
            if i >= 0 and np.array_equal(self.rows[i, 1:], b):
                out[j] = i
        return out
