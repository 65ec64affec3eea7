"""Plain reference of exact Tanimoto top-k (engine ``brute``).

Every visible row is scored; the answer is the k best by score
descending, then global id ascending, padded with id -1 / score 0 where
fewer than k rows are visible.
"""
from __future__ import annotations

import numpy as np

from bench import oracle


class Reference:
    def __init__(self, rows: np.ndarray, cfg: dict, precision: str = "f32"):
        """``rows``: every row in global-id order (library, then inserts)."""
        self.rows = rows
        self.k = int(cfg["k"])
        self.precision = precision
        self.counts = oracle.popcounts(rows)
        self.gids = np.arange(len(rows), dtype=np.int64)

    def answer(self, q: np.ndarray, visible: int):
        """Top-k of query ``q`` over rows ``[0, visible)``."""
        inter = oracle.intersections(q, self.rows[:visible])
        s = oracle.tanimoto(inter, oracle.popcounts(q[None])[0],
                            self.counts[:visible], self.precision)
        best = oracle.first_k(s, (self.gids[:visible],), self.k)
        return oracle.pad_answer(best, s[best], self.k)
