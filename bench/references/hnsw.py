"""Plain reference of approximate Tanimoto top-k (engine ``hnsw``).

An approximate answer is not one fixed list, so the reference gives what
the check holds it to (``check.grade``): the exact f32 score of any row it
returned, and the exact top-k over the visible rows, whose k-th best score
decides which returned rows are true neighbours. The top-k is brute force,
as ``brute.py`` computes it: score descending, then global id ascending,
padded with id -1 / score 0 where fewer than k rows are visible.
"""
from __future__ import annotations

import numpy as np

from bench import oracle


class Reference:
    def __init__(self, rows: np.ndarray, cfg: dict, precision: str = "f32"):
        """``rows``: every row in global-id order (library, then inserts)."""
        if cfg.get("metric", "tanimoto") != "tanimoto":
            raise ValueError("the reference scores Tanimoto only")
        self.rows = rows
        self.k = int(cfg["k"])
        self.precision = precision
        self.counts = oracle.popcounts(rows)
        self.gids = np.arange(len(rows), dtype=np.int64)

    def answer(self, q: np.ndarray, visible: int):
        """Exact top-k of query ``q`` over rows ``[0, visible)``."""
        inter = oracle.intersections(q, self.rows[:visible])
        s = oracle.tanimoto(inter, oracle.popcounts(q[None])[0],
                            self.counts[:visible], self.precision)
        best = oracle.first_k(s, (self.gids[:visible],), self.k)
        return oracle.pad_answer(best, s[best], self.k)

    def score(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """The scores of query ``q`` against rows ``ids``."""
        ids = np.asarray(ids, np.int64)
        inter = oracle.intersections(q, self.rows[ids])
        return oracle.tanimoto(inter, oracle.popcounts(q[None])[0],
                               self.counts[ids], self.precision)
