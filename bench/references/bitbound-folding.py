"""Plain reference of two-stage BitBound + folding top-k (engine
``bitbound-folding``; paper §III-B, §IV-A).

For a query of popcount a, over the visible rows in global-id order:

1. Window (Eq. 2): the rows of popcount b whose best possible score,
   min(a, b) / max(a, b) as an f32 quotient, reaches the cutoff as an f32.
2. Stage 1: each window row is scored on folded prints (strided
   modulo-OR, ``fold_m`` sections); the k * m * log2(2m) best are kept, by
   folded score descending, then popcount ascending, then global id
   ascending (the row order of a popcount-sorted store).
3. Stage 2: those candidates are rescored on full prints; the answer is the
   k best by full score descending, then stage-1 rank ascending, padded with
   id -1 / score 0 where fewer than k candidates exist.

No cutoff is applied to the answer itself: the cutoff only bounds the
window.
"""
from __future__ import annotations

import math

import numpy as np

from bench import oracle


def stage1_width(k: int, m: int) -> int:
    """Candidates kept by stage 1: k * m * log2(2m) (k when unfolded)."""
    return k if m <= 1 else int(k * m * math.log2(2 * m))


class Reference:
    def __init__(self, rows: np.ndarray, cfg: dict, precision: str = "f32"):
        """``rows``: every row in global-id order; rows past
        ``cfg["n_molecules"]`` are inserts, made visible by prefix."""
        if cfg.get("metric", "tanimoto") != "tanimoto":
            raise ValueError("the reference scores Tanimoto only")
        self.k = int(cfg["k"])
        self.m = int(cfg["fold_m"])
        self.kr1 = max(stage1_width(self.k, self.m), self.k)
        self.precision = precision
        self.cutoff = float(cfg["cutoff"])
        self.bits = int(cfg["fp_bits"])
        self.n_main = int(cfg["n_molecules"])
        self.rows = rows
        self.counts = oracle.popcounts(rows)
        self.folded = oracle.fold(rows, self.m, int(cfg["fold_scheme"]))
        self.fcounts = oracle.popcounts(self.folded)
        # the library sorted by (popcount, global id): a window is a slice
        order = np.argsort(self.counts[:self.n_main], kind="stable")
        self.main_gid = order.astype(np.int64)
        self.main_cnt = self.counts[:self.n_main][order]
        self.main_folded = self.folded[:self.n_main][order]
        self.main_fcnt = self.fcounts[:self.n_main][order]

    def answer(self, q: np.ndarray, visible: int):
        """Top-k of query ``q`` over rows ``[0, visible)``."""
        a = int(oracle.popcounts(q[None])[0])
        ok = oracle.window_mask(a, self.cutoff, self.bits, self.precision)
        if not ok.any():
            return oracle.pad_answer([], [], self.k)
        b = np.flatnonzero(ok)
        lo = np.searchsorted(self.main_cnt, b[0], side="left")
        hi = np.searchsorted(self.main_cnt, b[-1], side="right")
        ins = np.arange(self.n_main, visible)
        ins = ins[ok[self.counts[ins]]]
        gid = np.concatenate([self.main_gid[lo:hi], ins])
        cnt = np.concatenate([self.main_cnt[lo:hi], self.counts[ins]])
        qf = oracle.fold(q, self.m, 1)
        qf_cnt = int(oracle.popcounts(qf[None])[0])
        inter = np.concatenate([
            oracle.intersections(qf, self.main_folded[lo:hi]),
            oracle.intersections(qf, self.folded[ins])])
        fcnt = np.concatenate([self.main_fcnt[lo:hi], self.fcounts[ins]])
        s1 = oracle.tanimoto(inter, qf_cnt, fcnt, self.precision)
        cand = gid[oracle.first_k(s1, (cnt, gid), self.kr1)]
        s2 = oracle.tanimoto(oracle.intersections(q, self.rows[cand]), a,
                             self.counts[cand], self.precision)
        best = oracle.first_k(s2, (np.arange(len(cand)),), self.k)
        return oracle.pad_answer(cand[best], s2[best], self.k)
