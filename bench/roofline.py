"""Peaks of the chip, and the least bytes each kernel's work needs.

The least bytes come from shapes and windows alone, so the count is the
same whatever implements the kernel: a kernel that reads less than this
would not have read its inputs, and one that reads more (a window re-read
per query, a relayout) shows as a share under 100%.
"""
from __future__ import annotations

import numpy as np

from bench import oracle
from bench.spec import BENCH_DIR, load_json

COUNT_BYTES = 4       # an int32 popcount per row
ID_BYTES = 4          # an int32 id per answer slot
SCORE_BYTES = 4       # an f32 score per answer slot


def peaks(kind: str, path=BENCH_DIR / "peaks.json") -> dict:
    """The published peaks of a device kind; a kind not in the table is
    an error, not a default."""
    table = load_json(path)["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in {path}")
    return table[kind]


def union_rows(lo: np.ndarray, hi: np.ndarray) -> int:
    """Rows covered by the union of the row ranges [lo, hi)."""
    keep = hi > lo
    lo, hi = lo[keep], hi[keep]
    if lo.size == 0:
        return 0
    o = np.argsort(lo, kind="stable")
    lo, hi = lo[o], hi[o]
    reach = np.maximum.accumulate(hi)
    starts = np.concatenate([[lo[0]], np.maximum(lo[1:], reach[:-1])])
    return int(np.maximum(reach - starts, 0).sum())


def window_topk_bytes(sorted_counts: np.ndarray, query_counts: np.ndarray,
                      cfg: dict, k1: int) -> int:
    """Least HBM bytes of stage 1 for one batch: the folded rows of the
    union of the batch's Eq. 2 windows over the popcount-sorted library,
    each read once with its count, plus the folded queries and the
    stage-1 candidates written out."""
    bits = int(cfg["fp_bits"])
    folded_row = bits // 8 // int(cfg["fold_m"])
    lo, hi = [], []
    for a in query_counts:
        ok = np.flatnonzero(oracle.window_mask(int(a), cfg["cutoff"], bits))
        if ok.size == 0:
            lo.append(0)
            hi.append(0)
            continue
        lo.append(np.searchsorted(sorted_counts, ok[0], side="left"))
        hi.append(np.searchsorted(sorted_counts, ok[-1], side="right"))
    rows = union_rows(np.asarray(lo), np.asarray(hi))
    q = len(query_counts)
    return (rows * (folded_row + COUNT_BYTES) + q * folded_row
            + q * k1 * (ID_BYTES + SCORE_BYTES))


def fused_topk_bytes(n_rows: int, n_queries: int, cfg: dict) -> int:
    """Least HBM bytes of the exhaustive top-k for one batch: every row
    once with its count, plus the queries and the answers."""
    row = int(cfg["fp_bits"]) // 8
    return (n_rows * (row + COUNT_BYTES) + n_queries * row
            + n_queries * int(cfg["k"]) * (ID_BYTES + SCORE_BYTES))


def hbm_share(least_bytes: float, kernel_s: float, peak: dict) -> float | None:
    """Least time at the HBM peak over the measured kernel time, in %."""
    if kernel_s <= 0:
        return None
    return 100.0 * least_bytes / peak["hbm_bytes_per_s"] / kernel_s
