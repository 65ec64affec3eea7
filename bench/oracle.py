"""Exact arithmetic shared by the plain references (``bench/references``).

Plain numpy, independent of the program: popcounts, the f32 Tanimoto score
as IEEE f32 division rounds it, the precision a control computes in, and
top-k by (score descending, tie key ascending). Rows go through in blocks
so no temporary exceeds a few MB, and queries are spread over threads
(numpy releases the GIL inside its kernels).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROW_BLOCK = 1 << 15
PRECISIONS = ("f32", "bf16")


def popcounts(rows: np.ndarray) -> np.ndarray:
    """(N, W) uint32 -> (N,) int32 set bits per row."""
    return np.bitwise_count(rows).sum(-1, dtype=np.int32)


def intersections(q: np.ndarray, rows: np.ndarray) -> np.ndarray:
    """Popcount of ``q & row`` for every row, over 64-bit words."""
    q64 = np.ascontiguousarray(q).view(np.uint64)
    r64 = np.ascontiguousarray(rows).view(np.uint64)
    out = np.empty(len(rows), np.int32)
    for lo in range(0, len(rows), ROW_BLOCK):
        blk = r64[lo:lo + ROW_BLOCK]
        out[lo:lo + len(blk)] = np.bitwise_count(blk & q64).sum(
            -1, dtype=np.int32)
    return out


def to_precision(s: np.ndarray, precision: str) -> np.ndarray:
    """f32 scores as the given precision would hold them, back in f32:
    ``bf16`` rounds to the nearest 8-bit significand, ties to even."""
    s = np.asarray(s, np.float32)
    if precision == "f32":
        return s
    if precision != "bf16":
        raise ValueError(f"precision must be one of {PRECISIONS}")
    b = s.view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32)


def tanimoto(inter, a, b, precision: str = "f32") -> np.ndarray:
    """Tanimoto c / (a + b - c) as an IEEE f32 quotient (0 where the union
    is empty), in ``precision``."""
    inter = np.asarray(inter, np.int64)
    union = np.asarray(a, np.int64) + np.asarray(b, np.int64) - inter
    s = np.where(union > 0,
                 inter.astype(np.float32)
                 / np.maximum(union, 1).astype(np.float32),
                 np.float32(0))
    return to_precision(s.astype(np.float32), precision)


def window_mask(a: int, cutoff, bits: int,
                precision: str = "f32") -> np.ndarray:
    """BitBound's window (Eq. 2) for a query of popcount ``a``: a mask over
    popcounts 0..bits of the rows whose best possible Tanimoto,
    min(a, b) / max(a, b), reaches the cutoff, both as ``precision``
    holds them."""
    b = np.arange(bits + 1)
    cut = to_precision(np.float32(cutoff), precision)
    return tanimoto(np.minimum(a, b), a, b, precision) >= cut


def first_k(scores: np.ndarray, keys: tuple, k: int) -> np.ndarray:
    """Positions of the ``k`` best entries: score descending, then the
    ``keys`` arrays ascending (most significant first)."""
    n = len(scores)
    if n > k:
        kth = np.partition(scores, n - k)[n - k]
        pos = np.flatnonzero(scores >= kth)
    else:
        pos = np.arange(n)
    order = np.lexsort(tuple(key[pos] for key in reversed(keys))
                       + (-scores[pos],))
    return pos[order[:k]]


def pad_answer(ids, sims, k: int):
    """An answer of fewer than k rows, padded as the engines pad: -1, 0."""
    out_i = np.full(k, -1, np.int64)
    out_s = np.zeros(k, np.float32)
    out_i[:len(ids)] = ids
    out_s[:len(sims)] = sims
    return out_i, out_s


def fold(rows: np.ndarray, m: int, scheme: int) -> np.ndarray:
    """Strided modulo-OR fold (scheme 1): word j of the folded print ORs
    words j, j + W/m, j + 2W/m, ... of the full one."""
    if scheme != 1:
        raise ValueError("the reference folds by scheme 1 only")
    w = rows.shape[-1]
    if m == 1:
        return rows
    if w % m:
        raise ValueError(f"{w} words do not fold evenly by {m}")
    sec = rows.reshape(*rows.shape[:-1], m, w // m)
    out = sec[..., 0, :].copy()
    for i in range(1, m):
        out |= sec[..., i, :]
    return out


def map_queries(fn, n: int):
    """``[fn(i) for i in range(n)]`` on a few threads."""
    workers = max(1, min(8, os.cpu_count() or 1, n))
    with ThreadPoolExecutor(max_workers=workers) as ex:
        return list(ex.map(fn, range(n)))
