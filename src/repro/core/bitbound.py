"""BitBound pruning (Swamidass & Baldi) — Eq. 2 of the paper.

The database is sorted by popcount once at index-build time. For a query with
popcount ``a`` and similarity cutoff ``Sc``, only candidates whose popcount
``b`` satisfies

    a * Sc  <=  b  <=  a / Sc                                   (Eq. 2)

can have Tanimoto(query, cand) >= Sc (because S <= min(a,b)/max(a,b)).
The contiguous popcount-sorted range is located with two searchsorted ops and
the scan is restricted to it.  The paper models the pruned fraction with a
Gaussian fit of the popcount distribution (Eq. 3) — reproduced in
``gaussian_model`` / ``expected_speedup`` and benchmarked in
``benchmarks/bitbound_speedup.py`` (Fig. 2).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .fingerprints import Metric, TANIMOTO, metric_from_counts_np, popcount


@dataclass
class BitBoundIndex:
    """Popcount-sorted fingerprint database."""
    db: jax.Array            # (N, W) uint32, sorted by popcount ascending
    counts: jax.Array        # (N,) int32 popcounts, ascending
    order: jax.Array         # (N,) int32 — original index of each sorted row
    # Gaussian fit of the popcount distribution (paper Eq. 3)
    mu: float
    sigma: float

    @property
    def n(self) -> int:
        return self.db.shape[0]


def build_index(db: jax.Array) -> BitBoundIndex:
    counts = np.asarray(popcount(db))
    order = np.argsort(counts, kind="stable").astype(np.int32)
    db_sorted = jnp.asarray(np.asarray(db)[order])
    counts_sorted = jnp.asarray(counts[order].astype(np.int32))
    return BitBoundIndex(db=db_sorted, counts=counts_sorted,
                         order=jnp.asarray(order),
                         mu=float(counts.mean()), sigma=float(counts.std()))


def _best_score_f32(metric: Metric, a: np.ndarray, b: np.ndarray):
    """Highest score a print of popcount ``b`` can reach against a query of
    popcount ``a`` (overlap c = min(a, b) — every metric grows with c), in
    the f32 arithmetic the engines score with."""
    return metric_from_counts_np(metric, np.minimum(a, b), a, b)


def _widen(edge: np.ndarray, step: int, a: np.ndarray, sc: np.float32,
           metric: Metric) -> np.ndarray:
    """Move each window edge outward while the next popcount can still
    score >= ``sc``. The best score falls monotonically away from b = a, so
    the loop stops at the exact f32 boundary."""
    while True:
        nxt = edge + step
        ok = np.isfinite(nxt) & (nxt >= 0)
        ok[ok] = _best_score_f32(metric, a[ok], nxt[ok].astype(np.int64)) >= sc
        if not ok.any():
            return edge
        edge = np.where(ok, nxt, edge)


def bound_counts_np(query_counts: np.ndarray, cutoff: float,
                    metric: Metric = TANIMOTO):
    """Per-metric popcount bounds ``[lo_cnt, hi_cnt]`` (float64 arrays of
    integer values; an unbounded side is 0 / +inf).

    THE window formula: every engine, kernel wrapper and oracle derives its
    BitBound window from this one function — main-segment row windows
    (:func:`bound_range_np`), delta-segment masks and the kernel tests —
    so no two of them can disagree on a boundary popcount. The start is
    the real-arithmetic bound (Tanimoto: Eq. 2 ``[ceil(a*Sc), floor(a/Sc)]``;
    others via ``Metric.bound_ratios``). Scores are f32, whose rounding can
    lift a boundary popcount's best score onto the f32 cutoff, so each edge
    is then widened to the last popcount whose best f32 score still
    reaches ``float32(cutoff)``: any print that scores >= the cutoff on
    the device lies inside the window.
    """
    shape = np.shape(query_counts)
    a = np.asarray(query_counts, dtype=np.int64).reshape(-1)
    af = a.astype(np.float64)
    if metric.name == "tanimoto":
        lo_cnt = np.ceil(af * cutoff)
        hi_cnt = np.floor(af / max(cutoff, 1e-6))
    else:
        lo_r, hi_r = metric.bound_ratios(cutoff)
        lo_cnt = (np.ceil(af * lo_r) if metric.bounded_below
                  else np.zeros_like(af))
        hi_cnt = (np.floor(af * hi_r) if metric.bounded_above
                  else np.full_like(af, np.inf))
    sc = np.float32(cutoff)
    if sc > 0:
        if metric.bounded_below:
            lo_cnt = _widen(lo_cnt, -1, a, sc, metric)
        if metric.bounded_above:
            hi_cnt = _widen(hi_cnt, 1, a, sc, metric)
    return lo_cnt.reshape(shape), hi_cnt.reshape(shape)


def bound_range_np(counts_sorted: np.ndarray, query_counts: np.ndarray,
                   cutoff: float, metric: Metric = TANIMOTO):
    """Host-side batched per-metric windows [lo, hi) for a whole query batch
    over a popcount-sorted count vector (:func:`bound_counts_np` bounds).
    The engines use it to size the static kernel grid (a Python int) and
    pass the rows to the device."""
    lo_cnt, hi_cnt = bound_counts_np(query_counts, cutoff, metric)
    lo = np.searchsorted(counts_sorted, lo_cnt, side="left")
    hi = np.searchsorted(counts_sorted, hi_cnt, side="right")
    return lo.astype(np.int64), hi.astype(np.int64)


def bucket_tiles(n_tiles: int, total_tiles: int) -> int:
    """Round a tile-window size up to the next power of two (clamped to the
    whole DB) — the engine compiles one kernel per bucket, so the number of
    distinct compilations is O(log total_tiles) regardless of query mix."""
    n_tiles = max(int(n_tiles), 1)
    b = 1 << (n_tiles - 1).bit_length()
    return min(b, max(int(total_tiles), 1))


def aligned_range(lo, hi, tile: int, n: int):
    """Round the candidate range outward to tile boundaries (the engine scans
    whole HBM tiles; partial tiles are masked inside the kernel)."""
    lo_t = (lo // tile) * tile
    hi_t = jnp.minimum(((hi + tile - 1) // tile) * tile, n)
    return lo_t, hi_t


# --- analytical model (paper Fig. 2) ---------------------------------------

def gaussian_model(x: np.ndarray, mu: float, sigma: float) -> np.ndarray:
    """Paper Eq. 3 — popcount density model."""
    return np.exp(-((x - mu) ** 2) / (2 * sigma**2)) / np.sqrt(2 * np.pi * sigma**2)


def expected_search_fraction(mu: float, sigma: float, cutoff: float,
                             grid: int = 4096, max_bits: int = 1024) -> float:
    """Expected fraction of the DB scanned per query under the Gaussian model:
    E_a~N [ Phi(a/Sc) - Phi(a*Sc) ].  Speedup = 1 / fraction (Fig. 2d)."""
    from math import erf, sqrt

    def phi(x):
        return 0.5 * (1.0 + erf((x - mu) / (sigma * sqrt(2.0))))

    xs = np.linspace(max(0.0, mu - 5 * sigma), min(max_bits, mu + 5 * sigma), grid)
    dens = gaussian_model(xs, mu, sigma)
    dens /= dens.sum()
    frac = sum(d * (phi(a / max(cutoff, 1e-6)) - phi(a * cutoff)) for a, d in zip(xs, dens))
    return float(max(min(frac, 1.0), 1e-9))


def expected_speedup(mu: float, sigma: float, cutoff: float) -> float:
    return 1.0 / expected_search_fraction(mu, sigma, cutoff)
