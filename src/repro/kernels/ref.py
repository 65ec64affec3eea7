"""Pure-jnp oracles for the Pallas kernels (allclose targets in tests)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core import bitbound as bb
from ..core.fingerprints import Metric, TANIMOTO, metric_from_counts, popcount


def tanimoto_scores_ref(queries: jax.Array, db: jax.Array,
                        db_popcount: jax.Array | None = None,
                        metric: Metric = TANIMOTO) -> jax.Array:
    """(Q, W) x (N, W) -> (Q, N) float32 score matrix (Tanimoto default)."""
    if db_popcount is None:
        db_popcount = popcount(db)
    q_cnt = popcount(queries)
    inter = jnp.sum(
        jax.lax.population_count(queries[:, None, :] & db[None, :, :]).astype(jnp.int32),
        axis=-1)
    return metric_from_counts(metric, inter, q_cnt[:, None], db_popcount[None, :])


def tanimoto_topk_ref(queries: jax.Array, db: jax.Array, k: int,
                      db_popcount: jax.Array | None = None,
                      metric: Metric = TANIMOTO):
    """Oracle for the fused on-the-fly engine: exact top-k ids + scores."""
    scores = tanimoto_scores_ref(queries, db, db_popcount, metric=metric)
    vals, ids = jax.lax.top_k(scores, k)
    return ids.astype(jnp.int32), vals


def bitbound_topk_ref(queries: jax.Array, db_sorted: jax.Array,
                      counts_sorted: jax.Array, k: int, cutoff: float,
                      metric: Metric = TANIMOTO):
    """Oracle for the BitBound-pruned kernel: scores outside the metric's
    popcount window (``core.bitbound.bound_counts_np``; Tanimoto: Eq.2) are
    treated as -inf (never returned)."""
    scores = tanimoto_scores_ref(queries, db_sorted, counts_sorted,
                                 metric=metric)
    lo, hi = bb.bound_counts_np(np.asarray(popcount(queries)), cutoff,
                                metric=metric)
    lo = jnp.asarray(lo, jnp.float32)[:, None]
    hi = jnp.asarray(np.minimum(hi, 2.0**30), jnp.float32)[:, None]
    c = counts_sorted[None, :].astype(jnp.float32)
    in_range = jnp.logical_and(c >= lo, c <= hi)
    scores = jnp.where(in_range, scores, -jnp.inf)
    vals, ids = jax.lax.top_k(scores, k)
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    return ids.astype(jnp.int32), vals


def window_topk_ref(queries: jax.Array, db: jax.Array, db_cnt: jax.Array,
                    lo_row: jax.Array, hi_row: jax.Array, k: int,
                    metric: Metric = TANIMOTO):
    """Oracle for the row-window kernel: rows outside [lo_row, hi_row) are
    -inf (never returned); invalid slots come back as id -1."""
    scores = tanimoto_scores_ref(queries, db, db_cnt, metric=metric)
    idx = jnp.arange(db.shape[0])[None, :]
    in_window = jnp.logical_and(idx >= lo_row[:, None], idx < hi_row[:, None])
    scores = jnp.where(in_window, scores, -jnp.inf)
    vals, ids = jax.lax.top_k(scores, k)
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    return ids.astype(jnp.int32), vals


def gather_tanimoto_ref(queries: jax.Array, db: jax.Array,
                        ids: jax.Array,
                        metric: Metric = TANIMOTO) -> jax.Array:
    """Oracle for the gather-distance kernel: (Q, W) x (Q, E) ids -> (Q, E)
    sims, with -inf wherever id < 0."""
    safe = jnp.clip(ids, 0, db.shape[0] - 1)
    rows = db[safe]                                     # (Q, E, W)
    q_cnt = popcount(queries)
    inter = jnp.sum(jax.lax.population_count(
        queries[:, None, :] & rows).astype(jnp.int32), axis=-1)
    s = metric_from_counts(metric, inter, q_cnt[:, None], popcount(db)[safe])
    return jnp.where(ids >= 0, s, -jnp.inf)


def expand_sorted_ref(queries: jax.Array, nbr_fps: jax.Array,
                      nbr_cnt: jax.Array, pop_ids: jax.Array,
                      flat_ids: jax.Array, worst: jax.Array, kk: int,
                      metric: Metric = TANIMOTO):
    """Oracle for the fused beam-expansion kernel (``kernels/expand.py``):
    score every neighbour block of the popped beam, mask ``-1`` flat ids and
    scores ``<= worst``, return the top-``kk`` per query sorted descending
    (-inf / -1 in the empty tail)."""
    q_n = queries.shape[0]
    safe = jnp.clip(pop_ids, 0, nbr_fps.shape[0] - 1)
    blk = nbr_fps[safe]                                 # (Q, B, 2M, W)
    q_cnt = popcount(queries)
    inter = jnp.sum(jax.lax.population_count(
        queries[:, None, None, :] & blk).astype(jnp.int32), axis=-1)
    s = metric_from_counts(metric, inter, q_cnt[:, None, None], nbr_cnt[safe])
    s = s.reshape(q_n, -1)
    s = jnp.where(flat_ids >= 0, s, -jnp.inf)
    s = jnp.where(s > worst[:, None], s, -jnp.inf)
    ids = jnp.where(s > -jnp.inf, flat_ids, -1)
    s_srt, pos = jax.lax.top_k(s, kk)
    return s_srt, jnp.take_along_axis(ids, pos, axis=1)


def bitcount_ref(words: jax.Array) -> jax.Array:
    return popcount(words)
