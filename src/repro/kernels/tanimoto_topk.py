"""Fused Tanimoto-scan + streaming top-k Pallas kernels — the "on-the-fly"
query engine of the paper, TPU-native (DESIGN.md §2).

Design (FPGA -> TPU mapping):

* The FPGA cascades BitCnt -> TFC -> top-K-merge-sort FIFOs with pipeline
  interval 1, so a score is consumed by the sorter the cycle it's produced
  and the N-element score stream never exists in off-chip memory.
* Here the database is streamed HBM->VMEM in ``(TILE_N, W)`` BlockSpec tiles;
  each grid step computes the tile's scores in vector registers and merges
  them into a **persistent VMEM top-k scratch** — the scores never get
  written back to HBM. Only the final top-k rows leave the chip, so HBM
  traffic is one read of the database per query block.
* Scoring layout: the tile is transposed in VMEM to ``(W, TILE_N)`` so each
  query's intersection popcounts reduce over sublanes into one lane-dense
  ``(1, TILE_N)`` row (:func:`tile_inter`).
* The top-k merge (:func:`merge_topk`) is the constant-shape analogue of
  the paper's merge-sort unit, built only from ops Mosaic lowers (``max``,
  ``min``, selects, a one-lane roll): candidates that beat the running k-th
  score are pulled out best-first (lowest lane on ties) and each is
  shift-inserted into the sorted running row. A tile with no such candidate
  costs one compare. The result is exactly ``lax.top_k`` over
  ``concat(running, tile)``: score descending, ties to the lower id.
* Running top-k rows are padded to 128 lanes (``round_up(k, LANES)``) and
  sliced outside the kernel; count vectors are ``(1, N)`` lane-dense rows.
* The windowed variant adds scalar-prefetched per-block tile windows
  ``(lo_tile, n_tiles)``, each covering the union of a query block's row
  windows: the grid is sized for the *worst-case* block and the
  ``index_map`` offsets DB tile fetches by ``lo_tile[b]`` — the TPU
  analogue of the FPGA engine fetching only the popcount-bounded address
  range from HBM. Tiles beyond the block's window are skipped via
  ``pl.when`` (fetch suppressed by clamping the index map to a single
  repeated tile). BitBound (Eq. 2) search is this kernel over the row range
  that the popcount window selects in the popcount-sorted database.

VMEM budget (v5e: 16 MiB scoped by default): tile (TILE_N=2048, W=32) uint32
= 256 KiB double-buffered, its transpose, and ``(rows, k_pad + TILE_N)``
merge temporaries.

Validated with ``interpret=True`` on CPU against ``ref.py``, and compiled
for v5e with ``interpret=False`` by ``tests/test_chip_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.fingerprints import Metric, TANIMOTO, metric_from_counts

DEFAULT_TILE_N = 2048
LANES = 128
SUBLANES = 8
NEG = float("-inf")  # python scalar: must not be a captured jnp constant


def round_up(x: int, m: int) -> int:
    return -(-int(x) // m) * m


# ---------------------------------------------------------------------------
# in-kernel building blocks (shared with gather.py / expand.py)
# ---------------------------------------------------------------------------

def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def popcount_rows(x):
    """(R, W) uint32 -> (R, 1) int32 row popcounts."""
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=1,
                   keepdims=True)


def _row_to_col(x):
    """(1, W) uint32 row -> (W, 1) column, via a diagonal select and a lane
    sum (exact: one nonzero term per output)."""
    w = x.shape[1]
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    eye = _iota((w, w), 0) == _iota((w, w), 1)
    col = jnp.sum(jnp.where(eye, jnp.broadcast_to(xi, (w, w)), 0), axis=1,
                  keepdims=True)
    return jax.lax.bitcast_convert_type(col, jnp.uint32)


def tile_inter(q_rows, db_tile):
    """Intersection popcounts of ``q_rows (R, W)`` against ``db_tile (T, W)``
    -> ``(R, T)`` int32, lane-dense: the tile is transposed once and each
    query's ANDs reduce over sublanes."""
    r = q_rows.shape[0]
    t = db_tile.shape[0]
    db_t = db_tile.T                                   # (W, T)
    inter = None
    sub = _iota((r, t), 0)
    for j in range(r):
        qcol = _row_to_col(q_rows[j:j + 1, :])         # (W, 1)
        row = jnp.sum(jax.lax.population_count(db_t & qcol).astype(jnp.int32),
                      axis=0, keepdims=True)           # (1, T)
        inter = row if inter is None else jnp.where(sub == j, row, inter)
    return inter


def init_topk(top_s, top_i):
    top_s[...] = jnp.full(top_s.shape, NEG, jnp.float32)
    top_i[...] = jnp.full(top_i.shape, -1, jnp.int32)


def merge_topk(top_s, top_i, s, ids, k: int):
    """Fold candidates ``s (R, T)`` with ids ``ids (1 or R, T)`` into the
    running top-k held in lanes ``[0, k)`` of the ``(R, k_pad)`` refs.

    Every candidate id must exceed every id already in the run and ids must
    ascend along lanes (true for a tile scan in ascending row order): the
    result is then exactly ``lax.top_k(concat(run, s), k)`` — scores
    descending, ties to the lower id — with ``-inf`` slots carrying id -1.
    Only candidates strictly above the running k-th score can enter; at
    most ``k`` of them are shift-inserted, best first."""
    rows, kp = top_s.shape
    t = s.shape[1]
    lane_k = _iota((rows, kp), 1)
    in_k = lane_k < k
    run_s = top_s[...]
    kth = jnp.min(jnp.where(in_k, run_s, jnp.inf), axis=1, keepdims=True)
    cand = jnp.where(s > kth, s, NEG)
    n_new = jnp.max(jnp.sum((cand > NEG).astype(jnp.int32), axis=1,
                            keepdims=True))

    @pl.when(n_new > 0)
    def _merge():
        lane_t = _iota((rows, t), 1)
        ids_b = jnp.broadcast_to(ids, (rows, t))

        def insert_best(_, carry):
            cand, run_s, run_i = carry
            m = jnp.max(cand, axis=1, keepdims=True)
            pos = jnp.min(jnp.where(cand == m, lane_t, t), axis=1,
                          keepdims=True)
            hit = lane_t == pos
            mid = jnp.sum(jnp.where(hit, ids_b, 0), axis=1, keepdims=True)
            cand = jnp.where(hit, NEG, cand)
            kth = jnp.min(jnp.where(in_k, run_s, jnp.inf), axis=1,
                          keepdims=True)
            # insertion lane: after every running score >= m (lower ids)
            at = jnp.sum(jnp.where(in_k & (run_s >= m), 1, 0), axis=1,
                         keepdims=True)
            new_s = jnp.where(lane_k < at, run_s,
                              jnp.where(lane_k == at, m,
                                        pltpu.roll(run_s, 1, 1)))
            new_i = jnp.where(lane_k < at, run_i,
                              jnp.where(lane_k == at, mid,
                                        pltpu.roll(run_i, 1, 1)))
            ins = m > kth
            run_s = jnp.where(ins & in_k, new_s, run_s)
            run_i = jnp.where(ins & in_k, new_i, run_i)
            return cand, run_s, run_i

        _, run_s, run_i = jax.lax.fori_loop(
            0, jnp.minimum(n_new, k), insert_best,
            (cand, top_s[...], top_i[...]))
        top_s[...] = run_s
        top_i[...] = run_i


def select_topk(s, ids, k: int, kp: int):
    """Top-k of each row of ``s (R, E)`` -> ``(R, kp)`` scores and ids,
    exactly ``lax.top_k`` order (descending, ties to the lower lane);
    ``-inf`` slots carry id -1, lanes ``>= k`` are ``-inf`` / -1."""
    rows, e = s.shape
    lane = _iota((rows, e), 1)
    lane_k = _iota((rows, kp), 1)

    def pick(r, carry):
        s, out_s, out_i = carry
        m = jnp.max(s, axis=1, keepdims=True)
        pos = jnp.min(jnp.where(s == m, lane, e), axis=1, keepdims=True)
        hit = lane == pos
        mid = jnp.sum(jnp.where(hit, ids, 0), axis=1, keepdims=True)
        mid = jnp.where(m > NEG, mid, -1)
        s = jnp.where(hit, NEG, s)
        out_s = jnp.where(lane_k == r, m, out_s)
        out_i = jnp.where(lane_k == r, mid, out_i)
        return s, out_s, out_i

    _, out_s, out_i = jax.lax.fori_loop(
        0, k, pick, (s, jnp.full((rows, kp), NEG, jnp.float32),
                     jnp.full((rows, kp), -1, jnp.int32)))
    return out_s, out_i


# ---------------------------------------------------------------------------
# full-scan fused kernel (brute force), qb queries per DB sweep
# ---------------------------------------------------------------------------
#
# The paper's engine streams the database once PER QUERY: bytes/query =
# N * 128 B. Blocking qb queries into one sweep streams the DB once per
# BLOCK (bytes/query /= qb) while the per-tile compute grows only by the
# popcount ops. The FPGA analogue would be replicating the TFC+top-k
# pipeline behind one HBM channel.

def _fused_body(q_ref, db_ref, dbcnt_ref, ids_ref, vals_ref, top_s, top_i,
                *, k: int, tile_n: int, n_tiles: int, n_valid: int,
                metric: Metric = TANIMOTO):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        init_topk(top_s, top_i)

    q = q_ref[...]                                     # (qb, W) uint32
    inter = tile_inter(q, db_ref[...])                 # (qb, tile_n)
    # TFC stage: the metric maps the (a, b, c) triple to a score at trace
    # time.
    s = metric_from_counts(metric, inter, popcount_rows(q), dbcnt_ref[...])
    idx = t * tile_n + _iota((1, tile_n), 1)
    s = jnp.where(idx < n_valid, s, NEG)               # mask padded tail rows
    merge_topk(top_s, top_i, s, idx, k)

    @pl.when(t == n_tiles - 1)
    def _emit():
        vals_ref[...] = top_s[...]
        ids_ref[...] = top_i[...]


def fused_tanimoto_topk(queries: jax.Array, db: jax.Array, db_cnt: jax.Array,
                        k: int, n_valid: int, qb: int,
                        tile_n: int = DEFAULT_TILE_N, interpret: bool = False,
                        metric: Metric = TANIMOTO):
    """queries (Q, W) u32 with Q a multiple of ``qb`` (``qb`` a multiple of
    8, or ``qb == Q``), db (N_pad, W) u32 and db_cnt (1, N_pad) i32 padded
    to a tile multiple (``db_cnt`` may hold anything in the pad — masking is
    by row index vs ``n_valid``). Returns ids (Q, k_pad) i32 and vals
    (Q, k_pad) f32, ``k_pad = round_up(k, 128)``; lanes >= k are -1 / -inf."""
    q_n, w = queries.shape
    n_pad = db.shape[0]
    assert n_pad % tile_n == 0 and q_n % qb == 0, (n_pad, tile_n, q_n, qb)
    n_tiles = n_pad // tile_n
    kp = round_up(k, LANES)
    body = functools.partial(_fused_body, k=k, tile_n=tile_n, n_tiles=n_tiles,
                             n_valid=n_valid, metric=metric)
    out = pl.pallas_call(
        body,
        grid=(q_n // qb, n_tiles),
        in_specs=[
            pl.BlockSpec((qb, w), lambda q, t: (q, 0)),         # query block
            pl.BlockSpec((tile_n, w), lambda q, t: (t, 0)),     # DB tile stream
            pl.BlockSpec((1, tile_n), lambda q, t: (0, t)),     # DB popcounts
        ],
        out_specs=[
            pl.BlockSpec((qb, kp), lambda q, t: (q, 0)),
            pl.BlockSpec((qb, kp), lambda q, t: (q, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_n, kp), jnp.int32),
            jax.ShapeDtypeStruct((q_n, kp), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((qb, kp), jnp.float32),
            pltpu.VMEM((qb, kp), jnp.int32),
        ],
        interpret=interpret,
    )(queries, db, db_cnt)
    return out[0], out[1]


# ---------------------------------------------------------------------------
# row-window fused kernel (BitBound windows; stage 1 of the two-stage engine)
# ---------------------------------------------------------------------------
#
# Scalar-prefetched tile streaming over explicit per-query row intervals
# [lo_row, hi_row). On a popcount-sorted database the Eq.2 popcount window
# IS such an interval, and it is what the folded stage-1 scan needs: the
# window is defined on *full-resolution* popcounts (the sort key of the
# database), while the streamed tiles may hold the *folded* prints. A grid
# step serves a block of ``qb`` queries: it streams one tile of the union
# of their windows and each row masks the tile to its own window. Eq.2
# windows are monotone in the query popcount, so on popcount-sorted
# queries a block's union is barely wider than any one member's window
# and one tile stream serves the whole block.

def _window_body(lo_t_ref, nt_ref, lo_ref, hi_ref, q_ref, db_ref,
                 dbcnt_ref, ids_ref, vals_ref, top_s, top_i,
                 *, k: int, tile_n: int, max_tiles: int, n_valid: int,
                 metric: Metric = TANIMOTO):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        init_topk(top_s, top_i)

    @pl.when(t < nt_ref[b])
    def _scan():
        q = q_ref[...]                                 # (qb, W)
        inter = tile_inter(q, db_ref[...])             # (qb, tile_n)
        s = metric_from_counts(metric, inter, popcount_rows(q),
                               dbcnt_ref[...])
        idx = (lo_t_ref[b] + t) * tile_n + _iota((1, tile_n), 1)
        ok = (idx >= lo_ref[...]) & (idx < hi_ref[...]) & (idx < n_valid)
        merge_topk(top_s, top_i, jnp.where(ok, s, NEG), idx, k)

    @pl.when(t == max_tiles - 1)
    def _emit():
        vals_ref[...] = top_s[...]
        ids_ref[...] = top_i[...]


def windowed_fused_topk(queries: jax.Array, db: jax.Array, db_cnt: jax.Array,
                        lo_tile: jax.Array, n_tiles_b: jax.Array,
                        lo_row: jax.Array, hi_row: jax.Array, k: int,
                        max_tiles: int, n_valid: int, qb: int,
                        tile_n: int = DEFAULT_TILE_N, interpret: bool = False,
                        metric: Metric = TANIMOTO):
    """Scan only rows [lo_row[q], hi_row[q]) of ``db`` for each query,
    ``qb`` queries per grid step.

    queries (Q, W) u32 with Q a multiple of ``qb`` (``qb`` a multiple of 8,
    or ``qb == Q``); db (N_pad, W) u32 and db_cnt (1, N_pad) i32 padded to a
    tile multiple; lo_tile, n_tiles_b: (Q / qb,) int32 tile window of each
    block, covering the union of its queries' row intervals; lo_row,
    hi_row: (Q, 1) int32 exact row bounds of each query (rows outside them,
    in the block's tiles, are masked). ``db`` may be the folded database
    while the bounds come from the full-resolution popcount sort. Returns
    ids into the (sorted) DB and values, both (Q, k_pad); empty slots are
    id -1 / val -inf."""
    q_n, w = queries.shape
    n_pad = db.shape[0]
    assert q_n % qb == 0, (q_n, qb)
    total_tiles = n_pad // tile_n
    kp = round_up(k, LANES)

    def db_index(b, t, lo_t, nt):
        blk = jnp.where(t < nt[b], lo_t[b] + t, lo_t[b])
        return (jnp.minimum(blk, total_tiles - 1), 0)

    def cnt_index(b, t, lo_t, nt):
        blk = jnp.where(t < nt[b], lo_t[b] + t, lo_t[b])
        return (0, jnp.minimum(blk, total_tiles - 1))

    def row_index(b, t, lo_t, nt):
        return (b, 0)

    body = functools.partial(_window_body, k=k, tile_n=tile_n,
                             max_tiles=max_tiles, n_valid=n_valid,
                             metric=metric)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(q_n // qb, max_tiles),
        in_specs=[
            pl.BlockSpec((qb, 1), row_index),                # lo_row
            pl.BlockSpec((qb, 1), row_index),                # hi_row
            pl.BlockSpec((qb, w), row_index),                # query block
            pl.BlockSpec((tile_n, w), db_index),             # DB tile stream
            pl.BlockSpec((1, tile_n), cnt_index),            # DB popcounts
        ],
        out_specs=[
            pl.BlockSpec((qb, kp), row_index),
            pl.BlockSpec((qb, kp), row_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((qb, kp), jnp.float32),
            pltpu.VMEM((qb, kp), jnp.int32),
        ],
    )
    ids, vals = pl.pallas_call(
        body,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((q_n, kp), jnp.int32),
            jax.ShapeDtypeStruct((q_n, kp), jnp.float32),
        ],
        interpret=interpret,
    )(lo_tile.astype(jnp.int32), n_tiles_b.astype(jnp.int32),
      lo_row.astype(jnp.int32), hi_row.astype(jnp.int32), queries, db,
      db_cnt)
    return ids, vals


# ---------------------------------------------------------------------------
# standalone BitCnt kernel (paper module 1) — mostly pedagogical; the fused
# engine precomputes DB counts and counts queries inline.
# ---------------------------------------------------------------------------

def _bitcount_body(w_ref, o_ref):
    o_ref[...] = jnp.sum(jax.lax.population_count(w_ref[...]).astype(jnp.int32),
                         axis=-1)


def bitcount(words: jax.Array, tile_n: int = 4096, interpret: bool = False):
    """(N, W) uint32 -> (N,) int32 popcounts, tiled through VMEM."""
    n, w = words.shape
    pad = (-n) % tile_n
    wp = jnp.pad(words, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        _bitcount_body,
        grid=(wp.shape[0] // tile_n,),
        in_specs=[pl.BlockSpec((tile_n, w), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile_n,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((wp.shape[0],), jnp.int32),
        interpret=interpret,
    )(wp)
    return out[:n]
