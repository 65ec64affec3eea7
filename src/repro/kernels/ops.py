"""Jit'd public wrappers around the Pallas kernels (padding, tiling policy).

Kernel-to-engine mapping and the data layouts each kernel streams are
documented in docs/ARCHITECTURE.md. All wrappers are placement-transparent:
they launch on whatever device their operands are committed to, which is
what lets the sharded HNSW fan-out (``HNSWEngine(shards=N)``) run one
kernel-backed traversal per shard device with no per-device code here.

The wrappers own every layout rule Mosaic imposes, so the kernels see only
aligned blocks: queries are padded to a multiple of the query block
(:func:`_query_block`), k and candidate widths to 128 lanes, count vectors
become ``(1, N)`` rows, and the gather/expand kernels read fingerprints as
128-word lines. Results are sliced back before they are returned.
"""
from __future__ import annotations

import functools
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np

from ..core.fingerprints import Metric, TANIMOTO, popcount
from . import expand as ke
from . import gather as kg
from . import tanimoto_topk as ktk
from .tanimoto_topk import LANES, SUBLANES, round_up


def _interpret() -> bool:
    """Whether the kernels run in the Pallas interpreter (see
    :func:`interpret_on`) for this process's backend and platform choice."""
    return interpret_on(jax.default_backend(), jax.config.jax_platforms,
                        importlib.util.find_spec("libtpu") is not None)


def interpret_on(backend: str, platforms: str | None,
                 tpu_runtime: bool) -> bool:
    """Kernels compile through Mosaic on TPU. On CPU they run in the Pallas
    interpreter, but only when CPU is what was asked for: ``platforms``
    (``JAX_PLATFORMS``) puts ``cpu`` first, as the tests do, or is unset on
    a host with no TPU runtime. A host with a TPU runtime whose TPU failed
    to start also reports the CPU backend; that, and any other backend, is
    an error — a TPU engine must never be served by the interpreter."""
    if backend == "tpu":
        return False
    first = (platforms or "").split(",")[0]
    if backend == "cpu" and (first == "cpu" or not (first or tpu_runtime)):
        return True
    raise RuntimeError(f"Pallas kernels need a TPU (or the CPU interpreter, "
                       f"with JAX_PLATFORMS=cpu); the default JAX backend is "
                       f"{backend!r} with JAX_PLATFORMS={platforms!r}")


def _pick_tile(n: int, tile_n: int | None) -> int:
    if tile_n is not None:
        return tile_n
    # keep (tile, 32) u32 tile ~<= 256 KiB of VMEM and lane-aligned
    return min(ktk.DEFAULT_TILE_N, max(128, 1 << (max(n - 1, 1)).bit_length() - 1))


def _query_block(q_n: int, qb: int = SUBLANES) -> tuple[int, int]:
    """(rows per query block, padded Q): the whole batch when it fits in one
    block, else ``qb`` rounded up to the 8-row sublane tile."""
    if q_n <= qb:
        return q_n, q_n
    qb = round_up(qb, SUBLANES)
    return qb, round_up(q_n, qb)


def _pad_to(x, rows: int, cols: int | None = None, value=0):
    pad = [(0, rows - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    if cols is not None:
        pad[1] = (0, cols - x.shape[1])
    return jnp.pad(x, pad, constant_values=value)


@functools.partial(jax.jit, static_argnames=("k", "qb", "tile_n", "metric"))
def _tanimoto_topk_impl(queries, db, db_cnt, k: int, qb: int, tile_n: int,
                        metric: Metric = TANIMOTO):
    q_n = queries.shape[0]
    qb, q_pad = _query_block(q_n, qb)
    n = db.shape[0]
    n_pad = round_up(n, tile_n)
    ids, vals = ktk.fused_tanimoto_topk(
        _pad_to(queries, q_pad), _pad_to(db, n_pad),
        _pad_to(db_cnt.astype(jnp.int32), n_pad).reshape(1, n_pad),
        k=k, n_valid=n, qb=qb, tile_n=tile_n, interpret=_interpret(),
        metric=metric)
    return ids[:q_n, :k], vals[:q_n, :k]


def tanimoto_topk(queries: jax.Array, db: jax.Array, k: int,
                  db_popcount: jax.Array | None = None,
                  tile_n: int | None = None,
                  metric: Metric | None = None, qb: int = SUBLANES):
    """Fused on-the-fly exhaustive KNN: (Q, W) x (N, W) -> ids, vals (Q, k).

    One DB sweep serves ``qb`` queries (bytes/query divided by qb — see the
    kernel docstring); Q is padded up to a block multiple. ``metric`` is a
    trace-time constant: each (metric, shape) pair compiles once."""
    queries = jnp.asarray(queries)
    db = jnp.asarray(db)
    if db_popcount is None:
        db_popcount = popcount(db)
    tile = min(_pick_tile(db.shape[0], tile_n), round_up(db.shape[0], LANES))
    return _tanimoto_topk_impl(queries, db, db_popcount, k, qb, tile,
                               metric if metric is not None else TANIMOTO)


#: queries per grid step of the row-window kernel in a large batch: the
#: fastest of 8, 16, 32 and 64 at 1,024 queries on a TPU v5e (PERF.md §6)
WINDOW_QB = 32


def window_block(q_n: int) -> int:
    """Queries per block of the row-window kernel for a batch of ``q_n``:
    :data:`WINDOW_QB`, or the whole batch when it fits in one block."""
    return max(_query_block(q_n, WINDOW_QB)[0], 1)


def window_blocks(lo, hi, qb: int, tile: int, xp=np):
    """Tile window ``(lo_tile, n_tiles)`` of each block of ``qb``
    consecutive queries: the tiles covering the union of the block's row
    windows ``[lo, hi)``, empty windows (``hi <= lo``) ignored, ``(0, 0)``
    for a block with none. The batch is padded to a block multiple with
    empty windows. ``xp`` is numpy for the engine's host plan and
    ``jax.numpy`` in :func:`_window_topk_impl`, so both size blocks
    alike."""
    pad = (-lo.shape[0]) % qb
    live = hi > lo
    lo_b = xp.pad(xp.where(live, lo, 2**31 - 1), (0, pad),
                  constant_values=2**31 - 1).reshape(-1, qb).min(axis=1)
    hi_b = xp.pad(xp.where(live, hi, 0), (0, pad)).reshape(-1, qb).max(axis=1)
    any_live = hi_b > lo_b
    lo_t = xp.where(any_live, lo_b // tile, 0)
    n_t = xp.where(any_live, (hi_b + tile - 1) // tile - lo_t, 0)
    return lo_t, n_t


@functools.partial(jax.jit, static_argnames=("k", "max_tiles", "tile_n", "n_valid", "metric"))
def _window_topk_impl(queries, db_p, cnt_p, lo_row, hi_row, k: int,
                      max_tiles: int, tile_n: int, n_valid: int,
                      metric: Metric = TANIMOTO):
    q_n = queries.shape[0]
    qb = window_block(q_n)
    q_pad = round_up(q_n, qb)
    lo_tile, n_tiles = window_blocks(lo_row, hi_row, qb, tile_n, xp=jnp)
    n_tiles = jnp.clip(n_tiles, 0, max_tiles)
    ids, vals = ktk.windowed_fused_topk(
        _pad_to(queries, q_pad), db_p, cnt_p.reshape(1, -1), lo_tile,
        n_tiles, _pad_to(lo_row, q_pad).reshape(q_pad, 1),
        _pad_to(hi_row, q_pad).reshape(q_pad, 1), k=k, max_tiles=max_tiles,
        n_valid=n_valid, qb=qb, tile_n=tile_n, interpret=_interpret(),
        metric=metric)
    return ids[:q_n, :k], vals[:q_n, :k]


def window_topk(queries: jax.Array, db: jax.Array, db_cnt: jax.Array,
                lo_row: jax.Array, hi_row: jax.Array, k: int,
                max_tiles: int | None = None, tile_n: int | None = None,
                metric: Metric | None = None):
    """Fused KNN over a per-query row window [lo_row, hi_row) of ``db``.

    Stage 1 of the two-stage engine: ``db`` is typically the *folded*
    popcount-sorted database while the row bounds come from the Eq.2 window on
    the full-resolution popcounts. Ids index into ``db``; empty slots are
    id -1 / val -inf. Jit-compatible (callable from inside an enclosing jit)
    when k/max_tiles/tile_n are static.

    Each grid step streams one tile for a block of :func:`window_block`
    consecutive queries over the union of their windows
    (:func:`window_blocks`), so queries sorted by popcount share their
    tiles; every query still gets exactly its own window's top-k.

    HARD PRECONDITION: ``max_tiles`` (the static grid extent) must cover
    the union of each block's windows, in tiles, not only the widest single
    window — a block spanning more tiles is silently truncated to its first
    ``max_tiles`` tiles and rows beyond it are never scored (no error, no
    marker). The default, every tile, is always safe. The engine sorts the
    batch by popcount and buckets ``max_tiles`` to a power of two >= the
    widest block union; other callers must size it the same way with
    :func:`window_blocks` (the row bounds are traced values, so this cannot
    be validated here)."""
    queries = jnp.asarray(queries)
    db = jnp.asarray(db)
    n = db.shape[0]
    tile = _pick_tile(n, tile_n)
    pad = (-n) % tile
    db_p = jnp.pad(db, ((0, pad), (0, 0)))
    cnt_p = jnp.pad(jnp.asarray(db_cnt, dtype=jnp.int32), (0, pad))
    total_tiles = db_p.shape[0] // tile
    if max_tiles is None:
        max_tiles = total_tiles
    max_tiles = max(min(max_tiles, total_tiles), 1)
    ids, vals = _window_topk_impl(
        queries, db_p, cnt_p, jnp.asarray(lo_row, dtype=jnp.int32),
        jnp.asarray(hi_row, dtype=jnp.int32), k=k, max_tiles=max_tiles,
        tile_n=tile, n_valid=n,
        metric=metric if metric is not None else TANIMOTO)
    ids = jnp.where(jnp.isfinite(vals), ids, -1)
    return ids, vals


def bitcount(words: jax.Array) -> jax.Array:
    return ktk.bitcount(jnp.asarray(words), interpret=_interpret())


def _line_layout(queries, q_cnt, q_pad: int):
    """Queries as 128-word lines (padded to the line width and repeated
    across the line) plus (Q_pad, 1) counts — the gather/expand kernels'
    query operands."""
    q_n, w = queries.shape
    wp = kg.line_width(w)
    q_lines = jnp.tile(_pad_to(queries, q_n, wp), (1, LANES // wp))
    return (wp, _pad_to(q_lines, q_pad),
            _pad_to(q_cnt.astype(jnp.int32), q_pad).reshape(q_pad, 1))


def db_lines(db):
    """The gather kernel's database operand: (N, W) prints as
    (ceil(N / per_line), 128) lines of ``per_line = 128 // wp`` prints, each
    padded to ``wp = line_width(W)`` words. The owner of a device database
    builds this once (the HNSW device graph, the insert scorer's cache), so
    no kernel launch relays the database out. Numpy in, numpy out; jax in,
    jax out."""
    xp = np if isinstance(db, np.ndarray) else jnp
    n, w = db.shape
    wp = kg.line_width(w)
    rows = round_up(n, LANES // wp)
    return xp.pad(db, ((0, rows - n), (0, wp - w))).reshape(-1, LANES)


def db_lines_at(db: np.ndarray, line_ids: np.ndarray) -> np.ndarray:
    """Lines ``line_ids`` of ``db_lines(db)``, on the host, with rows past
    the end of ``db`` zero (a capacity-padded copy's pad rows): what an
    incremental refresh of a device copy scatters."""
    per_line = LANES // kg.line_width(db.shape[1])
    src = (np.asarray(line_ids)[:, None] * per_line
           + np.arange(per_line)).reshape(-1)
    rows = np.zeros((src.size, db.shape[1]), np.uint32)
    ok = src < db.shape[0]
    rows[ok] = db[src[ok]]
    return db_lines(rows)


def line_ids(w: int, rows):
    """Id of the :func:`db_lines` line that holds row ``rows`` of a
    ``w``-word print database, or the sorted distinct ids for an array of
    rows."""
    ids = np.asarray(rows) // (LANES // kg.line_width(w))
    return np.unique(ids) if ids.ndim else int(ids)


def nbr_slabs(nbr_fps):
    """The expand kernel's neighbour operand: (N, 2M, W) blocks as
    (N, S, 128) slabs of lines (:func:`db_lines` per node, 2M padded so
    ``S`` is a multiple of 8). Numpy in, numpy out; jax in, jax out."""
    xp = np if isinstance(nbr_fps, np.ndarray) else jnp
    n, m2, w = nbr_fps.shape
    wp = kg.line_width(w)
    per_line = LANES // wp
    m2p = round_up(m2, SUBLANES * per_line)            # slab rows % 8 == 0
    return xp.pad(nbr_fps, ((0, 0), (0, m2p - m2), (0, wp - w))
                  ).reshape(n, m2p // per_line, LANES)


def gather_tanimoto(queries: jax.Array, lines: jax.Array, ids: jax.Array,
                    q_cnt: jax.Array | None = None,
                    metric: Metric | None = None) -> jax.Array:
    """Fine-grained gather-distance stage: per-query candidate ids -> sims.

    queries (Q, W) u32 (W <= 128 words), lines the database in
    :func:`db_lines` layout, ids (Q, E) i32 -> (Q, E) f32. Entries with id
    ``-1`` come back as ``-inf``; ids past the end score the last row the
    lines hold. Jit-compatible — the HNSW traversal calls this from inside
    its ``lax.while_loop``, scoring one whole beam expansion (B·2M
    neighbour ids) per kernel launch.
    """
    queries = jnp.asarray(queries)
    lines = jnp.asarray(lines)
    ids = jnp.asarray(ids, dtype=jnp.int32)
    if q_cnt is None:
        q_cnt = popcount(queries)
    q_n, e_n = ids.shape
    qb, q_pad = _query_block(q_n)
    wp, q_lines, qc = _line_layout(queries, q_cnt, q_pad)
    n = lines.shape[0] * (LANES // wp)
    ids = jnp.where(ids >= 0, jnp.minimum(ids, n - 1), -1)
    ids = _pad_to(ids, q_pad, round_up(e_n, LANES), value=-1)
    s = kg.gather_tanimoto_scores(
        q_lines, qc, lines, ids, wp=wp, qb=qb, interpret=_interpret(),
        metric=metric if metric is not None else TANIMOTO)
    return s[:q_n, :e_n]


def expand_tanimoto_sorted(queries: jax.Array, slabs: jax.Array,
                           pop_ids: jax.Array, flat_ids: jax.Array,
                           worst: jax.Array, kk: int,
                           q_cnt: jax.Array | None = None,
                           metric: Metric | None = None):
    """Fused beam-expansion stage over the neighbour-blocked layout.

    queries (Q, W) u32 (W <= 128 words), slabs the (N, 2M, W) neighbour
    blocks (zero rows for -1 slots) in :func:`nbr_slabs` layout, pop_ids
    (Q, beam) i32, flat_ids (Q, beam*2M) i32, worst (Q,) f32 ->
    (scores (Q, kk) desc, ids (Q, kk)). One contiguous slab DMA per popped
    node (``beam`` streams per query-iteration vs the row kernel's
    ``beam*2M`` fetches), scores sorted in-kernel so the traversal merges a
    single run. Neighbour popcounts are recounted from the slabs.
    Jit-compatible — the HNSW ``lax.while_loop`` launches it once per
    iteration.
    """
    queries = jnp.asarray(queries)
    slabs = jnp.asarray(slabs)
    if q_cnt is None:
        q_cnt = popcount(queries)
    q_n = queries.shape[0]
    beam = pop_ids.shape[1]
    m2 = flat_ids.shape[1] // beam
    qb, q_pad = _query_block(q_n)
    wp, q_lines, qc = _line_layout(queries, q_cnt, q_pad)
    per_line = LANES // wp
    m2p = slabs.shape[1] * per_line
    assert m2p == round_up(m2, SUBLANES * per_line), (slabs.shape, m2, wp)
    slots = jnp.pad(jnp.asarray(flat_ids, jnp.int32).reshape(q_n, beam, m2),
                    ((0, 0), (0, 0), (0, m2p - m2)), constant_values=-1)
    slots = _pad_to(slots.reshape(q_n, beam * m2p), q_pad,
                    round_up(beam * m2p, LANES), value=-1)
    pops = _pad_to(jnp.asarray(pop_ids, jnp.int32), q_pad, value=-1)
    worst = _pad_to(jnp.asarray(worst, jnp.float32), q_pad).reshape(q_pad, 1)
    s, i = ke.expand_sorted_scores(
        q_lines, qc, slabs, pops, slots, worst, kk, wp=wp, qb=qb,
        interpret=_interpret(),
        metric=metric if metric is not None else TANIMOTO)
    return s[:q_n, :kk], i[:q_n, :kk]
