"""Beam-expansion stage microbenchmark (ISSUE 4): row-gather vs
neighbour-blocked layouts of the HNSW fine-grained distance engine.

For each (Q, M) grid point it builds a synthetic base-layer adjacency, a
neighbour-blocked copy (``nbr_fps (N, 2M, W)``), and one beam expansion per
query (``pop_ids (Q, beam)`` + flattened candidate ids with a visited-mask
fraction), then times the full gather -> score -> evict-filter -> sort chain
of one traversal iteration on both layouts:

* ``jnp`` paths — the plain-XLA stages the ``jnp`` backend runs
  (``score_ids``-style scattered row gather vs ``expand_scores_jnp``).
* ``kernel`` paths (optional, ``--pallas``) — the Pallas kernels
  (``ops.gather_tanimoto`` + top-k vs the fused ``ops.expand_tanimoto_sorted``;
  interpret mode off-TPU, where the grid itself is walked in Python — the
  row kernel walks ``Q*beam*2M`` steps, the blocked kernel ``Q*beam``).

The analytic columns are layout properties, independent of the timing host:
both layouts move the same HBM bytes per query-iteration
(``beam*2M*W*4``), but the row layout issues ``beam*2M`` scattered
``W*4``-byte DMAs while the blocked layout issues ``beam`` contiguous
``2M*W*4``-byte streams — the DMA-granularity gap flagged as ROADMAP #1.

Reading the wall-clocks on a CPU host (this container):

* ``speedup_jnp`` (row jnp vs blocked jnp) sits near 1x — off-TPU the chain
  is bound by ``lax.top_k`` (XLA CPU's fastest exact sort), which both
  layouts pay identically, and XLA lowers both gathers to the same memcpy
  loop. The layout's target is the *DMA descriptor count* on real hardware,
  which the ``dma_streams_*`` columns capture analytically.
* ``speedup_vs_row_kernel`` (the row Pallas kernel vs the blocked jnp
  stage) is the wall-clock improvement over what the ``tpu``-backend row
  path actually executes on this host — the headline ``>= 2x`` point at
  (Q=64, M=16).
* kernel-vs-kernel interpret timings (``--pallas``) carry an
  ``interpret_mode: true`` flag: the Pallas interpreter's per-step cost
  scales with *operand* size, not block size, so they do not model Mosaic.

Emits ``experiments/bench/BENCH_gather.json`` (see EXPERIMENTS.md for the
schema) and prints one CSV row per grid point. ``benchmarks/roofline.py
--gather`` turns the JSON into roofline terms for the blocked stage.
"""
from __future__ import annotations

import argparse
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hnsw import (NEG_INF, _blocked_rows, _np_popcount,
                             expand_scores_jnp)
from repro.core.fingerprints import popcount
from .common import emit, get_db, timeit


def make_case(n_db: int, q_n: int, m: int, beam: int, seed: int = 0,
              masked_frac: float = 0.3):
    """One synthetic beam expansion: adjacency, blocked copy, popped beam.
    The blocked copy is packed by the engine's own `_blocked_rows`, so the
    bench always measures (and bit-checks) the layout the engine ships."""
    rng = np.random.default_rng(seed)
    m2 = 2 * m
    db = np.asarray(get_db(n_db, seed=7))
    db_cnt = _np_popcount(db)
    adj = rng.integers(0, n_db, (n_db, m2)).astype(np.int32)
    adj[rng.random(adj.shape) < 0.05] = -1              # padded edge slots
    nbr, nbr_cnt = _blocked_rows(db, db_cnt, adj)
    pop = rng.integers(0, n_db, (q_n, beam)).astype(np.int32)
    flat = adj[pop].reshape(q_n, beam * m2).copy()
    flat[rng.random(flat.shape) < masked_frac] = -1     # "visited" slots
    worst = np.full((q_n,), -np.inf, dtype=np.float32)
    return dict(db=jnp.asarray(db), db_cnt=jnp.asarray(db_cnt),
                queries=jnp.asarray(db[:q_n]),
                nbr=jnp.asarray(nbr), nbr_cnt=jnp.asarray(nbr_cnt),
                pop=jnp.asarray(pop), flat=jnp.asarray(flat),
                worst=jnp.asarray(worst))


@functools.partial(jax.jit, static_argnames=("kk",))
def _row_expand_jnp(queries, db, db_cnt, flat, worst, kk):
    """The rows-layout expansion chain exactly as search_hnsw runs it on the
    jnp backend: scattered row gather + score + evict-filter + sort."""
    q_cnt = popcount(queries)
    safe = jnp.maximum(flat, 0)
    fps = db[safe]                                       # (Q, E, W) gather
    inter = jnp.sum(jax.lax.population_count(
        queries[:, None, :] & fps).astype(jnp.int32), axis=-1)
    union = q_cnt[:, None] + db_cnt[safe] - inter
    s = jnp.where(union > 0,
                  inter.astype(jnp.float32) / union.astype(jnp.float32), 0.0)
    s = jnp.where(flat >= 0, s, NEG_INF)
    keep = s > worst[:, None]
    s = jnp.where(keep, s, NEG_INF)
    fl = jnp.where(keep, flat, -1)
    s_srt, pos = jax.lax.top_k(s, kk)
    return s_srt, jnp.take_along_axis(fl, pos, axis=1)


@functools.partial(jax.jit, static_argnames=("kk",))
def _blocked_expand_jnp(queries, nbr, nbr_cnt, pop, flat, worst, kk):
    q_cnt = popcount(queries)
    return expand_scores_jnp(queries, q_cnt, nbr, nbr_cnt, pop, flat,
                             worst, kk)


def run(n_db=20_000, qs=(16, 64, 256), ms=(8, 16, 32), beam=4, ef=64,
        pallas_points=((64, 16),), repeats=3):
    from repro.kernels import ops

    rows = []
    for q_n in qs:
        for m in ms:
            c = make_case(n_db, q_n, m, beam)
            m2 = 2 * m
            n_exp = beam * m2
            kk = min(n_exp, ef)
            w = int(c["db"].shape[1])

            t_row = timeit(lambda: _row_expand_jnp(
                c["queries"], c["db"], c["db_cnt"], c["flat"], c["worst"],
                kk), repeats=repeats)
            t_blk = timeit(lambda: _blocked_expand_jnp(
                c["queries"], c["nbr"], c["nbr_cnt"], c["pop"], c["flat"],
                c["worst"], kk), repeats=repeats)
            # the two paths must agree bit-for-bit before we compare clocks
            s_r, i_r = _row_expand_jnp(c["queries"], c["db"], c["db_cnt"],
                                       c["flat"], c["worst"], kk)
            s_b, i_b = _blocked_expand_jnp(c["queries"], c["nbr"],
                                           c["nbr_cnt"], c["pop"], c["flat"],
                                           c["worst"], kk)
            np.testing.assert_array_equal(np.asarray(s_r), np.asarray(s_b))
            np.testing.assert_array_equal(np.asarray(i_r), np.asarray(i_b))

            row = {
                "name": f"gather_q{q_n}_m{m}", "q": q_n, "m": m,
                "beam": beam, "w": w, "n_db": n_db, "kk": kk,
                "n_exp": n_exp,
                # layout analytics (per query-iteration, host-independent)
                "bytes_hbm_per_query_iter": n_exp * w * 4,
                "dma_streams_row": n_exp,            # beam*2M scattered rows
                "dma_streams_blocked": beam,         # beam contiguous blocks
                "stream_bytes_row": w * 4,
                "stream_bytes_blocked": m2 * w * 4,
                # wall-clock of the full expansion chain, jnp backend
                "us_per_call": round(t_blk * 1e6, 1),
                "us_row_jnp": round(t_row * 1e6, 1),
                "us_blocked_jnp": round(t_blk * 1e6, 1),
                "speedup_jnp": round(t_row / t_blk, 2),
            }
            if (q_n, m) in set(map(tuple, pallas_points)):
                # jit-wrapped like the engine runs them (pallas_call retraces
                # per eager call otherwise; the traversal launches from
                # inside a jitted while_loop)
                @functools.partial(jax.jit, static_argnames=("kk",))
                def row_kernel(queries, lines, flat, worst, kk):
                    s = ops.gather_tanimoto(queries, lines, flat,
                                            q_cnt=popcount(queries))
                    s = jnp.where(s > worst[:, None], s, -jnp.inf)
                    return jax.lax.top_k(s, kk)

                @functools.partial(jax.jit, static_argnames=("kk",))
                def blocked_kernel(queries, slabs, pop, flat, worst, kk):
                    return ops.expand_tanimoto_sorted(
                        queries, slabs, pop, flat, worst, kk)

                # the kernels' layouts are built once, as the engine does
                lines, slabs = ops.db_lines(c["db"]), ops.nbr_slabs(c["nbr"])
                t_rk = timeit(lambda: row_kernel(
                    c["queries"], lines, c["flat"], c["worst"], kk),
                    repeats=1, warmup=1)
                t_bk = timeit(lambda: blocked_kernel(
                    c["queries"], slabs, c["pop"],
                    c["flat"], c["worst"], kk), repeats=1, warmup=1)
                row.update(
                    us_row_kernel=round(t_rk * 1e6, 1),
                    us_blocked_kernel=round(t_bk * 1e6, 1),
                    # the headline point: the blocked stage vs what the row
                    # kernel costs on this host (the tpu-backend row path)
                    speedup_vs_row_kernel=round(t_rk / t_blk, 2),
                    speedup_kernel=round(t_rk / t_bk, 2),
                    interpret_mode=jax.default_backend() != "tpu")
            rows.append(row)
    emit("BENCH_gather", rows)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-db", type=int, default=20_000)
    ap.add_argument("--qs", type=int, nargs="+", default=[16, 64, 256])
    ap.add_argument("--ms", type=int, nargs="+", default=[8, 16, 32])
    ap.add_argument("--beam", type=int, default=4)
    ap.add_argument("--ef", type=int, default=64)
    ap.add_argument("--no-pallas", action="store_true",
                    help="skip the Pallas kernel timings (interpret mode "
                         "walks the row kernel's Q*beam*2M grid in Python)")
    ap.add_argument("--pallas-points", type=int, nargs="+", default=None,
                    help="flat (q, m) pairs to time with the kernels, "
                         "e.g. --pallas-points 64 16 16 8")
    args = ap.parse_args()
    if args.no_pallas:
        points = ()
    elif args.pallas_points is not None:
        it = iter(args.pallas_points)
        points = tuple(zip(it, it))
    else:
        points = tuple((q, m) for q in args.qs for m in args.ms
                       if (q, m) == (64, 16)) or ((args.qs[0], args.ms[0]),)
    run(n_db=args.n_db, qs=tuple(args.qs), ms=tuple(args.ms), beam=args.beam,
        ef=args.ef, pallas_points=points)


if __name__ == "__main__":
    main()
