"""Fused beam-expansion kernel over the neighbour-blocked fingerprint layout
— the streaming fine-grained distance engine of the HNSW hot path.

The DMA-granularity problem: the row-gather kernel (``kernels/gather.py``)
issues one HBM fetch per *neighbour id* — a beam expansion of ``B`` popped
nodes costs ``B * 2M`` scattered fetches per query. The paper's FPGA engine
instead streams each popped vertex's whole adjacency list through the
distance unit at initiation interval 1 (§III-C); FPScreen makes the same
move explicit: pack the fingerprints a scan will touch contiguously and the
gather-bound stage becomes a streaming one.

This kernel is that layout change plus the fused compute:

* The device graph keeps a **neighbour-blocked copy of the base layer**:
  ``nbr_fps[v] = db[base_adj[v]]`` of shape ``(N, 2M, W)`` (invalid ``-1``
  slots hold zero rows). The wrapper views each node's block as ``S``
  lane-dense 128-word lines (fingerprints padded to ``wp`` words, ``2M``
  padded so ``S`` is a multiple of 8): one popped node's entire expansion
  is one contiguous ``(S, 128)`` slab.
* Each grid step serves ``qb`` queries. The popped node ids come from SMEM;
  one ``pltpu.make_async_copy`` per (query, beam slot) streams that node's
  slab HBM->VMEM — ``beam`` DMA streams per query-iteration (vs ``beam*2M``
  row fetches), same total bytes.
* The body popcounts all ``beam*2M`` neighbours per query in-register,
  re-lays each line's word-group sums into one lane-dense score row, masks
  pad slots and those already in the result queue or repeated in the
  expansion (id ``-1`` in the flattened candidate ids) and
  sub-threshold scores (``<= worst[q]``, the result queue's eviction
  bound), and **sorts in-kernel**: ``kk`` rounds of row max plus the lowest
  lane at that max (:func:`tanimoto_topk.select_topk`, ``lax.top_k``'s
  exact order) emit the sorted run with matching ids, so the traversal's
  gather -> score -> sort -> merge chain is one launch per iteration.
* Neighbour popcounts are recounted from the streamed rows (the blocked
  copy's zero rows count 0, exactly as the stored counts).

Arithmetic is bit-identical to gathering the rows by id and scoring them
(integer popcounts, the same ``metric_from_counts`` score), which
``tests/test_expand_kernel.py`` pins.
Validated with ``interpret=True`` on CPU against ``ref.expand_sorted_ref``,
and compiled for v5e by ``tests/test_chip_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.fingerprints import Metric, TANIMOTO, metric_from_counts
from .tanimoto_topk import LANES, NEG, SUBLANES, select_topk

_iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32)


def _expand_body(pop_sm, q_ref, qcnt_ref, ids_ref, worst_ref, nbr_hbm,
                 s_out, i_out, buf, sem, *, qb: int, beam: int, n_nodes: int,
                 slab: int, wp: int, kk: int, metric: Metric = TANIMOTO):
    per_line = LANES // wp
    n_lines = beam * slab
    np_ = ids_ref.shape[1]

    def fetch(i):
        j, b = i // beam, i % beam
        node = jnp.clip(pop_sm[j, b], 0, n_nodes - 1)
        return pltpu.make_async_copy(nbr_hbm.at[node],
                                     buf.at[pl.ds(i * slab, slab)], sem)

    def start(i, c):
        fetch(i).start()
        return c

    def wait(i, c):
        fetch(i).wait()
        return c

    jax.lax.fori_loop(0, qb * beam, start, 0)
    jax.lax.fori_loop(0, qb * beam, wait, 0)

    # line l, word group g holds expansion slot l * per_line + g
    lane_group = _iota((n_lines, LANES), 1) // wp
    dest = _iota((n_lines, np_), 1) - _iota((n_lines, np_), 0) * per_line
    sub = _iota((qb, np_), 0)
    s_all = jnp.full((qb, np_), NEG, jnp.float32)
    for j in range(qb):
        lines = buf[j * n_lines:(j + 1) * n_lines, :]  # (beam*S, 128)
        x = jax.lax.population_count(lines & q_ref[j:j + 1, :]).astype(
            jnp.int32)
        c = jax.lax.population_count(lines).astype(jnp.int32)
        inter = jnp.zeros((1, np_), jnp.int32)
        cnt = jnp.zeros((1, np_), jnp.int32)
        for g in range(per_line):
            mine = lane_group == g
            xg = jnp.sum(jnp.where(mine, x, 0), axis=1, keepdims=True)
            cg = jnp.sum(jnp.where(mine, c, 0), axis=1, keepdims=True)
            at = dest == g                             # slot l*per_line + g
            inter = inter + jnp.sum(jnp.where(at, xg, 0), axis=0,
                                    keepdims=True)
            cnt = cnt + jnp.sum(jnp.where(at, cg, 0), axis=0, keepdims=True)
        s = metric_from_counts(metric, inter, qcnt_ref[j:j + 1, :], cnt)
        s_all = jnp.where(sub == j, s, s_all)
    ids = ids_ref[...]
    s_all = jnp.where(ids >= 0, s_all, NEG)            # -1 = pad/in queue/dup
    s_all = jnp.where(s_all > worst_ref[...], s_all, NEG)  # evict-worst
    ids = jnp.where(s_all > NEG, ids, -1)
    s_top, i_top = select_topk(s_all, ids, kk, s_out.shape[1])
    s_out[...] = s_top
    i_out[...] = i_top


def expand_sorted_scores(q_lines: jax.Array, q_cnt: jax.Array,
                         nbr_lines: jax.Array, pop_ids: jax.Array,
                         slot_ids: jax.Array, worst: jax.Array, kk: int,
                         wp: int, qb: int, interpret: bool = False,
                         metric: Metric = TANIMOTO):
    """q_lines (Q, 128) u32 (each query padded to ``wp`` words and repeated
    across the line), q_cnt (Q, 1) i32, nbr_lines (N, S, 128) u32 (each
    node's neighbour block in line layout, ``S`` a multiple of 8), pop_ids
    (Q, beam) i32 (popped node ids, -1 = empty pop), slot_ids (Q, E_pad) i32
    (the adjacency of the popped beam in slot order ``b * S * 128 / wp +
    r``, -1 for pad slots and those already in the result queue or
    repeated in the expansion), worst (Q, 1) f32
    (per-query eviction threshold; scores must be strictly greater to
    survive). Q must be a multiple of ``qb`` (``qb`` a multiple of 8, or
    ``qb == Q``).

    Returns ``(scores (Q, kk_pad) f32 descending, ids (Q, kk_pad) i32)``,
    ``kk_pad = round_up(kk, 128)`` — the expansion's top-``kk`` survivors,
    -inf / -1 in the empty tail."""
    q_n = q_lines.shape[0]
    n_nodes, slab, _ = nbr_lines.shape
    beam = pop_ids.shape[1]
    np_ = slot_ids.shape[1]
    kkp = -(-kk // LANES) * LANES
    assert slab % SUBLANES == 0 and np_ % LANES == 0 and q_n % qb == 0, (
        slab, np_, q_n, qb)
    assert beam * slab * (LANES // wp) <= np_ and 0 < kk <= np_, (kk, np_)
    body = functools.partial(_expand_body, qb=qb, beam=beam, n_nodes=n_nodes,
                             slab=slab, wp=wp, kk=kk, metric=metric)
    blk = lambda i: (i, 0)                             # noqa: E731
    out = pl.pallas_call(
        body,
        grid=(q_n // qb,),
        in_specs=[
            pl.BlockSpec((qb, beam), blk, memory_space=pltpu.SMEM),  # pops
            pl.BlockSpec((qb, LANES), blk),                          # queries
            pl.BlockSpec((qb, 1), blk),                              # counts
            pl.BlockSpec((qb, np_), blk),                            # slot ids
            pl.BlockSpec((qb, 1), blk),                              # worst
            pl.BlockSpec(memory_space=pl.ANY),                       # slabs
        ],
        out_specs=[
            pl.BlockSpec((qb, kkp), blk),
            pl.BlockSpec((qb, kkp), blk),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_n, kkp), jnp.float32),
            jax.ShapeDtypeStruct((q_n, kkp), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((qb * beam * slab, LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA],
        interpret=interpret,
    )(pop_ids, q_lines, q_cnt, slot_ids, worst, nbr_lines)
    return out[0], out[1]
