"""Fused gather-distance Pallas kernel — the fine-grained distance engine of
the HNSW traversal path (paper §III-C: the distance calculation unit feeding
the graph-walk priority queues).

FPGA -> TPU mapping:

* On the FPGA, beam expansion issues the popped vertices' adjacency lists to
  a fine-grained distance engine: each neighbour id becomes one HBM fetch of
  a single fingerprint, pipelined through BitCnt -> TFC at initiation
  interval 1, and the scores stream straight into the register-array PQs.
* Here the database stays in HBM (``memory_space=pl.ANY``) as lane-dense
  128-word *lines*: a fingerprint padded to ``wp`` words (a power of two
  <= 128) shares its line with ``128 // wp`` neighbours. Each grid step
  serves a block of ``qb`` queries: it reads their candidate ids from SMEM
  and starts one ``pltpu.make_async_copy`` per id, fetching the id's line
  HBM->VMEM — a true data-dependent gather, the Pallas analogue of the
  FPGA's address generator — then waits for all of them.
* The gathered lines are ANDed with the query (replicated across the line),
  popcounted, transposed to ``(128, E)`` and reduced over the sublanes of
  the id's own ``wp``-word group, giving a lane-dense ``(1, E)`` row of
  intersection counts per query; the row's BitCnt is recomputed the same
  way (cheaper than a second gather of the precomputed count).
* Validity masking: id ``-1`` marks padded neighbours and those already in
  the result queue or repeated in the expansion. Their fetch is clamped to line 0 (it must stay addressable)
  and their score is overwritten with ``-inf`` so the PQ merge downstream
  never admits them.

The kernel is jit-compatible and is launched from *inside* the traversal's
``lax.while_loop`` — one launch scores a whole beam expansion (B·2M
neighbours for every query in the batch), which is what amortises traversal
overhead vs. per-candidate dispatch.

Validated with ``interpret=True`` on CPU against ``ref.gather_tanimoto_ref``,
and compiled for v5e by ``tests/test_chip_compile.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.fingerprints import Metric, TANIMOTO, metric_from_counts
from .tanimoto_topk import LANES, NEG

_iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32)


def line_width(w: int) -> int:
    """Words a fingerprint occupies in a 128-word line (a power of two)."""
    if not 0 < w <= LANES:
        raise ValueError(f"line layout holds 1..{LANES} words per "
                         f"fingerprint, got {w}")
    return 1 << (w - 1).bit_length()


def _gather_body(ids_sm, ids_ref, q_ref, qcnt_ref, db_hbm, out_ref, buf, sem,
                 *, qb: int, e_n: int, wp: int, metric: Metric = TANIMOTO):
    per_line = LANES // wp
    n_fetch = qb * e_n

    def fetch(i):
        j, e = i // e_n, i % e_n
        line = jnp.maximum(ids_sm[j, e], 0) // per_line
        return pltpu.make_async_copy(db_hbm.at[pl.ds(line, 1)],
                                     buf.at[pl.ds(i, 1)], sem)

    def start(i, c):
        fetch(i).start()
        return c

    def wait(i, c):
        fetch(i).wait()
        return c

    jax.lax.fori_loop(0, n_fetch, start, 0)
    jax.lax.fori_loop(0, n_fetch, wait, 0)

    group = _iota((LANES, e_n), 0) // wp               # word group per sublane
    for j in range(qb):
        lines = buf[j * e_n:(j + 1) * e_n, :]          # (E, 128) gathered
        ids = ids_ref[j:j + 1, :]                      # (1, E)
        own = group == jnp.maximum(ids, 0) % per_line  # the id's word group
        x = jax.lax.population_count(lines & q_ref[j:j + 1, :]).astype(
            jnp.int32).T                               # (128, E)
        c = jax.lax.population_count(lines).astype(jnp.int32).T
        inter = jnp.sum(jnp.where(own, x, 0), axis=0, keepdims=True)
        cnt = jnp.sum(jnp.where(own, c, 0), axis=0, keepdims=True)
        s = metric_from_counts(metric, inter, qcnt_ref[j:j + 1, :], cnt)
        out_ref[j:j + 1, :] = jnp.where(ids >= 0, s, NEG)


def gather_tanimoto_scores(q_lines: jax.Array, q_cnt: jax.Array,
                           db_lines: jax.Array, ids: jax.Array, wp: int,
                           qb: int, interpret: bool = False,
                           metric: Metric = TANIMOTO) -> jax.Array:
    """q_lines (Q, 128) u32 (each query padded to ``wp`` words and repeated
    across the line), q_cnt (Q, 1) i32, db_lines (N * wp / 128, 128) u32
    (the database in line layout), ids (Q, E) i32 with E a multiple of 128
    and Q a multiple of ``qb`` (``qb`` a multiple of 8, or ``qb == Q``).

    Returns sims (Q, E) f32: sim(query_q, db[ids[q, e]]) under ``metric``
    (Tanimoto by default), with ``-inf`` wherever ``ids[q, e] < 0``. The DB
    stays in HBM; only the lines holding the E ids per query cross into
    VMEM."""
    q_n = q_lines.shape[0]
    e_n = ids.shape[1]
    assert q_n % qb == 0 and e_n % LANES == 0, (q_n, qb, e_n)
    body = functools.partial(_gather_body, qb=qb, e_n=e_n, wp=wp,
                             metric=metric)
    blk = lambda i: (i, 0)                             # noqa: E731
    return pl.pallas_call(
        body,
        grid=(q_n // qb,),
        in_specs=[
            pl.BlockSpec((qb, e_n), blk, memory_space=pltpu.SMEM),  # fetch ids
            pl.BlockSpec((qb, e_n), blk),                           # mask ids
            pl.BlockSpec((qb, LANES), blk),                         # queries
            pl.BlockSpec((qb, 1), blk),                             # counts
            pl.BlockSpec(memory_space=pl.ANY),                      # DB lines
        ],
        out_specs=pl.BlockSpec((qb, e_n), blk),
        out_shape=jax.ShapeDtypeStruct((q_n, e_n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((qb * e_n, LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA],
        interpret=interpret,
    )(ids, ids, q_lines, q_cnt, db_lines)
