"""Top-k primitives: the shared register-array priority queue + tile merge.

One module models the paper's two sorting structures (DESIGN.md §2, Fig. 9):

* :class:`PQ` — a fixed-capacity *register-array priority queue*: a
  descending-sorted pair of (scores, payload) lanes with ``NEG_INF`` / ``-1``
  sentinels in the empty slots. This is the TPU analogue of the paper's
  register-array PQ (even/odd compare-and-swap network, initiation interval
  1): every operation is a constant-shape vector op across the ``cap`` lanes,
  never a data-dependent resize.

  - :func:`pq_insert` — compare-and-shift insert: find the insertion lane,
    shift the tail by one, write. O(cap) lane work, O(1) sequential depth.
    When full, the worst entry falls off the end (the paper's bounded result
    set M behaviour — "evict worst").
  - :func:`pq_insert_batch` — merge a batch of E unsorted candidates: one
    sort of the batch (``lax.top_k``) followed by :func:`merge_sorted` of
    the two sorted runs (one stable sort of their concatenation that
    carries the payloads: no loop, gather or scatter).
  - :func:`pq_pop` / :func:`pq_pop_many` — pop the best 1 / B entries and
    shift the array up (the candidate set C of HNSW's SEARCH-LAYER).
  - :func:`pq_worst` — the current eviction threshold (``NEG_INF`` while the
    queue still has free lanes, so inserts always succeed until full).

* :func:`streaming_topk` — the top-K merge-sort unit of the exhaustive
  engine: a score stream is consumed tile by tile and each tile is folded
  into a running :class:`PQ` via :func:`pq_insert_batch`, so the full score
  array never exists in memory. Pure-JAX model of the fused Pallas kernel in
  ``kernels/tanimoto_topk``.

Both the HNSW traversal queues (``core/hnsw.py``) and the streaming scan are
built on the same PQ primitives — there is exactly one top-k merge
implementation in the codebase; :func:`merge_sorted_many` extends it across
database shards (the fan-out combiner of ``HNSWEngine(shards=N)``, see
docs/ARCHITECTURE.md).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = jnp.float32(-jnp.inf)


class PQ(NamedTuple):
    """Fixed-capacity register-array priority queue.

    Invariant: ``scores`` is sorted descending; empty lanes hold ``NEG_INF``
    scores and ``-1`` payloads and always form a suffix. The queue is "full"
    exactly when ``scores[-1] > NEG_INF``.
    """
    scores: jax.Array   # (cap,) float32, descending
    payload: jax.Array  # (cap,) int32

    @property
    def cap(self) -> int:
        return self.scores.shape[0]


def pq_make(cap: int) -> PQ:
    return PQ(jnp.full((cap,), NEG_INF),
              jnp.full((cap,), -1, dtype=jnp.int32))


def pq_insert(pq: PQ, score: jax.Array, payload: jax.Array) -> PQ:
    """Compare-and-shift insert (register-array style).

    Vectorised across lanes: compute the insertion position, shift the tail
    one lane down, write. When the queue is full and ``score`` is worse than
    every entry, the insert is dropped; otherwise the worst entry is evicted.
    Ties keep existing entries ahead of the new one.
    """
    cap = pq.cap
    lane = jnp.arange(cap)
    pos = jnp.sum((pq.scores >= score).astype(jnp.int32))
    shifted_s = jnp.where(lane > pos, jnp.roll(pq.scores, 1), pq.scores)
    shifted_p = jnp.where(lane > pos, jnp.roll(pq.payload, 1), pq.payload)
    new_s = jnp.where(lane == pos, score, shifted_s)
    new_p = jnp.where(lane == pos, payload, shifted_p)
    dropped = pos >= cap
    return PQ(jnp.where(dropped, pq.scores, new_s),
              jnp.where(dropped, pq.payload, new_p))


def pq_pop(pq: PQ):
    """Pop the best entry; returns ``(score, payload, rest)``."""
    s, p, rest = pq_pop_many(pq, 1)
    return s[0], p[0], rest


def pq_pop_many(pq: PQ, b: int):
    """Pop the best ``b`` entries (the beam): returns ``(scores (b,),
    payloads (b,), rest)``. Popping past the valid suffix yields sentinels."""
    b = min(b, pq.cap)
    rest = PQ(
        jnp.concatenate([pq.scores[b:], jnp.full((b,), NEG_INF)]),
        jnp.concatenate([pq.payload[b:], jnp.full((b,), -1, jnp.int32)]))
    return pq.scores[:b], pq.payload[:b], rest


def pq_worst(pq: PQ) -> jax.Array:
    """Eviction threshold: the worst retained score, ``NEG_INF`` until full."""
    return pq.scores[-1]


def merge_sorted(s_a: jax.Array, i_a: jax.Array,
                 s_b: jax.Array, i_b: jax.Array):
    """Merge two descending-sorted runs, keeping the best ``len(s_a)``.

    One stable sort of the concatenation ``[run A, run B]``, keyed on the
    negated scores and carrying the payloads as a second operand, so the
    ids move with their scores and no loop, gather or scatter is emitted
    (the merge runs twice per HNSW traversal iteration). Stability gives
    the merge's order: ties place run-A elements first and each run keeps
    its own order, so re-merging is stable w.r.t. the existing queue;
    ``NEG_INF`` / ``-1`` pads sort last.
    """
    a = s_a.shape[0]
    neg, ids = jax.lax.sort((-jnp.concatenate([s_a, s_b]),
                             jnp.concatenate([i_a, i_b])),
                            num_keys=1, is_stable=True)
    return -neg[:a], ids[:a]


def merge_sorted_many(scores: jax.Array, ids: jax.Array):
    """Merge ``S`` descending-sorted runs into the best ``cap``.

    ``scores (S, cap)`` / ``ids (S, cap)`` are stacked per-shard result runs
    (the sharded-HNSW fan-out); the reduction is a **left-leaning pairwise
    merge tree** of :func:`merge_sorted` calls — ``ceil(log2 S)`` levels,
    each level one vmapped merge. Ties keep the lower run index first
    at every level (``merge_sorted`` places run A ahead), so equal scores
    come back ordered by shard index — the deterministic cross-shard order
    the sharded engines and their parity tests rely on. Sentinel slots
    (``NEG_INF`` / ``-1`` pads, e.g. a shard that returned fewer than
    ``cap`` valid rows) lose to every real entry and can only surface when
    fewer than ``cap`` valid entries exist in total.

    Returns ``(scores (cap,), ids (cap,))``. ``S == 1`` is the identity —
    the 1-shard bit-parity contract of the sharded traversal.
    """
    s, i = scores, ids
    while s.shape[0] > 1:
        even_s, even_i = s[0::2], i[0::2]
        odd_s, odd_i = s[1::2], i[1::2]
        if odd_s.shape[0] < even_s.shape[0]:      # odd run count: carry last
            pad_s = jnp.full_like(even_s[:1], NEG_INF)
            pad_i = jnp.full_like(even_i[:1], -1)
            odd_s = jnp.concatenate([odd_s, pad_s])
            odd_i = jnp.concatenate([odd_i, pad_i])
        s, i = jax.vmap(merge_sorted)(even_s, even_i, odd_s, odd_i)
    return s[0], i[0]


def pq_insert_batch(pq: PQ, scores: jax.Array, payloads: jax.Array) -> PQ:
    """Merge a batch of E unsorted candidates into the queue.

    Sorts the batch once (only its best ``cap`` can matter), then merges
    the two sorted runs. The sort stays although :func:`merge_sorted` sorts
    too: it cuts the batch to ``cap``, so the merge sorts at most ``2 * cap``
    entries and not ``cap + E`` (E is a whole tile in
    :func:`streaming_topk`). ``NEG_INF`` scores never displace valid
    entries.
    """
    kk = min(scores.shape[0], pq.cap)
    s_sorted, pos = jax.lax.top_k(scores, kk)
    p_sorted = jnp.take(payloads, pos)
    return PQ(*merge_sorted(pq.scores, pq.payload, s_sorted, p_sorted))


def streaming_topk(scores: jax.Array, k: int, tile: int = 2048):
    """Running top-k over a score stream of shape (N,), tiled like the engine.

    Returns (values desc, indices). Pads N up to a tile multiple with -inf.
    """
    n = scores.shape[0]
    n_pad = (-n) % tile
    scores_p = jnp.pad(scores, (0, n_pad), constant_values=-jnp.inf)
    n_tiles = scores_p.shape[0] // tile

    def body(pq, t):
        tile_s = jax.lax.dynamic_slice(scores_p, (t * tile,), (tile,))
        tile_i = t * tile + jnp.arange(tile, dtype=jnp.int32)
        return pq_insert_batch(pq, tile_s, tile_i), None

    pq, _ = jax.lax.scan(body, pq_make(k), jnp.arange(n_tiles))
    return pq.scores, pq.payload
