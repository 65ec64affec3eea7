"""Distributed similarity search: DB sharded over the mesh, hierarchical
top-k merge — the paper's multi-engine scaling mapped onto collectives
(DESIGN.md §2, last row; data layouts in docs/ARCHITECTURE.md).

Two scaling recipes share the merge primitives in ``core/topk.py``:

* **Exhaustive** (:func:`make_sharded_search`): each device scans its DB
  shard with the fused on-the-fly engine (Pallas kernel or the
  streaming-jnp equivalent), producing a local (Q, k) top-k. Local results
  are then merged: ``all_gather`` over ``data`` (intra-pod ring on ICI),
  merge-sort; for multi-pod meshes a second all_gather over ``pod``
  (cross-pod DCN) merges pod winners. This is a log-depth distributed
  version of the paper's top-k merge unit.
* **HNSW fan-out** (:func:`merge_shard_topk` + :func:`shard_devices`): the
  sharded graph engine (``core/hnsw.py`` / ``HNSWEngine(shards=N)``) runs
  one independent lock-step traversal per database shard — each with its
  own entry point and PQ queues, placed on its own device —
  and this module's rank-merge tree (``core/topk.merge_sorted_many``)
  combines the per-shard result runs into one global top-k.

Either way the wire bytes per query are shards·k·8 — independent of DB
size, which is what makes both designs scale to thousands of nodes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .fingerprints import popcount, tanimoto_scores
from .topk import NEG_INF, merge_sorted_many, streaming_topk


def _local_topk(queries, db_shard, cnt_shard, k: int, use_kernel: bool):
    if use_kernel:
        from ..kernels import ops as kops
        ids, vals = kops.tanimoto_topk(queries, db_shard, k=k,
                                       db_popcount=cnt_shard)
        return vals, ids

    def one(q):
        s = tanimoto_scores(q, db_shard, cnt_shard)
        return streaming_topk(s, k)

    vals, ids = jax.vmap(one)(queries)
    return vals, ids


def make_sharded_search(mesh, n_total: int, k: int, use_kernel: bool = False,
                        n_valid: int | None = None):
    """Build a pjit-able sharded search fn.

    DB layout: fingerprints sharded over all DP axes (('pod','data') if
    present); queries replicated; result (Q, k) replicated.

    ``n_valid`` is the unpadded database size (``shard_database`` returns
    it): ids of the zero pad rows the sharder appends are masked to ``-1``
    (sim 0) instead of leaking into the merged top-k — without the mask a
    pad row's 0-score entry can displace a truncated real row whenever ``k``
    approaches the shard size. Defaults to ``n_total`` (no masking) for
    callers that pad externally.
    """
    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    db_spec = P(dp_axes, None)
    cnt_spec = P(dp_axes)
    n_shards = 1
    for a in dp_axes:
        n_shards *= mesh.shape[a]
    shard_n = n_total // n_shards
    if n_valid is None:
        n_valid = n_total

    def local_fn(queries, db_shard, cnt_shard):
        vals, ids = _local_topk(queries, db_shard, cnt_shard, k, use_kernel)
        # global ids: offset by this shard's position along the DP axes
        idx = jax.lax.axis_index(dp_axes)
        ids = jnp.where(ids >= 0, ids + idx * shard_n, ids)
        # pad rows out of every queue: id -1, score -inf (never beats a real
        # row; restored to 0 after the merge)
        pad = ids >= n_valid
        ids = jnp.where(pad, -1, ids)
        vals = jnp.where(pad, -jnp.inf, vals)
        # hierarchical merge: gather per-shard top-k along 'data' then 'pod'
        for ax in reversed(dp_axes):            # innermost (ICI) first
            av = jax.lax.all_gather(vals, ax)   # (D, Q, k)
            ai = jax.lax.all_gather(ids, ax)
            d = av.shape[0]
            av = jnp.moveaxis(av, 0, 1).reshape(av.shape[1], d * k)
            ai = jnp.moveaxis(ai, 0, 1).reshape(ai.shape[1], d * k)
            vals, sel = jax.lax.top_k(av, k)
            ids = jnp.take_along_axis(ai, sel, axis=1)
        vals = jnp.where(ids >= 0, vals, 0.0)
        return vals, ids

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(), db_spec, cnt_spec),
                       out_specs=(P(), P()),
                       check_vma=False)
    return jax.jit(fn), db_spec, cnt_spec


def shard_devices(n_shards: int) -> list:
    """Device placement for a shard fan-out: shard ``s`` lives on local
    device ``s % n_devices``. With fewer devices than shards the assignment
    wraps (several logical shards per device — same results, serialized);
    on a single-device host every shard is local and the fan-out degrades
    to a loop. The forced-host recipe in EXPERIMENTS.md §Sharded HNSW gives
    a laptop 8 devices to place on."""
    devs = jax.devices()
    return [devs[s % len(devs)] for s in range(n_shards)]


@functools.partial(jax.jit, static_argnames=("k",))
def merge_shard_topk(sims: jax.Array, gids: jax.Array, k: int):
    """Combine per-shard top-k runs into the global top-k.

    ``sims (S, Q, kk)`` / ``gids (S, Q, kk)`` are the fan-out's per-shard
    result runs (descending scores, global ids, ``-1`` pads). Pad rows are
    masked to ``NEG_INF`` so a real 0-similarity entry always beats them,
    the rank-merge tree (``core/topk.merge_sorted_many``) reduces the S
    runs per query, and pad similarities are restored to 0 after — the same
    conventions as the single-shard traversal's output. Ties across shards
    come back ordered by shard index (the tree is left-leaning).

    Returns ``(ids (Q, k), sims (Q, k))``. With ``S == 1`` the merge is the
    identity, which is what makes a 1-shard engine bit-identical to the
    unsharded path.
    """
    s = jnp.where(gids >= 0, sims, NEG_INF)
    s_q = jnp.moveaxis(s, 0, 1)                    # (Q, S, kk)
    i_q = jnp.moveaxis(gids, 0, 1)
    ms, mi = jax.vmap(merge_sorted_many)(s_q, i_q)
    ms, mi = ms[:, :k], mi[:, :k]
    return mi, jnp.where(mi >= 0, ms, 0.0)


def shard_database(mesh, db, counts=None):
    """Place a packed fingerprint DB (padded to the shard multiple) onto the
    mesh. Returns (db_sharded, counts_sharded, n_valid)."""
    dp_axes = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    n_shards = 1
    for a in dp_axes:
        n_shards *= mesh.shape[a]
    n = db.shape[0]
    pad = (-n) % n_shards
    db = jnp.asarray(db)
    if pad:
        db = jnp.concatenate([db, jnp.zeros((pad, db.shape[1]), db.dtype)])
    if counts is None:
        counts = popcount(db)
        # force padded rows out of every top-k (score 0 beats -inf only at k>N)
    db_s = jax.device_put(db, NamedSharding(mesh, P(dp_axes, None)))
    cnt_s = jax.device_put(counts, NamedSharding(mesh, P(dp_axes)))
    return db_s, cnt_s, n
