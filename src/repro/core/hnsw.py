"""HNSW over Tanimoto similarity — paper §III-C / §IV-B.

* Graph **construction** is batched on the device (:func:`build_hnsw`): the
  library goes in in rounds of consecutive ids, each round searched against
  the graph built so far on the same lock-step traversal as search, scored
  against itself by brute force, linked with a batched Alg. 4 heuristic
  (Malkov & Yashunin) and its reverse links grouped by target. Online
  inserts (:func:`insert_hnsw`) stay on the host, one node at a time
  (:func:`_insert_node`), continuing the bulk-built graph.
* Graph **search** is the accelerated path: a batched, device-resident
  traversal engine mirroring the paper's FPGA graph engine —
  SEARCH-LAYER-TOP greedy descent (Alg. 1) followed by a lock-step batched
  SEARCH-LAYER-BASE beam search (Alg. 2):

  - two fixed-shape **register-array priority queues** per query (candidates
    C, results M) from ``core/topk.py`` — compare-and-shift / merge
    semantics, the paper's Fig. 9 structure;
  - a **fused expansion stage** scoring one whole beam expansion
    (``beam * 2M`` neighbour ids per query) per launch over a
    neighbour-packed copy of the base layer (``nbr_fps (N, 2M, W)``): one
    contiguous block per popped node streams through the fused
    gather/score/sort kernel ``kernels.ops.expand_tanimoto_sorted`` (jnp
    twin :func:`expand_scores_jnp`), ``beam`` DMA streams per
    query-iteration instead of ``beam*2M`` scattered row fetches; the row
    gather (``kernels.ops.gather_tanimoto``, jnp twin
    :func:`score_ids_jnp`) scores the entry point, the greedy descent, the
    build and the online inserts' frontiers;
  - per-query **termination** (Alg. 2 bound) with a global ``max_iters``
    budget; per-query telemetry (iterations, expansions, stop reason) comes
    back as :class:`TraversalStats`.

Distances: we work directly in *similarity* space (maximise Tanimoto), so the
candidate queue pops the most-similar element and the result queue evicts the
least-similar — sign-flipped but otherwise identical to Alg. 1/2.

Scaling past one device: the §"sharded fan-out" helpers below partition the
database round-robin into independent per-shard graphs, fan queries out to
one traversal per shard device and rank-merge the per-shard runs
(``core/distributed.merge_shard_topk``). Schemas and control flow for all of
this are documented in docs/ARCHITECTURE.md.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .fingerprints import (Metric, TANIMOTO, metric_from_counts,
                           metric_from_counts_np)
from ..obs.trace import TRACER as _TR
from .topk import NEG_INF, PQ, merge_sorted, pq_pop_many, pq_worst


# ---------------------------------------------------------------------------
# host-side helpers (numpy popcount similarity)
# ---------------------------------------------------------------------------

def _np_popcount(words: np.ndarray) -> np.ndarray:
    return np.bitwise_count(words).sum(axis=-1).astype(np.int32)


def _np_tanimoto(q: np.ndarray, db: np.ndarray, db_cnt: np.ndarray) -> np.ndarray:
    inter = np.bitwise_count(q[None, :] & db).sum(axis=-1).astype(np.int32)
    union = _np_popcount(q[None, :]) + db_cnt - inter
    return np.where(union > 0, inter / np.maximum(union, 1), 0.0).astype(np.float32)


def _np_metric(metric: Metric, q: np.ndarray, db: np.ndarray,
               db_cnt: np.ndarray) -> np.ndarray:
    """Metric-generic host scorer; the Tanimoto branch is the historical
    f64-divide path verbatim (the graph-determinism anchor)."""
    if metric.name == "tanimoto":
        return _np_tanimoto(q, db, db_cnt)
    inter = np.bitwise_count(q[None, :] & db).sum(axis=-1).astype(np.int64)
    return metric_from_counts_np(metric, inter,
                                 _np_popcount(q[None, :]).astype(np.int64),
                                 db_cnt.astype(np.int64))


# ---------------------------------------------------------------------------
# index structure
# ---------------------------------------------------------------------------

@dataclass
class HNSWIndex:
    db: np.ndarray                 # (N, W) uint32 packed fingerprints
    db_popcount: np.ndarray        # (N,) int32
    m: int                         # max degree upper layers; base layer 2M
    ef_construction: int
    entry_point: int
    max_level: int
    base_adj: np.ndarray           # (N, 2M) int32, -1 padded
    # upper layers: per level 1..max_level
    level_nodes: list = field(default_factory=list)   # [int32 array of global ids]
    level_adj: list = field(default_factory=list)     # [(n_l, M) int32 global ids]
    level_of: np.ndarray | None = None                # (N,) int8 max level per node
    seed: int = 0                  # level-draw stream; insert_hnsw continues it
    # the bulk build's largest round (rows inserted together) and the rows
    # it built: with ``seed`` they define the graph, so a rebuild of the
    # first ``bulk_rows`` rows with the same round followed by the same
    # online inserts reproduces it
    build_round: int = 0
    bulk_rows: int = 0
    max_level_cap: int = 4
    # similarity the graph was built under; searches must use the same one
    metric: Metric = TANIMOTO
    # construction-time upper layers (level -> {gid: int32 neighbour array});
    # kept so insert_hnsw can continue building without re-deriving state
    upper_dicts: list | None = field(default=None, repr=False)
    # persisted level-draw Generator: continuing it yields exactly the values
    # a from-scratch _draw_levels(seed, n_total) stream would, without the
    # O(n_total) re-draw per insert batch (rebuilt from ``seed`` when absent)
    rng: np.random.Generator | None = field(default=None, repr=False)
    # log of nodes whose base_adj row changed (inserted nodes +
    # bidirectional-link updates); engines consume suffixes of it to update
    # device copies (incl. the neighbour blocks) incrementally.
    # Bounded: when it outgrows ~2n entries it is cleared and ``dirty_epoch``
    # bumps, forcing stale consumers to full-rebuild instead of leaking host
    # memory under sustained insert streams.
    dirty_log: list | None = field(default=None, repr=False)
    dirty_epoch: int = 0
    # bumped whenever an insert batch can have touched the upper layers
    # (some inserted node drew level > 0); lets engines skip the O(cap)
    # upper-layer densify entirely on level-0-only batches (the ~(m-1)/m
    # common case)
    upper_version: int = 0
    # amortized-doubling backing arrays; db/db_popcount/base_adj/level_of are
    # views of their prefixes once insert_hnsw has run (O(1) amortized growth
    # instead of an O(n_total) concatenate per batch)
    _db_cap: np.ndarray | None = field(default=None, repr=False)
    _cnt_cap: np.ndarray | None = field(default=None, repr=False)
    _adj_cap: np.ndarray | None = field(default=None, repr=False)
    _lvl_cap: np.ndarray | None = field(default=None, repr=False)
    # the bulk build's device arrays (:class:`_BuildGraph`), handed to the
    # first :func:`to_device_graph` so the built graph is not uploaded again
    device_build: object | None = field(default=None, repr=False)

    @property
    def n(self) -> int:
        return self.db.shape[0]


def _select_heuristic(cand_ids: np.ndarray, cand_sims: np.ndarray, m: int,
                      db: np.ndarray, db_cnt: np.ndarray,
                      metric: Metric = TANIMOTO) -> np.ndarray:
    """Alg. 4 neighbour selection: keep candidate e only if it is closer to the
    query than to every already-selected neighbour (keeps long-range links).

    The candidate-to-candidate similarity matrix is computed in one vectorised
    pass (candidate sets are small: <= ef_construction rows); the selection
    loop itself is pure index bookkeeping. This is the construction hot path —
    the per-pair scoring it replaces dominated build time.
    """
    order = np.argsort(-cand_sims, kind="stable")
    cand = np.asarray(cand_ids, dtype=np.int64)[order]
    sims = np.asarray(cand_sims, dtype=np.float32)[order]
    fps = db[cand]
    cnts = db_cnt[cand].astype(np.int64)
    inter = np.bitwise_count(fps[:, None, :] & fps[None, :, :]).sum(-1)
    if metric.name == "tanimoto":
        union = cnts[:, None] + cnts[None, :] - inter
        pair = np.where(union > 0, inter / np.maximum(union, 1),
                        0.0).astype(np.float32)
    else:
        pair = metric_from_counts_np(metric, inter.astype(np.int64),
                                     cnts[:, None], cnts[None, :])

    selected: list[int] = []
    # rejected[j]: candidate j is closer to a selected neighbour than to q
    rejected = np.zeros(len(cand), dtype=bool)
    for j in range(len(cand)):
        if len(selected) >= m:
            break
        if not rejected[j]:
            selected.append(j)
            rejected |= pair[:, j] > sims
    # backfill with best remaining if heuristic selected < m (paper keeps M links)
    if len(selected) < m:
        chosen = set(selected)
        for j in range(len(cand)):
            if j not in chosen:
                selected.append(j)
                chosen.add(j)
                if len(selected) >= m:
                    break
    return cand[np.asarray(selected[:m], dtype=np.int64)].astype(np.int32)


def _search_layer_np(index_db, db_cnt, adj, q, entry_points, ef,
                     counters: dict | None = None, scorer=None,
                     metric: Metric = TANIMOTO):
    """Host-side SEARCH-LAYER-BASE used during construction and by the
    ``numpy`` engine backend. adj: dict-like callable gid -> int32 array of
    neighbour gids. ``counters`` (optional) accumulates ``evals`` (scored
    neighbours) and ``iters`` (queue pops) for the telemetry contract.
    ``scorer(q, ids) -> sims`` replaces the default numpy popcount-Tanimoto
    for the frontier batches (e.g. the device gather kernel during online
    inserts); it must be value-identical to keep graphs deterministic."""
    if scorer is None:
        def scorer(qq, ids):
            return _np_metric(metric, qq, index_db[ids], db_cnt[ids])
    visited = set(int(e) for e in entry_points)
    ep = np.asarray(list(visited), dtype=np.int32)
    sims = scorer(q, ep)
    # one order for both queues: (similarity, id), the larger the better —
    # a node is a key no other node shares, so ties never depend on the
    # order neighbours arrive in (the device build's searches keep it too)
    # candidates: best-first; results: bounded by ef, worst first
    cand = [(-s, -e) for s, e in zip(sims.tolist(), ep.tolist())]
    import heapq
    heapq.heapify(cand)
    results = list(zip(sims.tolist(), ep.tolist()))
    heapq.heapify(results)
    while cand:
        neg_s, neg_c = heapq.heappop(cand)
        c = -neg_c
        if (-neg_s, c) < results[0] and len(results) >= ef:
            break
        if counters is not None:
            counters["iters"] = counters.get("iters", 0) + 1
        neigh = adj(c)
        neigh = [int(e) for e in neigh if e >= 0 and int(e) not in visited]
        if not neigh:
            continue
        visited.update(neigh)
        na = np.asarray(neigh, dtype=np.int32)
        ns = scorer(q, na)
        if counters is not None:
            counters["evals"] = counters.get("evals", 0) + len(neigh)
        for e, s in zip(neigh, ns.tolist()):
            if len(results) < ef or (s, e) > results[0]:
                heapq.heappush(cand, (-s, -e))
                heapq.heappush(results, (s, e))
                if len(results) > ef:
                    heapq.heappop(results)
    rs = sorted(results, reverse=True)
    return (np.asarray([e for _, e in rs], dtype=np.int32),
            np.asarray([s for s, _ in rs], dtype=np.float32))


def _draw_levels(rng: np.random.Generator, n: int, m: int,
                 max_level_cap: int) -> np.ndarray:
    """Draw the next ``n`` node levels from the persisted rng stream.

    ``Generator.random(n)`` consumes the PCG64 stream sequentially, so
    drawing ``n_old`` values at build time and ``n_new`` more per insert
    batch yields exactly the levels one ``random(n_old + n_new)`` call of a
    from-scratch build would — the property the insert-then-rebuild parity
    contract rests on, now without the O(n_total) re-draw per batch.
    """
    ml = 1.0 / math.log(m)
    u = rng.random(n)
    return np.minimum(
        np.floor(-np.log(np.maximum(u, 1e-12)) * ml).astype(np.int32),
        max_level_cap)


def _level_rng(index: HNSWIndex) -> np.random.Generator:
    """The index's persisted level-draw Generator; indexes that predate the
    field (deserialized) rebuild it by fast-forwarding ``seed``'s stream
    past the ``n`` draws the existing nodes consumed."""
    if index.rng is None:
        index.rng = np.random.default_rng(index.seed)
        index.rng.random(index.n)
    return index.rng


def _insert_node(db, db_cnt, base_adj, upper, levels, i, m, ef_construction,
                 entry_point, ep_level, scorer=None, dirty=None,
                 metric: Metric = TANIMOTO):
    """Insert node ``i`` into the half-built graph (Alg. 1 descent + Alg. 2
    layer searches + Alg. 4 selection, with bidirectional link shrink).

    Online :func:`insert_hnsw` runs it one node at a time over the graph
    the bulk :func:`build_hnsw` left (whose rounds of one row and beam of
    one retrace it step for step). ``upper`` is the level -> {gid:
    neighbours} dict list; ``entry_point < 0`` means the graph is still
    empty. Returns the updated ``(entry_point, ep_level)``.

    ``dirty`` (optional list) collects every node whose *base-layer* row is
    written — the inserted node plus its bidirectionally-linked neighbours —
    so engines can refresh device adjacency copies incrementally.
    """
    m0 = base_adj.shape[1]
    l_new = int(levels[i])
    if entry_point < 0:                       # first node ever
        for l in range(1, l_new + 1):
            upper[l][i] = np.empty((0,), np.int32)
        return i, l_new

    def adj_at(level):
        if level == 0:
            return lambda gid: base_adj[gid]
        return lambda gid: upper[level].get(gid, np.empty((0,), np.int32))

    q = db[i]
    ep = np.asarray([entry_point], dtype=np.int32)
    # greedy descent through layers above l_new (Alg. 1)
    for level in range(ep_level, l_new, -1):
        ids, _ = _search_layer_np(db, db_cnt, adj_at(level), q, ep, 1,
                                  scorer=scorer, metric=metric)
        ep = ids[:1]
    # insert at layers min(ep_level, l_new) .. 0 (Alg. 2 + Alg. 4)
    for level in range(min(ep_level, l_new), -1, -1):
        ids, sims = _search_layer_np(db, db_cnt, adj_at(level), q, ep,
                                     ef_construction, scorer=scorer,
                                     metric=metric)
        mmax = m0 if level == 0 else m
        sel = _select_heuristic(ids, sims, min(m, len(ids)), db, db_cnt,
                                metric=metric)
        if level == 0:
            base_adj[i, :len(sel)] = sel
            if dirty is not None:
                dirty.append(i)
        else:
            upper[level][i] = sel.copy()
        # bidirectional links + shrink
        for e in sel:
            e = int(e)
            if level == 0:
                if dirty is not None:
                    dirty.append(e)
                row = base_adj[e]
                free = np.where(row < 0)[0]
                if len(free):
                    row[free[0]] = i
                else:
                    cand = np.concatenate([row, [i]]).astype(np.int32)
                    cs = _np_metric(metric, db[e], db[cand], db_cnt[cand])
                    base_adj[e] = _select_heuristic(cand, cs, mmax, db, db_cnt,
                                                    metric=metric)
            else:
                row = upper[level].get(e, np.empty((0,), np.int32))
                row = np.concatenate([row, [i]]).astype(np.int32)
                if len(row) > m:
                    cs = _np_metric(metric, db[e], db[row], db_cnt[row])
                    row = _select_heuristic(row, cs, m, db, db_cnt,
                                            metric=metric)
                upper[level][e] = row
        ep = ids
    if l_new > ep_level:
        entry_point, ep_level = i, l_new
        for l in range(1, l_new + 1):
            upper[l].setdefault(i, np.empty((0,), np.int32))
    return entry_point, ep_level


def _densify(upper: list, max_level: int, m: int):
    """Upper-layer dicts -> per-level (node ids, dense adjacency) arrays."""
    level_nodes, level_adj = [], []
    for l in range(1, max_level + 1):
        gids = np.asarray(sorted(upper[l].keys()), dtype=np.int32)
        adjm = np.full((len(gids), m), -1, dtype=np.int32)
        for r, g in enumerate(gids):
            row = upper[l][g][:m]
            adjm[r, :len(row)] = row
        level_nodes.append(gids)
        level_adj.append(adjm)
    return level_nodes, level_adj


def _upper_dicts_from_dense(index: HNSWIndex) -> list:
    """Rebuild the construction-time dict view from the dense per-level
    arrays (for indexes that predate ``upper_dicts``, e.g. deserialized)."""
    upper = [dict() for _ in range(index.max_level_cap + 1)]
    for l in range(1, index.max_level + 1):
        for g, row in zip(index.level_nodes[l - 1], index.level_adj[l - 1]):
            upper[l][int(g)] = row[row >= 0].astype(np.int32).copy()
    return upper


#: the largest round of the bulk build (rows inserted together), where the
#: caller names none: the faster of 1,024 and 2,048 at 131,072 rows on a
#: v5e, at the same recall (PERF.md)
DEFAULT_BUILD_ROUND = 2048
#: a round holds at most 1/ROUND_GROWTH as many rows as the graph it joins,
#: so the first rows go in one at a time and the rounds grow with the graph
ROUND_GROWTH = 8
#: the smallest padded round and link pass: rounds pad to 16, 256, 4096
#: rows, link passes to 256, 1024, 4096 ... targets, each at most the
#: largest the round size allows
ROUND_FLOOR = 16
LINK_FLOOR = 256


def build_schedule(n: int, build_round: int) -> list:
    """The bulk build's rounds over ``n`` rows: consecutive ``(a, b)`` id
    ranges, each of ``clamp(a // ROUND_GROWTH, 1, build_round)`` rows."""
    if build_round < 1:
        raise ValueError(f"build_round must be >= 1, got {build_round}")
    out, a = [], 0
    while a < n:
        b = min(n, a + max(1, min(int(build_round), a // ROUND_GROWTH)))
        out.append((a, b))
        a = b
    return out


def build_hnsw(db: np.ndarray, m: int = 16, ef_construction: int = 100,
               seed: int = 0, max_level_cap: int = 4,
               metric: Metric = TANIMOTO,
               build_round: int = DEFAULT_BUILD_ROUND) -> HNSWIndex:
    """Bulk construction on the device, in rounds (:func:`build_schedule`).

    Round ``[a, b)`` is searched against the graph of ids ``[0, a)``: the
    greedy descent and the efConstruction search of every level run on the
    lock-step traversal of :func:`search_hnsw` (:func:`auto_beam` of
    ``ef_construction`` candidates a step). Each row's
    candidates are its search results merged with the round's earlier rows
    at that level, scored by brute force; Alg. 4's heuristic picks ``m`` of
    them, batched over the round, and the reverse links go in grouped by
    target (:func:`_build_link`). Levels come from ``seed``'s stream as
    online inserts draw them, so :func:`insert_hnsw` continues the result.
    The adjacency stays on the device for the whole build; the host keeps
    the round schedule and copies the graph back once at the end. At
    ``build_round=1`` and an ``ef_construction`` under 32 (a beam of one)
    every round is one row and the build is the sequential Alg. 1/2/4
    insertion.
    """
    db = np.asarray(db, dtype=np.uint32)
    n, w = db.shape
    db_cnt = _np_popcount(db)
    rng = np.random.default_rng(seed)
    levels = _draw_levels(rng, n, m, max_level_cap)
    beam = auto_beam(ef_construction)
    rounds = build_schedule(n, build_round)
    entry_point, ep_level, bg = -1, 0, None
    with _TR.span("hnsw.build", rows=int(n), rounds=len(rounds)):
        if n:
            bg = _BuildGraph.upload(db, db_cnt, m, max_level_cap)
        for r, (a, b) in enumerate(rounds):
            with _TR.span("hnsw.build_round", round=r, rows=b - a):
                bg, entry_point, ep_level = _build_round(
                    bg, levels, a, b, entry_point, ep_level, m,
                    ef_construction, beam, metric, build_round)
        if bg is not None:
            adj = np.asarray(bg.adj)[:, :n]             # the one host copy
        else:
            adj = np.full((max_level_cap + 1, 0, 2 * m), -1, np.int32)
    max_level = int(levels.max(initial=0))
    upper = [dict() for _ in range(max_level_cap + 1)]
    for l in range(1, max_level + 1):
        for g in np.flatnonzero(levels >= l):
            row = adj[l, g, :m]
            upper[l][int(g)] = row[row >= 0].copy()
    level_nodes, level_adj = _densify(upper, max_level, m)
    return HNSWIndex(db=db, db_popcount=db_cnt, m=m,
                     ef_construction=ef_construction, entry_point=entry_point,
                     max_level=max_level, base_adj=adj[0].copy(),
                     level_nodes=level_nodes, level_adj=level_adj,
                     level_of=levels.astype(np.int8), seed=seed,
                     build_round=int(build_round), bulk_rows=int(n),
                     max_level_cap=max_level_cap, metric=metric,
                     upper_dicts=upper, rng=rng, device_build=bg)


def _ensure_capacity(index: HNSWIndex, n_total: int) -> None:
    """Amortized-doubling growth: make db / db_popcount / base_adj / level_of
    views into power-of-two backing arrays with room for ``n_total`` rows.

    One copy per doubling instead of an O(n_total) ``np.concatenate`` per
    insert batch (the ROADMAP known cost). Values are untouched, so parity
    with a from-scratch rebuild is preserved bit-for-bit. The check against
    ``.base`` re-seeds the backing arrays whenever the caller swapped the
    public arrays out from under us (fresh build, deserialized index).
    """
    cap_arr = index._db_cap
    if (cap_arr is not None and cap_arr.shape[0] >= n_total
            and index.db.base is cap_arr):
        return
    cap = 1 << max(int(n_total - 1).bit_length(), 4)
    n, w = index.db.shape
    m2 = index.base_adj.shape[1]
    index._db_cap = np.zeros((cap, w), dtype=np.uint32)
    index._db_cap[:n] = index.db
    index._cnt_cap = np.zeros((cap,), dtype=np.int32)
    index._cnt_cap[:n] = index.db_popcount
    index._adj_cap = np.full((cap, m2), -1, dtype=np.int32)
    index._adj_cap[:n] = index.base_adj
    index._lvl_cap = np.zeros((cap,), dtype=np.int8)
    index._lvl_cap[:n] = index.level_of


def insert_hnsw(index: HNSWIndex, new_fps: np.ndarray,
                scorer_factory=None) -> np.ndarray:
    """Batched incremental inserts: grow ``index`` in place by ``new_fps``.

    Levels continue the seed's rng stream (the persisted Generator,
    :func:`_draw_levels`) and every node runs :func:`_insert_node`, one at
    a time, so after any number of insert batches the index is
    **identical** to the same bulk build over the same first rows followed
    by the same rows inserted in one batch — the engine parity contract
    (where the bulk build stopped is part of what defines the graph).
    Array growth is amortized-doubling (:func:`_ensure_capacity`):
    appending a batch costs O(batch), not O(n_total).

    ``scorer_factory(db, db_cnt) -> scorer(q, ids) -> sims`` swaps the
    frontier distance stage; engines pass the Pallas ``gather_tanimoto``
    wrapper to score insert frontiers on device (first cut of the ROADMAP
    device-side-construction item — the kernel's f32 arithmetic is
    value-identical to the host scorer for <=2048-bit prints, keeping the
    graph deterministic). Base-layer rows touched by the batch are appended
    to ``index.dirty_log`` for incremental device-copy refresh (engines
    track their own consumed offset). Returns the new nodes' global ids.
    """
    new_fps = np.atleast_2d(np.asarray(new_fps, dtype=np.uint32))
    n_new = new_fps.shape[0]
    n_old = index.n
    if n_new == 0:
        return np.empty((0,), dtype=np.int64)
    n_total = n_old + n_new
    _ensure_capacity(index, n_total)
    index._db_cap[n_old:n_total] = new_fps
    index._cnt_cap[n_old:n_total] = _np_popcount(new_fps)
    index._adj_cap[n_old:n_total] = -1
    levels_new = _draw_levels(_level_rng(index), n_new, index.m,
                              index.max_level_cap)
    index._lvl_cap[n_old:n_total] = levels_new
    index.db = index._db_cap[:n_total]
    index.db_popcount = index._cnt_cap[:n_total]
    index.base_adj = index._adj_cap[:n_total]
    index.level_of = index._lvl_cap[:n_total]
    if index.upper_dicts is None:
        index.upper_dicts = _upper_dicts_from_dense(index)
    if index.dirty_log is None:
        index.dirty_log = []
    upper = index.upper_dicts
    scorer = (scorer_factory(index.db, index.db_popcount)
              if scorer_factory is not None else None)
    metric = getattr(index, "metric", TANIMOTO)
    ep, epl = int(index.entry_point), int(index.max_level)
    for i in range(n_old, n_total):
        ep, epl = _insert_node(index.db, index.db_popcount, index.base_adj,
                               upper, index.level_of, i, index.m,
                               index.ef_construction, ep, epl, scorer=scorer,
                               dirty=index.dirty_log, metric=metric)
    index.entry_point, index.max_level = int(ep), int(epl)
    index.level_nodes, index.level_adj = _densify(upper, index.max_level,
                                                  index.m)
    if bool((levels_new > 0).any()):
        # only a level>0 node can write upper-layer rows (_insert_node's
        # upper mutations all sit under ``level >= 1`` of the new node)
        index.upper_version += 1
    if len(index.dirty_log) > max(1024, 2 * n_total):
        index.dirty_log = []
        index.dirty_epoch += 1
    return np.arange(n_old, n_total, dtype=np.int64)


def auto_beam(ef_search: int) -> int:
    """Beam width from ``ef_search`` (ROADMAP telemetry note: B=4 cuts
    lock-step iterations ~3.7x at equal recall for ef=64). Scales B with ef
    so small-ef searches don't waste expansions, clamped to [1, 8]."""
    return max(1, min(8, int(ef_search) // 16))


# ---------------------------------------------------------------------------
# accelerated batched search (JAX) — the paper's graph traversal engine
# ---------------------------------------------------------------------------

class HNSWDeviceGraph(NamedTuple):
    """Device-resident, constant-shape view of the index for the JAX engine.

    Besides the rows and the adjacency it carries the **neighbour-blocked
    copy of the base layer** the expansion stage streams: ``nbr_fps[v] =
    db[base_adj[v]]`` with zero rows for ``-1`` slots, plus the matching
    popcounts — one popped node's whole expansion is a single contiguous
    ``2M*W``-word HBM stream, at the HBM cost of one extra ``2M*W``-word
    copy of the base layer per node.

    A graph for the Pallas kernels (``kernel=True``) holds the database also
    in the gather kernel's line layout (``db_lines``), and the neighbour
    blocks in the expand kernel's slab layout (``nbr_slabs``) in place of
    ``nbr_fps``/``nbr_cnt`` — built once here, so no traversal relays them
    out per call.
    """
    db: jax.Array             # (N, W) uint32
    db_popcount: jax.Array    # (N,) int32
    base_adj: jax.Array       # (N, 2M) int32
    upper_adj: jax.Array      # (L, N, M) int32 — dense per-level adjacency (-1 pad)
    entry_point: jax.Array    # () int32
    max_level: int
    nbr_fps: jax.Array | None = None   # (N, 2M, W) uint32 — jnp only
    nbr_cnt: jax.Array | None = None   # (N, 2M) int32 — jnp only
    db_lines: jax.Array | None = None  # ops.db_lines(db) — kernel only
    nbr_slabs: jax.Array | None = None  # ops.nbr_slabs(nbr_fps) — kernel only


def _dense_upper(index: HNSWIndex, cap: int) -> np.ndarray:
    """Dense (L, cap, M) upper-layer adjacency (-1 padded)."""
    L = max(index.max_level, 0)
    upper = np.full((max(L, 1), cap, index.m), -1, dtype=np.int32)
    for l in range(1, L + 1):
        gids = index.level_nodes[l - 1]
        upper[l - 1, gids] = index.level_adj[l - 1]
    return upper


def _blocked_rows(db: np.ndarray, db_cnt: np.ndarray, adj: np.ndarray):
    """Neighbour-blocked rows for ``adj`` (any (R, 2M) id slice): gathers the
    referenced fingerprints/popcounts, zeroing ``-1`` slots."""
    safe = np.maximum(adj, 0)
    fps = db[safe].copy()                              # (R, 2M, W)
    fps[adj < 0] = 0
    cnt = db_cnt[safe].astype(np.int32)
    cnt[adj < 0] = 0
    return fps, cnt


def to_device_graph(index: HNSWIndex, capacity: int | None = None,
                    kernel: bool = False) -> HNSWDeviceGraph:
    """Densify the index for the device engine. ``capacity`` (>= n) pads the
    node dimension — pad rows are zero fingerprints with no edges, so they
    are unreachable and the traversal is unaffected. Engines pad to a power
    of two so online inserts below the capacity reuse compiled traversals.
    The graph carries the neighbour-blocked base-layer copy, and with
    ``kernel=True`` the Pallas kernels' layouts of the database and of that
    copy (:class:`HNSWDeviceGraph` docstring)."""
    L = max(index.max_level, 0)
    n = index.n
    cap = n if capacity is None else max(int(capacity), n)
    bg = index.device_build
    if bg is not None and bg.db.shape[0] == cap and n == index.bulk_rows:
        return _graph_from_build(bg, index, kernel)
    upper = _dense_upper(index, cap)
    db = np.zeros((cap, index.db.shape[1]), dtype=np.uint32)
    db[:n] = index.db
    cnt = np.zeros((cap,), dtype=np.int32)
    cnt[:n] = index.db_popcount
    base = np.full((cap, index.base_adj.shape[1]), -1, dtype=np.int32)
    base[:n] = index.base_adj
    nbr_fps = nbr_cnt = db_lines = nbr_slabs = None
    nbr_fps_np, nbr_cnt_np = _blocked_rows(db, cnt, base)
    if kernel:
        from ..kernels import ops as kops
        db_lines = jnp.asarray(kops.db_lines(db))
        nbr_slabs = jnp.asarray(kops.nbr_slabs(nbr_fps_np))
    else:
        nbr_fps = jnp.asarray(nbr_fps_np)
        nbr_cnt = jnp.asarray(nbr_cnt_np)
    return HNSWDeviceGraph(
        db=jnp.asarray(db), db_popcount=jnp.asarray(cnt),
        base_adj=jnp.asarray(base), upper_adj=jnp.asarray(upper),
        entry_point=jnp.int32(index.entry_point), max_level=L,
        nbr_fps=nbr_fps, nbr_cnt=nbr_cnt, db_lines=db_lines,
        nbr_slabs=nbr_slabs)


def _graph_from_build(bg, index: HNSWIndex,
                      kernel: bool) -> HNSWDeviceGraph:
    """:func:`to_device_graph` of a freshly built index from the build's own
    device arrays: the same graph, with no upload."""
    L = max(index.max_level, 0)
    base = bg.adj[0]
    db_lines = nbr_slabs = None
    safe = jnp.maximum(base, 0)
    nbr_fps = jnp.where((base >= 0)[:, :, None], bg.db[safe], 0)
    nbr_cnt = jnp.where(base >= 0, bg.db_popcount[safe], 0)
    if kernel:
        from ..kernels import ops as kops
        db_lines = kops.db_lines(bg.db)
        nbr_slabs = kops.nbr_slabs(nbr_fps)
        nbr_fps = nbr_cnt = None
    return HNSWDeviceGraph(
        db=bg.db, db_popcount=bg.db_popcount, base_adj=base,
        upper_adj=bg.adj[1:max(L, 1) + 1, :, :index.m],
        entry_point=jnp.int32(index.entry_point), max_level=L,
        nbr_fps=nbr_fps, nbr_cnt=nbr_cnt, db_lines=db_lines,
        nbr_slabs=nbr_slabs)


def score_ids_jnp(queries: jax.Array, q_cnt: jax.Array, g: HNSWDeviceGraph,
                  ids: jax.Array, metric: Metric = TANIMOTO) -> jax.Array:
    """Batched gather-distance stage: (Q, W) x (Q, E) ids -> (Q, E) sims.

    Plain-jnp twin of the Pallas ``kernels.ops.gather_tanimoto`` kernel —
    identical arithmetic (popcount similarity via ``metric_from_counts``,
    -inf for id -1), used by the ``"jnp"`` engine backend.
    """
    return _score_rows(g.db, g.db_popcount, queries, q_cnt, ids, metric)


def _score_rows(db, db_cnt, queries, q_cnt, ids, metric: Metric = TANIMOTO):
    """:func:`score_ids_jnp` over bare ``db``/``db_cnt`` arrays."""
    safe = jnp.maximum(ids, 0)
    fps = db[safe]                                      # (Q, E, W)
    inter = jnp.sum(jax.lax.population_count(
        queries[:, None, :] & fps).astype(jnp.int32), axis=-1)
    s = metric_from_counts(metric, inter, q_cnt[:, None], db_cnt[safe])
    return jnp.where(ids >= 0, s, NEG_INF)


def expand_scores_jnp(queries: jax.Array, q_cnt: jax.Array,
                      nbr_fps: jax.Array, nbr_cnt: jax.Array,
                      pop_ids: jax.Array, flat_ids: jax.Array,
                      worst: jax.Array, kk: int, metric: Metric = TANIMOTO):
    """Plain-jnp twin of the fused expand kernel (``kernels/expand.py``):
    gather ``beam`` contiguous neighbour blocks per query from the
    neighbour-blocked copy, score, mask ``-1``/sub-threshold slots, return
    the top-``kk`` sorted run. Identical arithmetic to the kernel and, bit
    for bit, to gathering the rows by id, scoring them, filtering at
    ``worst`` and sorting (``tests/test_expand_kernel.py``).
    """
    q_n = queries.shape[0]
    safe = jnp.maximum(pop_ids, 0)
    blk = nbr_fps[safe]                                 # (Q, B, 2M, W)
    inter = jnp.sum(jax.lax.population_count(
        queries[:, None, None, :] & blk).astype(jnp.int32), axis=-1)
    s = metric_from_counts(metric, inter, q_cnt[:, None, None], nbr_cnt[safe])
    s = s.reshape(q_n, -1)
    s = jnp.where(flat_ids >= 0, s, NEG_INF)
    s = jnp.where(s > worst[:, None], s, NEG_INF)
    ids = jnp.where(s > NEG_INF, flat_ids, -1)
    s_srt, pos = jax.lax.top_k(s, kk)
    return s_srt, jnp.take_along_axis(ids, pos, axis=1)


def _greedy(q, q_cnt, db, db_cnt, adj, start, metric: Metric = TANIMOTO,
            id_ties: bool = False):
    """SEARCH-LAYER-TOP (Alg. 1): a greedy walk over one upper level's
    adjacency ``adj`` from ``start`` to a local best; returns ``(node,
    similarity)``. A neighbour replaces the current node only when
    strictly more similar, the first such in row order; with ``id_ties``
    (the build), the best by (similarity, id) as the host search orders."""
    def sims(ids):
        return _score_rows(db, db_cnt, q[None], q_cnt[None], ids[None],
                           metric)[0]

    def cond(state):
        cur, cur_sim, moved = state
        return moved

    def body(state):
        cur, cur_sim, _ = state
        neigh = adj[cur]                                   # (M,)
        s = sims(neigh)
        if id_ties:
            top = jnp.max(s)
            j = jnp.argmax(jnp.where(s == top, neigh, -1))
            better = (s[j] > cur_sim) | ((s[j] == cur_sim) & (neigh[j] > cur))
        else:
            j = jnp.argmax(s)
            better = s[j] > cur_sim
        return (jnp.where(better, neigh[j], cur),
                jnp.where(better, s[j], cur_sim), better)

    s0 = sims(start[None])[0]
    cur, sim, _ = jax.lax.while_loop(cond, body, (start, s0, jnp.bool_(True)))
    return cur, sim


# Early-termination reasons (TraversalStats.reason values).
REASON_CONVERGED = 0   # best remaining candidate worse than worst result
REASON_MAX_ITERS = 1   # iteration budget exhausted before convergence


class TraversalStats(NamedTuple):
    """Per-query telemetry from one batched traversal."""
    iters: jax.Array        # (Q,) int32 — beam-expansion iterations executed
    expansions: jax.Array   # (Q,) int32 — candidates actually expanded
    reason: jax.Array       # (Q,) int32 — REASON_CONVERGED / REASON_MAX_ITERS
    deduped: jax.Array      # (Q,) int32 — neighbour slots masked as seen


def stats_summary(iters, expansions, reason, deduped, m2: int) -> dict:
    """Fold (already stacked/summed-ready) per-query traversal telemetry
    into the scalar totals dict shared by :attr:`HNSWEngine.stats` and the
    ``hnsw.search`` trace-span args (ISSUE 8): iteration/expansion totals,
    neighbour evaluations (``expansions * 2M``), the slots the dedup masked
    and termination-reason counts. Accepts device arrays or numpy; always
    returns plain ints."""
    iters = np.asarray(iters)
    expansions = np.asarray(expansions)
    reason = np.asarray(reason)
    return {
        "iters": int(iters.sum()),
        "expansions": int(expansions.sum()),
        "neighbour_evals": int(expansions.sum()) * int(m2),
        "deduped": int(np.asarray(deduped).sum()),
        "converged": int((reason == REASON_CONVERGED).sum()),
        "max_iters_hit": int((reason == REASON_MAX_ITERS).sum()),
    }


def search_hnsw(g: HNSWDeviceGraph, queries: jax.Array, k: int, ef: int,
                max_iters: int | None = None, beam: int = 1, score_fn=None,
                expand_fn=None, metric: Metric = TANIMOTO):
    """Batched device-resident KNN search over the base layer.

    The whole query batch traverses in lock-step inside one
    ``lax.while_loop``: per iteration each still-active query pops its best
    ``beam`` candidates from the candidate queue C, gathers their base-layer
    adjacency (``beam * 2M`` neighbour ids), scores all of them in ONE
    expansion launch (``expand_fn``), and merges the scored batch
    into both fixed-shape register-array queues (C and the result set M,
    ``core/topk.py``). A neighbour already in the result queue or repeated in
    the expansion is masked, so no node enters a queue twice; queries
    terminate individually (Alg. 2 bound: best candidate worse than
    the worst retained result) and finished queries ride along masked until
    the last one converges or ``max_iters`` is hit.

    queries: (Q, W) uint32. Returns ``(ids (Q, k), sims (Q, k), stats)``
    with ids descending by similarity (-1 pads) and :class:`TraversalStats`
    device arrays.

    ``score_fn(queries, q_cnt, ids) -> sims`` scores the entry points;
    default is the jnp gather (:func:`score_ids_jnp`), engines pass the
    Pallas ``gather_tanimoto`` kernel for the ``tpu`` backend.

    ``expand_fn(queries, q_cnt, pop_ids, flat_ids, worst, kk) ->
    (scores (Q, kk) desc, ids (Q, kk))`` is the whole gather -> score ->
    evict-filter -> sort stage of one beam expansion (``pop_ids (Q, beam)``
    are the popped node ids, ``flat_ids (Q, beam*2M)`` their adjacency with
    -1 for pad slots and those already in the result queue or repeated in
    the expansion, ``worst (Q,)`` the result queues' eviction
    bounds) over the graph's neighbour blocks; default is the jnp twin
    (:func:`expand_scores_jnp`) over ``g.nbr_fps``/``g.nbr_cnt``, engines
    pass the fused Pallas kernel (``kernels.ops.expand_tanimoto_sorted``)
    over ``g.nbr_slabs`` for the ``tpu`` backend. The emitted run is
    sorted, so the queues merge it directly (one launch per iteration).
    """
    ef = max(ef, k)
    if score_fn is None:
        def score_fn(qs, qc, ids):
            return score_ids_jnp(qs, qc, g, ids, metric=metric)
    if expand_fn is None:
        def expand_fn(qs, qc, pop_ids, flat_ids, worst, kk):
            return expand_scores_jnp(qs, qc, g.nbr_fps, g.nbr_cnt, pop_ids,
                                     flat_ids, worst, kk, metric=metric)
    q_cnt = jnp.sum(jax.lax.population_count(queries).astype(jnp.int32), -1)

    # greedy descent through the upper layers (Alg. 1), vmapped per query
    def descend(q, qc):
        ep = g.entry_point
        for level in range(g.max_level, 0, -1):          # static unroll
            ep = _greedy(q, qc, g.db, g.db_popcount, g.upper_adj[level - 1],
                         ep, metric)[0]
        return ep

    ep = jax.vmap(descend)(queries, q_cnt)               # (Q,)
    ep_sim = score_fn(queries, q_cnt, ep[:, None])[:, 0]
    res, active, iters, expans, dedup = _beam_search(
        g.base_adj, queries, q_cnt, ep_sim[:, None], ep[:, None], ef, beam,
        max_iters, score_fn, expand_fn)
    reason = jnp.where(active, REASON_MAX_ITERS, REASON_CONVERGED)
    ids = res.payload[:, :k]
    sims = jnp.where(ids >= 0, res.scores[:, :k], 0.0)
    return ids, sims, TraversalStats(iters=iters, expansions=expans,
                                     reason=reason.astype(jnp.int32),
                                     deduped=dedup)


def _keyed(s, i):
    """Sort rows of (scores, ids) by (score, id) descending; ``NEG_INF``
    entries last."""
    s, i = jax.lax.sort((-s, -i), dimension=1, num_keys=2)
    return -s, -i


def _above(s, i, ws, wi):
    """(s, i) > (ws, wi) in the (score, id) order."""
    return (s > ws) | ((s == ws) & (i > wi))


def _beam_search(adj, queries, q_cnt, init_s, init_i, ef: int, beam: int,
                 max_iters, score_fn, expand_fn=None, id_ties: bool = False):
    """SEARCH-LAYER (Alg. 2) over one layer's adjacency ``adj (N, D)``, in
    lock-step for the batch: the loop of :func:`search_hnsw`, from each
    query's entry list ``init_s``/``init_i (Q, E0)`` (descending, ``-1`` /
    ``NEG_INF`` padded; an empty list leaves the query idle). Returns the
    result queue (the best ``ef`` found, descending), the per-query
    still-active flags, iterations, expansions and deduped slots.

    No visited set: a neighbour slot is masked (``-1``) when its id is
    already in the result queue or repeated at an earlier slot of the same
    expansion (the first wins). Every other node scored before sits at or
    below today's worst result, which never falls: it was filtered at the
    worst of its time, cut from a run whose kept entries were at least as
    good, or evicted, and a tie never evicts a queue entry (the merges put
    the queue's entries first; ``_keyed`` keys are unique). So the strict
    filter (``s > worst``; ``_above`` under ``id_ties``) drops it again,
    and the queues, the stats and the answers are those of an exactly-once
    visited check, with no state of size N.

    ``id_ties`` orders both queues by (similarity, id) as the host search
    does (:func:`_search_layer_np`): a node's key is its own, so no tie
    depends on the order neighbours arrive in. The build uses it, so a
    build of one row a round retraces the sequential insertion; the search
    path keeps the arrival order, which the kernels' runs give: each
    iteration's sorted run, from ``expand_fn`` (:func:`search_hnsw`), folds
    into both queues by ``merge_sorted``, one stable sort of ``[queue,
    run]`` that carries the ids, so ties keep the queue's entries first,
    with no loop, gather or scatter. ``score_fn`` scores the build's
    expansions (``id_ties``) and is not called on the search path."""
    beam = max(1, min(beam, ef))
    if max_iters is None:
        max_iters = 4 * ef + 16
    q_n = queries.shape[0]
    m2 = adj.shape[1]
    n_exp = beam * m2                                   # neighbours per launch
    kk = min(n_exp, ef)                                 # sorted-run width

    e0 = init_i.shape[1]

    # C (candidates, pop-best) and M (results, evict-worst): batched
    # register-array queues (core/topk.py PQ invariants), one row per query —
    # every queue op below is the vmapped scalar PQ primitive.
    pad = ef - e0
    cand = PQ(jnp.concatenate(
                  [init_s, jnp.full((q_n, pad), NEG_INF)], axis=1),
              jnp.concatenate(
                  [init_i.astype(jnp.int32),
                   jnp.full((q_n, pad), -1, jnp.int32)], axis=1))
    res = cand
    if id_ties:
        cand = res = PQ(*_keyed(cand.scores, cand.payload))
    earlier = jnp.tri(n_exp, k=-1, dtype=bool)          # slot j < slot i

    def where_rows(mask, new, old):
        """Per-query select between two batched PQ pytrees."""
        return jax.tree.map(
            lambda a, b: jnp.where(mask[:, None], a, b), new, old)

    state = (cand, res,
             jnp.ones((q_n,), bool),                     # active
             jnp.zeros((q_n,), jnp.int32),               # iters
             jnp.zeros((q_n,), jnp.int32),               # expansions
             jnp.zeros((q_n,), jnp.int32),               # deduped slots
             jnp.int32(0))                               # lock-step counter

    def cond(st):
        active, it = st[2], st[6]
        return jnp.logical_and(jnp.any(active), it < max_iters)

    def body(st):
        cand, res, active, iters, expans, dedup, it = st
        worst = jax.vmap(pq_worst)(res)                  # eviction threshold
        worst_i = res.payload[:, -1]

        def kept(s, i):
            """Not below the worst retained result."""
            if id_ties:
                return ~_above(worst[:, None], worst_i[:, None], s, i)
            return s >= worst[:, None]
        # Alg. 2 termination, per query: stop when the best candidate cannot
        # beat the worst retained result (monotone -> inactive stays inactive)
        go = jnp.logical_and(active, jnp.logical_and(
            cand.scores[:, 0] > NEG_INF,
            kept(cand.scores[:, :1], cand.payload[:, :1])[:, 0]))

        # pop the beam: best B candidates, queue shifts up by B
        pop_s, pop_i, popped = jax.vmap(
            lambda pq: pq_pop_many(pq, beam))(cand)
        valid_pop = (pop_s > NEG_INF) & kept(pop_s, pop_i) & go[:, None]
        cand = where_rows(go, popped, cand)

        # beam expansion: adjacency gather, (Q, beam*2M) in slot order
        nb = adj[jnp.maximum(pop_i, 0)]
        nb = jnp.where(valid_pop[:, :, None], nb, -1).reshape(q_n, n_exp)

        # dedup: mask a slot whose id is already in the result queue or
        # repeated at an earlier slot of this expansion (the first wins)
        in_res = jnp.any(nb[:, :, None] == res.payload[:, None, :], axis=2)
        repeat = jnp.any((nb[:, :, None] == nb[:, None, :]) & earlier, axis=2)
        dup = (nb >= 0) & (in_res | repeat)
        flat = jnp.where(dup, -1, nb)

        # fused expansion stage: gather + score + evict-filter + sort for
        # all B*2M neighbours per query in one launch; the sorted run
        # merges into both queues (Fig. 9)
        if id_ties:
            s = score_fn(queries, q_cnt, flat)
            keep = _above(s, flat, worst[:, None], worst_i[:, None])
            s = jnp.where(keep, s, NEG_INF)
            fl = jnp.where(keep, flat, -1)

            def merged(pq):
                rs, ri = _keyed(jnp.concatenate([pq.scores, s], 1),
                                jnp.concatenate([pq.payload, fl], 1))
                return PQ(rs[:, :ef], ri[:, :ef])
            res = where_rows(go, merged(res), res)
            cand = where_rows(go, merged(cand), cand)
        else:
            s_srt, i_srt = expand_fn(queries, q_cnt, pop_i, flat, worst, kk)
            vmerge = jax.vmap(
                lambda pq, ms, mi: PQ(*merge_sorted(pq.scores, pq.payload,
                                                    ms, mi)))
            res = where_rows(go, vmerge(res, s_srt, i_srt), res)
            cand = where_rows(go, vmerge(cand, s_srt, i_srt), cand)

        iters = iters + go.astype(jnp.int32)
        expans = expans + jnp.sum(valid_pop, axis=1).astype(jnp.int32)
        dedup = dedup + jnp.sum(dup, axis=1).astype(jnp.int32)
        return cand, res, go, iters, expans, dedup, it + 1

    _, res, active, iters, expans, dedup, _ = jax.lax.while_loop(
        cond, body, state)
    return res, active, iters, expans, dedup


# ---------------------------------------------------------------------------
# bulk build on the device (build_hnsw's rounds)
# ---------------------------------------------------------------------------

def _bucket(n: int, floor: int, step: int, top: int) -> int:
    """The padded size of a round or a link pass of ``n``: the smallest of
    ``floor * step**j`` that holds it, but no more than ``top`` (a power of
    two at least ``n``), so a build compiles a handful of shapes."""
    b = floor
    while b < n:
        b *= step
    top = max(top, 1 << max(n - 1, 0).bit_length())
    return min(b, top)


class _BuildGraph(NamedTuple):
    """The graph under construction, on the device: the library, its
    popcounts and every level's adjacency in one ``(cap_levels + 1, cap,
    2M)`` array (level 0 the base layer; an upper level uses its first M
    columns), ``cap`` the next power of two above the library."""
    db: jax.Array        # (cap, W) uint32
    db_popcount: jax.Array  # (cap,) int32
    adj: jax.Array       # (L + 1, cap, 2M) int32, -1 padded

    @classmethod
    def upload(cls, db, db_cnt, m: int, max_level_cap: int):
        n, w = db.shape
        cap = 1 if n <= 1 else 1 << (n - 1).bit_length()
        dbp = np.zeros((cap, w), np.uint32)
        dbp[:n] = db
        cnt = np.zeros((cap,), np.int32)
        cnt[:n] = db_cnt
        return cls(jnp.asarray(dbp), jnp.asarray(cnt),
                   jnp.full((max_level_cap + 1, cap, 2 * m), -1, jnp.int32))


def _padded(ids: np.ndarray, size: int) -> np.ndarray:
    out = np.full((size,), -1, np.int32)
    out[:len(ids)] = ids
    return out


@functools.partial(jax.jit, static_argnames=("ef", "metric"))
def _build_entry(db, cnt, rows, entry_point, ef: int, metric: Metric):
    """Each round row's entry list: the graph's entry point and its score,
    then ``-1`` pads."""
    safe = jnp.maximum(rows, 0)
    ep = jnp.full((rows.shape[0], 1), entry_point, jnp.int32)
    s = _score_rows(db, cnt, db[safe], cnt[safe], ep, metric)
    return (jnp.full((rows.shape[0], ef), NEG_INF).at[:, :1].set(s),
            jnp.full((rows.shape[0], ef), -1, jnp.int32).at[:, :1].set(ep))


@functools.partial(jax.jit, static_argnames=("m", "metric"))
def _build_descend(adj, lev, db, cnt, rows, e_s, e_i, move, m: int,
                   metric: Metric):
    """One upper level's greedy step (Alg. 1) for the round rows whose own
    level is below it: their single entry (column 0) walks to its local
    best over the level's M-wide adjacency."""
    fps = db[jnp.maximum(rows, 0)]
    qc = cnt[jnp.maximum(rows, 0)]
    adj_l = adj[lev, :, :m]
    cur, sim = jax.vmap(lambda q, c, st: _greedy(
        q, c, db, cnt, adj_l, st, metric, id_ties=True))(fps, qc, e_i[:, 0])
    return (e_s.at[:, 0].set(jnp.where(move, sim, e_s[:, 0])),
            e_i.at[:, 0].set(jnp.where(move, cur, e_i[:, 0])))


@functools.partial(jax.jit, static_argnames=("ef", "beam", "metric"))
def _build_search(adj, lev, db, cnt, rows, e_s, e_i, pos, ef: int,
                  beam: int, metric: Metric):
    """The efConstruction search of one level for the round rows at
    positions ``pos`` (``-1`` pads idle), from their entry lists
    ``e_s``/``e_i``: :func:`_beam_search`, the search path's own loop.
    Returns the entry lists with those rows' results written in (the next
    level's entries) and the results."""
    at = jnp.maximum(pos, 0)
    sub = jnp.where(pos >= 0, rows[at], -1)
    safe = jnp.maximum(sub, 0)
    init_i = jnp.where(sub[:, None] >= 0, e_i[at], -1)
    init_s = jnp.where(init_i >= 0, e_s[at], NEG_INF)

    def score_fn(qs, qcnt, ids):
        return _score_rows(db, cnt, qs, qcnt, ids, metric)
    res, _, _, _, _ = _beam_search(adj[lev], db[safe], cnt[safe], init_s,
                                init_i, ef, beam, None, score_fn,
                                id_ties=True)
    dest = jnp.where(pos >= 0, pos, rows.shape[0])
    return (e_s.at[dest].set(res.scores, mode="drop"),
            e_i.at[dest].set(res.payload, mode="drop"),
            res.scores, res.payload)


def _heuristic(db, cnt, cand_i, cand_s, m, width: int, metric: Metric):
    """Alg. 4 over a batch of candidate lists, each already in the order the
    heuristic walks them (descending similarity, the caller's tie rule;
    ``-1`` entries skipped): a candidate is kept when it is no more similar
    to any kept one than to the row's own print; lists that keep fewer than
    ``m`` are filled with the best of the rest. Returns ``(B, width)`` ids
    (``width >= m``), the kept in walk order then the fill, ``-1`` padded —
    what :func:`_select_heuristic` returns row by row."""
    bsz, c = cand_i.shape
    valid = cand_i >= 0
    safe = jnp.maximum(cand_i, 0)
    fps, cc = db[safe], cnt[safe]
    inter = jnp.sum(jax.lax.population_count(
        fps[:, :, None, :] & fps[:, None, :, :]).astype(jnp.int32), axis=-1)
    pair = metric_from_counts(metric, inter, cc[:, :, None], cc[:, None, :])

    def body(j, st):
        count, sel, rej = st
        take = valid[:, j] & ~rej[:, j] & (count < m)
        sel = sel.at[:, j].set(take)
        rej = rej | (take[:, None] & (pair[:, :, j] > cand_s))
        return count + take.astype(jnp.int32), sel, rej
    zero = jnp.zeros((bsz, c), bool)
    count, sel, _ = jax.lax.fori_loop(
        0, c, body, (jnp.zeros((bsz,), jnp.int32), zero, zero))
    rest = valid & ~sel
    rank = jnp.cumsum(rest, axis=1) - 1
    fill = rest & (rank < (m - count)[:, None])
    pos = jnp.where(sel, jnp.cumsum(sel, axis=1) - 1,
                    jnp.where(fill, count[:, None] + rank, width))
    out = jnp.full((bsz, width + 1), -1, jnp.int32)
    out = out.at[jnp.arange(bsz)[:, None], pos].set(cand_i)
    return out[:, :width]


@functools.partial(jax.jit, static_argnames=("m", "metric"),
                   donate_argnums=(0,))
def _build_select(adj, lev, db, cnt, rows, cand_s, cand_i, m: int,
                  metric: Metric):
    """One level's neighbour selection for the round rows ``rows``
    (ascending, ``-1`` pads): the search's candidates merged with the
    earlier rows of the round at this level (brute-force scores), the best
    ``ef`` in (similarity, id) descending order — the order the host
    search hands Alg. 4 — then the batched heuristic. Writes each row's
    selection as its adjacency at the level."""
    bsz, ef = cand_i.shape
    safe = jnp.maximum(rows, 0)
    fps, qc = db[safe], cnt[safe]
    inter = jnp.sum(jax.lax.population_count(
        fps[:, None, :] & fps[None, :, :]).astype(jnp.int32), axis=-1)
    s = metric_from_counts(metric, inter, qc[:, None], qc[None, :])
    pos = jnp.arange(bsz)
    earlier = (pos[None, :] < pos[:, None]) & (rows[None, :] >= 0) \
        & (rows[:, None] >= 0)
    s = jnp.where(earlier, s, NEG_INF)
    kk = min(bsz, ef)
    in_s, at = jax.lax.top_k(s, kk)
    in_i = jnp.where(in_s > NEG_INF, rows[at], -1)
    all_s = jnp.concatenate([cand_s, in_s], axis=1)
    all_i = jnp.concatenate([cand_i, in_i], axis=1)
    all_s = jnp.where(all_i >= 0, all_s, NEG_INF)
    srt_s, srt_i = _keyed(all_s, all_i)
    sel = _heuristic(db, cnt, srt_i[:, :ef], srt_s[:, :ef], m, m, metric)
    sel = jnp.where(rows[:, None] >= 0, sel, -1)
    m2 = adj.shape[2]
    row = jnp.concatenate([sel, jnp.full((bsz, m2 - m), -1, jnp.int32)], 1)
    dest = jnp.where(rows >= 0, rows, adj.shape[1])
    return adj.at[lev, dest].set(row, mode="drop"), sel


@functools.partial(jax.jit, static_argnames=("metric",),
                   donate_argnums=(0,))
def _build_link(adj, lev, db, cnt, targets, new, mmax, metric: Metric):
    """One pass of reverse links at one level: each target (``-1`` pads)
    takes its next new neighbours ``new (U, 2M)`` (at most ``mmax`` of
    them, in source order). They are appended where the row has room;
    otherwise the row and the new neighbours, in that order, are shrunk to
    ``mmax`` by the heuristic (stable on ties), as :func:`_insert_node`
    shrinks a full row. ``mmax`` is 2M on the base layer and M above it."""
    m2 = adj.shape[2]
    safe = jnp.maximum(targets, 0)
    row = adj[lev, safe]
    comb = jnp.concatenate([row, new], axis=1)
    comb = jnp.take_along_axis(
        comb, jnp.argsort(comb < 0, axis=1, stable=True), axis=1)
    fits = jnp.sum(comb >= 0, axis=1) <= mmax
    s = _score_rows(db, cnt, db[safe], cnt[safe], comb, metric)
    order = jnp.argsort(-s, axis=1, stable=True)
    shrunk = _heuristic(db, cnt, jnp.take_along_axis(comb, order, axis=1),
                        jnp.take_along_axis(s, order, axis=1), mmax, m2,
                        metric)
    out = jnp.where(fits[:, None], comb[:, :m2], shrunk)
    dest = jnp.where(targets >= 0, targets, adj.shape[1])
    return adj.at[lev, dest].set(out, mode="drop")


def _link_groups(sources: np.ndarray, sel: np.ndarray, width: int,
                 m2: int, top: int):
    """Group one level's new links ``sources[r] -> sel[r, :]`` by target,
    each target's sources in ascending order, as link passes of at most
    ``width`` sources a target: yields ``(targets (U,), new (U, m2))``,
    ``U`` padded (:func:`_bucket`, at most ``top``)."""
    src = np.repeat(sources, sel.shape[1])
    tgt = sel.ravel()
    ok = tgt >= 0
    src, tgt = src[ok], tgt[ok]
    if not len(tgt):
        return
    order = np.lexsort((src, tgt))
    src, tgt = src[order], tgt[order]
    first = np.r_[True, tgt[1:] != tgt[:-1]]
    group = np.cumsum(first) - 1
    rank = np.arange(len(tgt)) - np.flatnonzero(first)[group]
    for p in range(int(rank.max()) // width + 1):
        pick = (rank >= p * width) & (rank < (p + 1) * width)
        uniq, slot = np.unique(group[pick], return_inverse=True)
        targets = _padded(tgt[first][uniq],
                          _bucket(len(uniq), LINK_FLOOR, 4, top))
        new = np.full((len(targets), m2), -1, np.int32)
        new[slot, rank[pick] - p * width] = src[pick]
        yield targets, new


def _build_round(bg: _BuildGraph, levels: np.ndarray, a: int, b: int,
                 entry_point: int, ep_level: int, m: int, ef: int,
                 beam: int, metric: Metric, build_round: int):
    """Insert rows ``[a, b)`` into the graph of ``[0, a)`` on the device
    (:func:`build_hnsw`); returns the graph and the new ``(entry_point,
    ep_level)``."""
    ids = np.arange(a, b, dtype=np.int32)
    lv = levels[a:b]
    top_rows = 1 << max(int(build_round) - 1, 0).bit_length()

    def padded(x):
        return _padded(x, _bucket(len(x), ROUND_FLOOR, 16, top_rows))
    rows = padded(ids)
    bsz = len(rows)
    lv_p = np.full((bsz,), -1, np.int32)
    lv_p[:len(ids)] = lv
    top = int(lv.max())
    searched = {}                      # level -> (positions, scores, ids)
    with _TR.span("hnsw.build_search"):
        if entry_point >= 0:
            e_s, e_i = _build_entry(bg.db, bg.db_popcount, rows, entry_point,
                                    ef=ef, metric=metric)
            for lev in range(ep_level, -1, -1):
                if lev > 0:
                    e_s, e_i = _build_descend(
                        bg.adj, lev, bg.db, bg.db_popcount, rows, e_s, e_i,
                        lv_p < lev, m=m, metric=metric)
                pos = np.flatnonzero(lv_p >= lev)
                if not len(pos):
                    continue
                pos_p = padded(pos)
                e_s, e_i, r_s, r_i = _build_search(
                    bg.adj, lev, bg.db, bg.db_popcount, rows, e_s, e_i,
                    pos_p, ef=ef, beam=beam, metric=metric)
                searched[lev] = (pos_p, r_s, r_i)
    sels = {}
    with _TR.span("hnsw.build_select"):
        adj = bg.adj
        for lev in range(top, -1, -1):
            if lev in searched:
                pos_p, c_s, c_i = searched[lev]
            else:                      # above the graph: the round alone
                pos_p = padded(np.flatnonzero(lv_p >= lev))
                c_s = np.full((len(pos_p), ef), -np.inf, np.float32)
                c_i = np.full((len(pos_p), ef), -1, np.int32)
            sub = np.where(pos_p >= 0, rows[np.maximum(pos_p, 0)], -1)
            adj, sel = _build_select(adj, lev, bg.db, bg.db_popcount, sub,
                                     c_s, c_i, m=m, metric=metric)
            sels[lev] = (sub, sel)
    with _TR.span("hnsw.build_link"):
        got = jax.device_get({lev: s for lev, (_, s) in sels.items()})
        for lev in range(top, -1, -1):
            mmax = 2 * m if lev == 0 else m
            for targets, new in _link_groups(sels[lev][0], got[lev], mmax,
                                             2 * m, top_rows * m):
                adj = _build_link(adj, lev, bg.db, bg.db_popcount, targets,
                                  new, mmax, metric=metric)
    if entry_point < 0 or top > ep_level:
        entry_point, ep_level = int(ids[int(np.argmax(lv == top))]), top
    return bg._replace(adj=adj), entry_point, ep_level


# ---------------------------------------------------------------------------
# sharded fan-out (ISSUE 5) — partition-then-merge across the device mesh
# ---------------------------------------------------------------------------
#
# The paper scales its HNSW engine by replicating traversal/distance engines
# and splitting the database across parallel pipelines (§IV, Fig. 8); the
# same recipe here is FPScreen-style partition-then-merge: the database rows
# are **round-robin partitioned** into S shards (global row g lives in shard
# ``g % S`` at local row ``g // S``), each shard builds its own independent
# HNSW graph, queries fan out to one lock-step traversal per shard (each
# with its own entry point and PQ queues, placed on its own device), and the per-shard result runs rank-merge into one global top-k
# (``core/topk.merge_sorted_many`` via ``core/distributed.merge_shard_topk``).
# Round-robin keeps shards balanced under online inserts and makes the
# local<->global id map a closed form — no translation table on device.


def sharded_global_ids(local_ids: np.ndarray, shard: int,
                       n_shards: int) -> np.ndarray:
    """Map one shard's local result ids to global ids (``-1`` pads kept) —
    host-side twin of :func:`globalize_shard_ids` for the numpy backend."""
    return np.where(local_ids >= 0, local_ids * n_shards + shard, -1)


@jax.jit
def globalize_shard_ids(local_ids: jax.Array) -> jax.Array:
    """(S, ..., k) stacked per-shard local ids -> global ids under the
    round-robin partition (``gid = local * S + shard``; ``-1`` pads kept).
    The single device-side implementation of the id map — the engine's
    fan-out and :func:`search_hnsw_sharded` both go through it."""
    n_shards = local_ids.shape[0]
    shard = jnp.arange(n_shards, dtype=local_ids.dtype).reshape(
        (n_shards,) + (1,) * (local_ids.ndim - 1))
    return jnp.where(local_ids >= 0, local_ids * n_shards + shard, -1)


def build_hnsw_sharded(db: np.ndarray, n_shards: int, m: int = 16,
                       ef_construction: int = 100, seed: int = 0,
                       max_level_cap: int = 4,
                       metric: Metric = TANIMOTO,
                       build_round: int = DEFAULT_BUILD_ROUND) -> list:
    """Build S independent per-shard indexes over the round-robin partition.

    Shard ``s`` is ``build_hnsw(db[s::S], seed=seed + s)``, with the same
    ``build_round`` — with ``n_shards == 1`` this is exactly the unsharded
    build (same rows, same seed), the base of the 1-shard bit-parity
    contract. Each shard draws its
    levels from its own seed stream so per-shard graphs stay deterministic
    under :func:`insert_hnsw_sharded` growth.
    """
    db = np.asarray(db, dtype=np.uint32)
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    if db.shape[0] < n_shards:
        raise ValueError(f"cannot split {db.shape[0]} rows into "
                         f"{n_shards} shards")
    return [build_hnsw(db[s::n_shards], m=m, ef_construction=ef_construction,
                       seed=seed + s, max_level_cap=max_level_cap,
                       metric=metric, build_round=build_round)
            for s in range(n_shards)]


def insert_hnsw_sharded(indexes: list, new_fps: np.ndarray,
                        scorer_factory=None):
    """Route an insert batch to its shards (``gid % S``) in global-id order.

    New rows get the next global ids ``n_total..``; because the ids are
    contiguous, the sub-batch landing on shard ``s`` appends at exactly the
    local rows ``gid // S`` — the round-robin invariant is self-maintaining
    and an engine grown online stays graph-identical to
    :func:`build_hnsw_sharded` on the concatenated database (the per-shard
    :func:`insert_hnsw` parity contract). Returns ``(gids, touched)`` where
    ``touched`` lists the shards whose device copies need refreshing.

    ``scorer_factory`` is per-shard (called with each shard's db); callers
    that cache device state per database must key it per shard.
    """
    new_fps = np.atleast_2d(np.asarray(new_fps, dtype=np.uint32))
    n_shards = len(indexes)
    n_total = sum(ix.n for ix in indexes)
    for s, ix in enumerate(indexes):            # round-robin invariant
        expect = len(range(s, n_total, n_shards))
        if ix.n != expect:
            raise ValueError(f"shard {s} holds {ix.n} rows, round-robin of "
                             f"{n_total} total expects {expect}")
    gids = np.arange(n_total, n_total + new_fps.shape[0], dtype=np.int64)
    touched = []
    for s in range(n_shards):
        rows = new_fps[(gids % n_shards) == s]
        if rows.shape[0]:
            insert_hnsw(indexes[s], rows, scorer_factory=scorer_factory)
            touched.append(s)
    return gids, touched


def place_graph(g: HNSWDeviceGraph, device) -> HNSWDeviceGraph:
    """Commit a device graph's arrays to ``device`` (static fields kept)."""
    return HNSWDeviceGraph(**{
        f: (jax.device_put(v, device) if isinstance(v, jax.Array) else v)
        for f, v in g._asdict().items()})


def to_device_graph_sharded(indexes: list,
                            capacities: list | None = None,
                            devices: list | None = None) -> list:
    """Per-shard device graphs for the fan-out traversal.

    Each shard's graph is an ordinary :func:`to_device_graph` (padded to its
    own power-of-two capacity unless ``capacities`` overrides it, with its
    own nodes' ``nbr_fps`` blocks), committed to its
    own device (``devices``, default
    :func:`repro.core.distributed.shard_devices`) so the S traversals run
    in parallel across the mesh.
    """
    from .distributed import shard_devices
    from ..serve.store import next_pow2
    if devices is None:
        devices = shard_devices(len(indexes))
    if capacities is None:
        capacities = [next_pow2(ix.n) for ix in indexes]
    return [place_graph(to_device_graph(ix, capacity=cap), dev)
            for ix, cap, dev in zip(indexes, capacities, devices)]


def search_hnsw_sharded(graphs: list, queries, k: int, ef: int,
                        max_iters: int | None = None, beam: int = 1,
                        score_fn_for=None, expand_fn_for=None,
                        metric: Metric = TANIMOTO):
    """Fan-out KNN over per-shard device graphs + rank-merge.

    Runs one :func:`search_hnsw` lock-step traversal per shard (queries are
    committed to each shard's device, so launches overlap across the mesh
    under JAX's async dispatch), maps local ids to global ids
    (:func:`globalize_shard_ids`) and rank-merges the per-shard runs with
    ``core/distributed.merge_shard_topk``. ``score_fn_for(g)`` /
    ``expand_fn_for(g)`` build optional per-shard kernel stages; ``None``
    uses the jnp defaults. Returns ``(gids (Q, k), sims (Q, k),
    stats_list)``.

    This is the uncached module-level form of the fan-out — one eager
    traversal per call. ``HNSWEngine(shards=N)`` runs the same loop with
    per-shape jit-compiled traversals (``engine.py::_search_sharded``);
    ``tests/test_sharded_hnsw.py`` pins the two paths equal. With one
    shard the merge is the identity, so results are bit-identical to the
    unsharded traversal — ``HNSWEngine(shards=1)``'s contract.
    """
    from .distributed import merge_shard_topk
    dev0 = next(iter(graphs[0].db.devices()))
    runs_s, runs_i, stats = [], [], []
    for g in graphs:
        q = jax.device_put(jnp.asarray(queries), next(iter(g.db.devices())))
        ids, sims, st = search_hnsw(
            g, q, k, ef, max_iters=max_iters, beam=beam,
            score_fn=score_fn_for(g) if score_fn_for else None,
            expand_fn=expand_fn_for(g) if expand_fn_for else None,
            metric=metric)
        runs_s.append(jax.device_put(sims, dev0))
        runs_i.append(jax.device_put(ids, dev0))
        stats.append(st)
    gids = globalize_shard_ids(jnp.stack(runs_i))
    gids, sims = merge_shard_topk(jnp.stack(runs_s), gids, k)
    return gids, sims, stats


def search_hnsw_numpy(index: HNSWIndex, queries: np.ndarray, k: int, ef: int):
    """Host-side reference traversal (the ``numpy`` engine backend).

    True variable-length queues (heapq), one python loop per query — the
    semantics oracle for the fixed-shape device path. Returns
    ``(ids (Q, k) int64, sims (Q, k) float32, counters)`` where counters
    accumulates ``evals`` / ``iters`` over the batch.
    """
    ef = max(ef, k)
    db, db_cnt = index.db, index.db_popcount
    metric = getattr(index, "metric", TANIMOTO)

    def adj_at(level):
        if level == 0:
            return lambda gid: index.base_adj[gid]
        gids = index.level_nodes[level - 1]
        adjm = index.level_adj[level - 1]

        def f(gid):
            r = np.searchsorted(gids, gid)
            if r < len(gids) and gids[r] == gid:
                return adjm[r]
            return np.empty((0,), np.int32)
        return f

    queries = np.asarray(queries)
    ids_out = np.full((len(queries), k), -1, dtype=np.int64)
    sims_out = np.zeros((len(queries), k), dtype=np.float32)
    counters: dict = {"evals": 0, "iters": 0}
    for qi, q in enumerate(queries):
        ep = np.asarray([index.entry_point], dtype=np.int32)
        for level in range(index.max_level, 0, -1):
            ids, _ = _search_layer_np(db, db_cnt, adj_at(level), q, ep, 1,
                                      metric=metric)
            ep = ids[:1]
        ids, sims = _search_layer_np(db, db_cnt, adj_at(0), q, ep, ef,
                                     counters=counters, metric=metric)
        kk = min(k, len(ids))
        ids_out[qi, :kk] = ids[:kk]
        sims_out[qi, :kk] = sims[:kk]
    return ids_out, sims_out, counters
