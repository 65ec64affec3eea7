"""HNSW over Tanimoto similarity — paper §III-C / §IV-B.

* Graph **construction** is host-side numpy (as in the paper: hnswlib builds
  on CPU; the FPGA/TPU accelerates *search*). Heuristic neighbour selection
  (Malkov & Yashunin Alg. 4) with the long-range-link property the paper
  credits for HNSW's recall.
* Graph **search** is the accelerated path: a batched, device-resident
  traversal engine mirroring the paper's FPGA graph engine —
  SEARCH-LAYER-TOP greedy descent (Alg. 1) followed by a lock-step batched
  SEARCH-LAYER-BASE beam search (Alg. 2):

  - two fixed-shape **register-array priority queues** per query (candidates
    C, results M) from ``core/topk.py`` — compare-and-shift / rank-merge
    semantics, the paper's Fig. 9 structure;
  - a **fine-grained gather-distance stage** scoring one whole beam
    expansion (``beam * 2M`` neighbour ids per query) per launch, on one of
    two memory layouts (bit-exact results): ``rows`` — per-neighbour row
    gather (Pallas scalar-prefetch kernel ``kernels.ops.gather_tanimoto`` or
    its jnp twin :func:`score_ids_jnp`) — or ``blocked`` — a neighbour-packed
    copy of the base layer (``nbr_fps (N, 2M, W)``) streamed one contiguous
    block per popped node through the fused gather/score/sort kernel
    ``kernels.ops.expand_tanimoto_sorted`` (jnp twin
    :func:`expand_scores_jnp`), ``beam`` DMA streams per query-iteration
    instead of ``beam*2M`` scattered row fetches;
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

import math
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .fingerprints import (Metric, TANIMOTO, metric_from_counts,
                           metric_from_counts_np)
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
    # device copies (incl. the neighbour-blocked layout) incrementally.
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
    # candidates: max-first by similarity; results: bounded by ef
    cand = list(zip((-sims).tolist(), ep.tolist()))
    import heapq
    heapq.heapify(cand)
    results = list(zip(sims.tolist(), ep.tolist()))
    heapq.heapify(results)  # min-heap over similarity = worst first
    while cand:
        neg_s, c = heapq.heappop(cand)
        if -neg_s < results[0][0] and len(results) >= ef:
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
            if len(results) < ef or s > results[0][0]:
                heapq.heappush(cand, (-s, e))
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

    One shared implementation drives both offline :func:`build_hnsw` and
    online :func:`insert_hnsw` — graph determinism across the two paths is
    what makes online engines bit-identical to a rebuild. ``upper`` is the
    level -> {gid: neighbours} dict list; ``entry_point < 0`` means the graph
    is still empty. Returns the updated ``(entry_point, ep_level)``.

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


def build_hnsw(db: np.ndarray, m: int = 16, ef_construction: int = 100,
               seed: int = 0, max_level_cap: int = 4,
               metric: Metric = TANIMOTO) -> HNSWIndex:
    """Sequential insert construction (paper builds offline; search is the
    accelerated path). The per-node insertion is :func:`_insert_node` — the
    same code online :func:`insert_hnsw` runs, so incremental growth and
    from-scratch builds produce identical graphs."""
    db = np.asarray(db, dtype=np.uint32)
    n, _ = db.shape
    db_cnt = _np_popcount(db)
    rng = np.random.default_rng(seed)
    levels = _draw_levels(rng, n, m, max_level_cap)
    base_adj = np.full((n, 2 * m), -1, dtype=np.int32)
    upper = [dict() for _ in range(max_level_cap + 1)]  # gid -> int32 array

    entry_point, ep_level = -1, 0
    for i in range(n):
        entry_point, ep_level = _insert_node(
            db, db_cnt, base_adj, upper, levels, i, m, ef_construction,
            entry_point, ep_level, metric=metric)

    max_level = int(levels.max(initial=0))
    level_nodes, level_adj = _densify(upper, max_level, m)
    return HNSWIndex(db=db, db_popcount=db_cnt, m=m,
                     ef_construction=ef_construction, entry_point=entry_point,
                     max_level=max_level, base_adj=base_adj,
                     level_nodes=level_nodes, level_adj=level_adj,
                     level_of=levels.astype(np.int8), seed=seed,
                     max_level_cap=max_level_cap, metric=metric,
                     upper_dicts=upper, rng=rng)


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
    :func:`_draw_levels`) and every node runs the same :func:`_insert_node`
    the offline build uses, so after any number of insert batches the index
    is **identical** to ``build_hnsw(concatenated_db)`` — the engine parity
    contract. Array growth is amortized-doubling (:func:`_ensure_capacity`):
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

    ``layout="blocked"`` additionally carries the **neighbour-blocked copy of
    the base layer** (ISSUE 4): ``nbr_fps[v] = db[base_adj[v]]`` with zero
    rows for ``-1`` slots, plus the matching popcounts — one popped node's
    whole expansion is a single contiguous ``2M*W``-word HBM stream for the
    fused expand kernel, at the HBM cost of one extra ``2M*W``-word copy of
    the base layer per node.

    A graph for the Pallas kernels (``kernel=True``) also holds the database
    in the gather kernel's line layout (``db_lines``) and, on the blocked
    layout, the neighbour blocks in the expand kernel's slab layout
    (``nbr_slabs``) in place of ``nbr_fps``/``nbr_cnt`` — built once here,
    so no traversal relays them out per call.
    """
    db: jax.Array             # (N, W) uint32
    db_popcount: jax.Array    # (N,) int32
    base_adj: jax.Array       # (N, 2M) int32
    upper_adj: jax.Array      # (L, N, M) int32 — dense per-level adjacency (-1 pad)
    entry_point: jax.Array    # () int32
    max_level: int
    nbr_fps: jax.Array | None = None   # (N, 2M, W) uint32 — blocked, jnp
    nbr_cnt: jax.Array | None = None   # (N, 2M) int32 — blocked, jnp
    db_lines: jax.Array | None = None  # ops.db_lines(db) — kernel only
    nbr_slabs: jax.Array | None = None  # ops.nbr_slabs(nbr_fps) — kernel, blocked


LAYOUTS = ("rows", "blocked")


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
                    layout: str = "rows",
                    kernel: bool = False) -> HNSWDeviceGraph:
    """Densify the index for the device engine. ``capacity`` (>= n) pads the
    node dimension — pad rows are zero fingerprints with no edges, so they
    are unreachable and the traversal is unaffected. Engines pad to a power
    of two so online inserts below the capacity reuse compiled traversals.
    ``layout="blocked"`` also builds the neighbour-blocked base-layer copy,
    and ``kernel=True`` the Pallas kernels' layouts of the database and of
    that copy (:class:`HNSWDeviceGraph` docstring)."""
    if layout not in LAYOUTS:
        raise ValueError(f"layout must be one of {LAYOUTS}, got {layout!r}")
    L = max(index.max_level, 0)
    n = index.n
    cap = n if capacity is None else max(int(capacity), n)
    upper = _dense_upper(index, cap)
    db = np.zeros((cap, index.db.shape[1]), dtype=np.uint32)
    db[:n] = index.db
    cnt = np.zeros((cap,), dtype=np.int32)
    cnt[:n] = index.db_popcount
    base = np.full((cap, index.base_adj.shape[1]), -1, dtype=np.int32)
    base[:n] = index.base_adj
    nbr_fps = nbr_cnt = db_lines = nbr_slabs = None
    if kernel:
        from ..kernels import ops as kops
        db_lines = jnp.asarray(kops.db_lines(db))
    if layout == "blocked":
        nbr_fps_np, nbr_cnt_np = _blocked_rows(db, cnt, base)
        if kernel:
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


def _sims(q: jax.Array, q_cnt: jax.Array, g: HNSWDeviceGraph, ids: jax.Array,
          metric: Metric = TANIMOTO) -> jax.Array:
    """Single-query view of :func:`score_ids_jnp` (greedy-descent stage)."""
    return score_ids_jnp(q[None], q_cnt[None], g, ids[None], metric=metric)[0]


def score_ids_jnp(queries: jax.Array, q_cnt: jax.Array, g: HNSWDeviceGraph,
                  ids: jax.Array, metric: Metric = TANIMOTO) -> jax.Array:
    """Batched gather-distance stage: (Q, W) x (Q, E) ids -> (Q, E) sims.

    Plain-jnp twin of the Pallas ``kernels.ops.gather_tanimoto`` kernel —
    identical arithmetic (popcount similarity via ``metric_from_counts``,
    -inf for id -1), used by the ``"jnp"`` engine backend.
    """
    safe = jnp.maximum(ids, 0)
    fps = g.db[safe]                                    # (Q, E, W)
    inter = jnp.sum(jax.lax.population_count(
        queries[:, None, :] & fps).astype(jnp.int32), axis=-1)
    s = metric_from_counts(metric, inter, q_cnt[:, None], g.db_popcount[safe])
    return jnp.where(ids >= 0, s, NEG_INF)


def expand_scores_jnp(queries: jax.Array, q_cnt: jax.Array,
                      nbr_fps: jax.Array, nbr_cnt: jax.Array,
                      pop_ids: jax.Array, flat_ids: jax.Array,
                      worst: jax.Array, kk: int, metric: Metric = TANIMOTO):
    """Plain-jnp twin of the fused expand kernel (``kernels/expand.py``):
    gather ``beam`` contiguous neighbour blocks per query from the blocked
    layout, score, mask ``-1``/sub-threshold slots, return the top-``kk``
    sorted run. Identical arithmetic to the kernel and to the row path's
    gather -> score -> filter -> sort chain — the bit-exactness contract
    between ``layout="blocked"`` and ``layout="rows"``.
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


def _greedy_descent(q, q_cnt, g: HNSWDeviceGraph, level: int,
                    start: jax.Array, metric: Metric = TANIMOTO) -> jax.Array:
    """SEARCH-LAYER-TOP (Alg. 1) at one (static) upper level from ``start``."""
    adj = g.upper_adj[level - 1]

    def cond(state):
        cur, cur_sim, moved = state
        return moved

    def body(state):
        cur, cur_sim, _ = state
        neigh = adj[cur]                                   # (M,)
        s = _sims(q, q_cnt, g, neigh, metric=metric)
        j = jnp.argmax(s)
        better = s[j] > cur_sim
        return (jnp.where(better, neigh[j], cur),
                jnp.where(better, s[j], cur_sim), better)

    s0 = _sims(q, q_cnt, g, start[None], metric=metric)[0]
    cur, _, _ = jax.lax.while_loop(cond, body, (start, s0, jnp.bool_(True)))
    return cur


# Early-termination reasons (TraversalStats.reason values).
REASON_CONVERGED = 0   # best remaining candidate worse than worst result
REASON_MAX_ITERS = 1   # iteration budget exhausted before convergence


class TraversalStats(NamedTuple):
    """Per-query telemetry from one batched traversal."""
    iters: jax.Array        # (Q,) int32 — beam-expansion iterations executed
    expansions: jax.Array   # (Q,) int32 — candidates actually expanded
    reason: jax.Array       # (Q,) int32 — REASON_CONVERGED / REASON_MAX_ITERS


def stats_summary(iters, expansions, reason, m2: int) -> dict:
    """Fold (already stacked/summed-ready) per-query traversal telemetry
    into the scalar totals dict shared by :attr:`HNSWEngine.stats` and the
    ``hnsw.search`` trace-span args (ISSUE 8): iteration/expansion totals,
    neighbour evaluations (``expansions * 2M``) and termination-reason
    counts. Accepts device arrays or numpy; always returns plain ints."""
    iters = np.asarray(iters)
    expansions = np.asarray(expansions)
    reason = np.asarray(reason)
    return {
        "iters": int(iters.sum()),
        "expansions": int(expansions.sum()),
        "neighbour_evals": int(expansions.sum()) * int(m2),
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
    gather-distance launch (``score_fn``), and rank-merges the scored batch
    into both fixed-shape register-array queues (C and the result set M,
    ``core/topk.py``). A per-query visited bitset gives exactly-once scoring;
    queries terminate individually (Alg. 2 bound: best candidate worse than
    the worst retained result) and finished queries ride along masked until
    the last one converges or ``max_iters`` is hit.

    queries: (Q, W) uint32. Returns ``(ids (Q, k), sims (Q, k), stats)``
    with ids descending by similarity (-1 pads) and :class:`TraversalStats`
    device arrays.

    ``score_fn(queries, q_cnt, ids) -> sims`` is the fine-grained distance
    stage for the entry-point scoring and the ``rows``-layout expansion;
    default is the jnp gather (:func:`score_ids_jnp`), engines pass the
    Pallas ``gather_tanimoto`` kernel for the ``tpu`` backend.

    ``expand_fn(queries, q_cnt, pop_ids, flat_ids, worst, kk) ->
    (scores (Q, kk) desc, ids (Q, kk))`` replaces the whole
    gather -> score -> evict-filter -> sort stage of one beam expansion
    (``pop_ids (Q, beam)`` are the popped node ids, ``flat_ids (Q, beam*2M)``
    their adjacency with -1 for pad/visited slots, ``worst (Q,)`` the result
    queues' eviction bounds). Engines pass the fused blocked-layout kernel
    (``kernels.ops.expand_tanimoto_sorted``) or its jnp twin
    (:func:`expand_scores_jnp`) for ``layout="blocked"``; the default is the
    row-gather chain over ``score_fn``. Either way the emitted run is sorted,
    so the queues merge it directly (one launch per iteration).
    """
    ef = max(ef, k)
    beam = max(1, min(beam, ef))
    if max_iters is None:
        max_iters = 4 * ef + 16
    if score_fn is None:
        def score_fn(qs, qc, ids):
            return score_ids_jnp(qs, qc, g, ids, metric=metric)

    q_n = queries.shape[0]
    n = g.db.shape[0]
    m2 = g.base_adj.shape[1]
    n_exp = beam * m2                                   # neighbours per launch
    kk = min(n_exp, ef)                                 # sorted-run width
    if expand_fn is None:
        def expand_fn(qs, qc, pop_ids, flat, worst, kk):
            # rows layout: scattered row gather + score, then evict-filter
            # and one sort (the run feeds BOTH queues — pq_insert_batch
            # would sort twice)
            s = score_fn(qs, qc, flat)
            keep = s > worst[:, None]                    # evict-worst filter
            s = jnp.where(keep, s, NEG_INF)
            fl = jnp.where(keep, flat, -1)
            s_srt, pos = jax.lax.top_k(s, kk)
            return s_srt, jnp.take_along_axis(fl, pos, axis=1)

    vwords = (n + 31) // 32
    q_cnt = jnp.sum(jax.lax.population_count(queries).astype(jnp.int32), -1)

    # greedy descent through the upper layers (Alg. 1), vmapped per query
    def descend(q, qc):
        ep = g.entry_point
        for level in range(g.max_level, 0, -1):          # static unroll
            ep = _greedy_descent(q, qc, g, level, ep, metric=metric)
        return ep

    ep = jax.vmap(descend)(queries, q_cnt)               # (Q,)
    ep_sim = score_fn(queries, q_cnt, ep[:, None])[:, 0]

    # C (candidates, pop-best) and M (results, evict-worst): batched
    # register-array queues (core/topk.py PQ invariants), one row per query —
    # every queue op below is the vmapped scalar PQ primitive.
    cand = PQ(jnp.full((q_n, ef), NEG_INF).at[:, 0].set(ep_sim),
              jnp.full((q_n, ef), -1, jnp.int32).at[:, 0].set(ep))
    res = cand
    rows = jnp.arange(q_n)
    visited = jnp.zeros((q_n, vwords), jnp.uint32)
    visited = visited.at[rows, ep // 32].set(
        jnp.uint32(1) << (ep % 32).astype(jnp.uint32))

    def where_rows(mask, new, old):
        """Per-query select between two batched PQ pytrees."""
        return jax.tree.map(
            lambda a, b: jnp.where(mask[:, None], a, b), new, old)

    state = (cand, res, visited,
             jnp.ones((q_n,), bool),                     # active
             jnp.zeros((q_n,), jnp.int32),               # iters
             jnp.zeros((q_n,), jnp.int32),               # expansions
             jnp.int32(0))                               # lock-step counter

    def cond(st):
        active, it = st[3], st[6]
        return jnp.logical_and(jnp.any(active), it < max_iters)

    def body(st):
        cand, res, visited, active, iters, expans, it = st
        worst = jax.vmap(pq_worst)(res)                  # eviction threshold
        # Alg. 2 termination, per query: stop when the best candidate cannot
        # beat the worst retained result (monotone -> inactive stays inactive)
        go = jnp.logical_and(active, jnp.logical_and(
            cand.scores[:, 0] > NEG_INF, cand.scores[:, 0] >= worst))

        # pop the beam: best B candidates, queue shifts up by B
        pop_s, pop_i, popped = jax.vmap(
            lambda pq: pq_pop_many(pq, beam))(cand)
        valid_pop = (pop_s > NEG_INF) & (pop_s >= worst[:, None]) & go[:, None]
        cand = where_rows(go, popped, cand)

        # beam expansion: adjacency gather, (Q, beam, 2M)
        nb = g.base_adj[jnp.maximum(pop_i, 0)]
        nb = jnp.where(valid_pop[:, :, None], nb, -1)

        # visited check + mark, one beam slot at a time (static unroll): ids
        # within a slot are unique (one node's adjacency), so the scatter-ADD
        # below equals scatter-OR; marking between slots dedups neighbours
        # shared by two popped candidates in the same iteration.
        fresh_slots = []
        for b in range(beam):
            ids_b = nb[:, b, :]
            safe = jnp.maximum(ids_b, 0)
            word = jnp.take_along_axis(visited, safe // 32, axis=1)
            bit = (word >> (safe % 32).astype(jnp.uint32)) & 1
            fresh = jnp.logical_and(ids_b >= 0, bit == 0)
            upd = jnp.where(
                fresh, jnp.uint32(1) << (safe % 32).astype(jnp.uint32),
                jnp.uint32(0))
            visited = visited.at[rows[:, None], safe // 32].add(upd)
            fresh_slots.append(fresh)
        fresh = jnp.stack(fresh_slots, axis=1).reshape(q_n, n_exp)
        flat = jnp.where(fresh, nb.reshape(q_n, n_exp), -1)

        # fused expansion stage: gather + score + evict-filter + sort for
        # all B*2M neighbours per query in one launch; the sorted run
        # rank-merges into both queues (Fig. 9)
        s_srt, i_srt = expand_fn(queries, q_cnt, pop_i, flat, worst, kk)
        vmerge = jax.vmap(
            lambda pq, ms, mi: PQ(*merge_sorted(pq.scores, pq.payload,
                                                ms, mi)))
        res = where_rows(go, vmerge(res, s_srt, i_srt), res)
        cand = where_rows(go, vmerge(cand, s_srt, i_srt), cand)

        iters = iters + go.astype(jnp.int32)
        expans = expans + jnp.sum(valid_pop, axis=1).astype(jnp.int32)
        return cand, res, visited, go, iters, expans, it + 1

    _, res, _, active, iters, expans, _ = jax.lax.while_loop(cond, body, state)
    reason = jnp.where(active, REASON_MAX_ITERS, REASON_CONVERGED)
    ids = res.payload[:, :k]
    sims = jnp.where(ids >= 0, res.scores[:, :k], 0.0)
    return ids, sims, TraversalStats(iters=iters, expansions=expans,
                                     reason=reason.astype(jnp.int32))


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
# with its own entry point, visited bitset and PQ queues, placed on its own
# device), and the per-shard result runs rank-merge into one global top-k
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
                       metric: Metric = TANIMOTO) -> list:
    """Build S independent per-shard indexes over the round-robin partition.

    Shard ``s`` is ``build_hnsw(db[s::S], seed=seed + s)`` — with
    ``n_shards == 1`` this is exactly the unsharded build (same rows, same
    seed), the base of the 1-shard bit-parity contract. Each shard draws its
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
                       metric=metric)
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


def to_device_graph_sharded(indexes: list, layout: str = "rows",
                            capacities: list | None = None,
                            devices: list | None = None) -> list:
    """Per-shard device graphs for the fan-out traversal.

    Each shard's graph is an ordinary :func:`to_device_graph` (padded to its
    own power-of-two capacity unless ``capacities`` overrides it — per-shard
    ``nbr_fps`` blocks included on ``layout="blocked"``), committed to its
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
    return [place_graph(to_device_graph(ix, capacity=cap, layout=layout), dev)
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
