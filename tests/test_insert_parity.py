"""Insert-then-search parity.

After any interleaving of online inserts and searches — including across an
LSM compaction boundary — every exhaustive engine returns **bit-identical**
(ids, sims) to an engine rebuilt from scratch on the concatenated database,
on every backend. This pins the whole write path: store segment layout, the
merged main+delta candidate ordering (stable (popcount, gid) ties) and the
device merge ranks.

An HNSW graph is defined by where its bulk build stopped: the build inserts
its rows in batched rounds on the device, online inserts go in one node at
a time on the host. So an HNSW engine that inserted online is held equal to
one rebuilt by the same bulk build over the same first rows followed by the
same inserts (:func:`_hnsw_rebuilt`, one insert batch: how the inserts were
batched does not matter), which pins the rng-continuation levels and the
incremental construction.
"""
import numpy as np
import pytest

from repro.core import BruteForceEngine, BitBoundFoldingEngine, HNSWEngine
from repro.core import hnsw as hn
from repro.data.molecules import (SyntheticConfig, queries_from_db,
                                  synthetic_fingerprints)
from repro.kernels import ops


@pytest.fixture(scope="module")
def data():
    base = synthetic_fingerprints(SyntheticConfig(n=700, seed=0))
    extra = synthetic_fingerprints(SyntheticConfig(n=100, seed=9))
    full = np.concatenate([base, extra])
    q = queries_from_db(full, 10, seed=4)
    return base, extra, full, q


def _hnsw_rebuilt(first, inserted, **kw):
    """An HNSW engine rebuilt as the restated contract says: the bulk build
    over ``first``, then ``inserted`` online in one batch."""
    reb = HNSWEngine(first, **kw)
    reb.insert(inserted)
    return reb


def _assert_equal(eng, reb, q, k, label):
    ids, sims = eng.search(q, k)
    rids, rsims = reb.search(q, k)
    np.testing.assert_array_equal(ids, rids, err_msg=label)
    np.testing.assert_array_equal(sims, rsims, err_msg=label)


@pytest.mark.parametrize("backend", ["jnp", "tpu"])
def test_brute_insert_parity(data, backend):
    base, extra, full, q = data
    eng = BruteForceEngine(base, backend=backend, compact_threshold=64)
    eng.insert(extra[:30])                     # delta only
    assert eng.store.compactions == 0
    _assert_equal(eng, BruteForceEngine(np.concatenate([base, extra[:30]]),
                                        backend=backend), q, 15,
                  f"brute/{backend} pre-compaction")
    eng.insert(extra[30:])                     # 100 >= 64 -> compaction
    assert eng.store.compactions == 1
    _assert_equal(eng, BruteForceEngine(full, backend=backend), q, 15,
                  f"brute/{backend} post-compaction")
    assert eng.n_total == len(full)


@pytest.mark.parametrize("backend,m,cutoff", [
    ("numpy", 1, 0.6), ("numpy", 4, 0.2),
    ("jnp", 2, 0.4), ("jnp", 1, 0.2),
    ("tpu", 1, 0.6), ("tpu", 4, 0.2),
])
def test_bitbound_insert_parity(data, backend, m, cutoff):
    base, extra, full, q = data
    label = f"bitbound/{backend} m={m} Sc={cutoff}"
    eng = BitBoundFoldingEngine(base, cutoff=cutoff, m=m, backend=backend,
                                compact_threshold=64)
    eng.insert(extra[:30])
    mid = BitBoundFoldingEngine(np.concatenate([base, extra[:30]]),
                                cutoff=cutoff, m=m, backend=backend)
    _assert_equal(eng, mid, q, 15, label + " pre-compaction")
    # scanned-work accounting matches the rebuild too (Eq.2 windows + delta)
    assert eng.scanned(len(q)) == mid.scanned(len(q)), label
    eng.insert(extra[30:])
    assert eng.store.compactions == 1
    reb = BitBoundFoldingEngine(full, cutoff=cutoff, m=m, backend=backend)
    _assert_equal(eng, reb, q, 15, label + " post-compaction")
    assert eng.scanned(len(q)) == reb.scanned(len(q)), label


@pytest.mark.parametrize("backend,layout", [
    ("numpy", "rows"), ("jnp", "rows"), ("jnp", "blocked"),
    ("tpu", "rows"), ("tpu", "blocked"),
])
def test_hnsw_insert_parity(data, backend, layout):
    base, extra, full, q = data
    eng = HNSWEngine(base[:600], m=6, ef_construction=24, ef_search=24,
                     seed=3, backend=backend, layout=layout)
    eng.search(q, 10)       # build the device graph at n=600 so the insert
    eng.insert(extra[:20])  # refresh below exercises the incremental path
    eng.insert(extra[20:40])
    reb = _hnsw_rebuilt(base[:600], extra[:40], m=6, ef_construction=24,
                        ef_search=24, seed=3, backend=backend, layout=layout)
    _assert_equal(eng, reb, q, 10, f"hnsw/{backend}/{layout}")
    assert eng.n_total == 640
    if backend != "numpy":
        # the incrementally-refreshed device graph (dirty_log scatter) is
        # identical to a from-scratch to_device_graph densify+upload
        g_inc = eng._graph
        g_new = hn.to_device_graph(eng.index, capacity=g_inc.db.shape[0],
                                   layout=layout, kernel=backend == "tpu")
        for field in ("db", "db_popcount", "base_adj", "upper_adj",
                      "nbr_fps", "nbr_cnt", "db_lines", "nbr_slabs"):
            a, b = getattr(g_inc, field), getattr(g_new, field)
            if a is None:
                assert b is None, field
                continue
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{backend}/{layout}/"
                                                  f"{field}")


def test_hnsw_cross_layout_results_identical(data):
    """ISSUE 4 acceptance: after online inserts, the blocked layout returns
    bit-identical results to the rows layout (jnp backend)."""
    base, extra, full, q = data
    res = {}
    for layout in ("rows", "blocked"):
        eng = HNSWEngine(base[:600], m=6, ef_construction=24, ef_search=24,
                         seed=3, backend="jnp", layout=layout)
        eng.insert(extra[:40])
        res[layout] = eng.search(q, 10)
    np.testing.assert_array_equal(res["rows"][0], res["blocked"][0])
    np.testing.assert_array_equal(res["rows"][1], res["blocked"][1])


def test_hnsw_incremental_graph_identical(data):
    """The graph itself (not just search results) matches the restated
    rebuild — the same bulk build, then the inserts in one batch: same
    adjacency, entry point, levels (the rng-continuation + one-node-at-a-
    time _insert_node contract)."""
    base, extra, full, q = data
    idx = hn.build_hnsw(base[:600], m=6, ef_construction=24, seed=3)
    hn.insert_hnsw(idx, extra[:20])
    hn.insert_hnsw(idx, extra[20:40])
    ref = hn.build_hnsw(base[:600], m=6, ef_construction=24, seed=3)
    hn.insert_hnsw(ref, extra[:40])
    np.testing.assert_array_equal(idx.base_adj, ref.base_adj)
    np.testing.assert_array_equal(idx.level_of, ref.level_of)
    assert idx.entry_point == ref.entry_point
    assert idx.max_level == ref.max_level
    assert len(idx.level_nodes) == len(ref.level_nodes)
    for a, b in zip(idx.level_nodes, ref.level_nodes):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(idx.level_adj, ref.level_adj):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("backend", ["jnp", "tpu"])
def test_brute_delta_parity_when_k_spans_padding(backend):
    """Regression (code-review find): with k > n_main the main scan's
    capacity-pad rows (sim 0, raw row ids) used to win cross-run score-0
    ties against real delta rows in the merge — ids must instead match the
    rebuild exactly up to k = n_total."""
    rows = synthetic_fingerprints(SyntheticConfig(n=8, seed=1))
    eng = BruteForceEngine(rows[:5], backend=backend,   # capacity pads 5->8
                           compact_threshold=100)
    eng.insert(rows[5:])
    reb = BruteForceEngine(rows, backend=backend)
    q = rows[:2]
    _assert_equal(eng, reb, q, 8, f"brute/{backend} k==n_total")
    ids, _ = eng.search(q, 8)
    assert (ids >= 0).all() and (ids < 8).all(), ids


def test_hnsw_amortized_growth_and_persisted_rng(data):
    """ISSUE 4 satellites: insert_hnsw grows through doubling backing arrays
    (views, no O(n_total) concatenate per batch) and continues a persisted
    rng Generator instead of re-drawing the whole level stream."""
    base, extra, _, _ = data
    idx = hn.build_hnsw(base[:200], m=4, ef_construction=12, seed=3)
    assert idx.rng is not None                    # persisted at build time
    hn.insert_hnsw(idx, extra[:8])
    cap_arr = idx._db_cap
    assert cap_arr is not None and cap_arr.shape[0] >= 208
    assert idx.db.base is cap_arr                 # view, not a copy
    assert idx.base_adj.base is idx._adj_cap
    hn.insert_hnsw(idx, extra[8:16])
    assert idx._db_cap is cap_arr                 # no reallocation below cap
    assert idx.n == 216
    # the dirty log accumulated the touched base rows, incl. all new nodes
    assert set(range(200, 216)) <= set(idx.dirty_log)
    # a legacy index (no persisted rng) fast-forwards the seed stream and
    # still matches the rebuild exactly
    legacy = hn.build_hnsw(base[:200], m=4, ef_construction=12, seed=3)
    legacy.rng = None
    hn.insert_hnsw(legacy, extra[:16])
    np.testing.assert_array_equal(legacy.level_of, idx.level_of)
    np.testing.assert_array_equal(legacy.base_adj, idx.base_adj)


def test_hnsw_dirty_log_bounded(data):
    """The dirty log is bounded: once it outgrows ~2n entries it is cleared
    and the epoch bumps, and engines holding a stale epoch full-rebuild
    instead of consuming lost entries — no unbounded host growth under
    sustained insert streams, no stale device graphs."""
    base, extra, _, q = data
    eng = HNSWEngine(base[:300], m=4, ef_construction=12, ef_search=16,
                     seed=3, backend="jnp", layout="blocked")
    eng.search(q, 5)
    idx = eng.index
    idx.dirty_log = [0] * (2 * idx.n + 1025)   # long-consumed service log
    eng.insert(extra[:8])
    assert idx.dirty_epoch == 1
    assert len(idx.dirty_log) <= 2 * idx.n + 1024
    reb = _hnsw_rebuilt(base[:300], extra[:8], m=4, ef_construction=12,
                        ef_search=16, seed=3, backend="jnp",
                        layout="blocked")
    _assert_equal(eng, reb, q, 5, "hnsw dirty-log epoch rebuild")
    assert eng._dirty_epoch == idx.dirty_epoch


def test_hnsw_tpu_insert_scorer_db_cache(data):
    """ISSUE 4 satellite: the tpu insert-frontier scorer appends new rows
    into a cached capacity-padded device db (in the gather kernel's line
    layout) instead of re-uploading the full database every insert batch."""
    base, extra, _, q = data
    eng = HNSWEngine(base[:200], m=4, ef_construction=12, ef_search=16,
                     seed=3, backend="tpu")
    assert eng._insert_db_cache is None
    eng.insert(extra[:4])
    dev, filled = eng._insert_db_cache
    w = base.shape[1]
    assert filled == 204 and ops.line_ids(w, 203) < dev.shape[0]
    cap0 = dev.shape[0]
    eng.insert(extra[4:8])
    dev2, filled2 = eng._insert_db_cache
    assert filled2 == 208 and dev2.shape[0] == cap0   # appended in place
    # cached lines hold exactly the index's fingerprints, zero past them
    np.testing.assert_array_equal(
        np.asarray(dev2), ops.db_lines_at(eng.index.db, np.arange(cap0)))
    reb = _hnsw_rebuilt(base[:200], extra[:8], m=4, ef_construction=12,
                        ef_search=16, seed=3, backend="tpu")
    _assert_equal(eng, reb, q, 5, "hnsw/tpu scorer cache")


def test_insert_returns_global_ids(data):
    base, extra, _, _ = data
    eng = BruteForceEngine(base, compact_threshold=10_000)
    g1 = eng.insert(extra[0])                  # single row broadcastable
    g2 = eng.insert(extra[1:4])
    np.testing.assert_array_equal(g1, [len(base)])
    np.testing.assert_array_equal(g2, np.arange(len(base) + 1, len(base) + 4))
    assert eng.insert(np.empty((0, base.shape[1]), np.uint32)).size == 0
