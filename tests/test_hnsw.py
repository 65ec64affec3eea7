"""HNSW: build invariants, accelerated search recall, backend parity."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # collection-safe fallback (see tests/_propcheck.py)
    from _propcheck import given, settings, strategies as st

from repro.core import hnsw as hn
from repro.core import HNSWEngine, recall_at_k
from repro.data.molecules import SyntheticConfig, synthetic_fingerprints, queries_from_db


@pytest.fixture(scope="module")
def tiny_index():
    db = synthetic_fingerprints(SyntheticConfig(n=800, seed=3))
    return db, hn.build_hnsw(db, m=8, ef_construction=40, seed=0)


def test_degree_bounds(tiny_index):
    db, idx = tiny_index
    assert idx.base_adj.shape == (800, 16)           # 2M at base
    # no self-loops, ids in range
    for i in range(800):
        row = idx.base_adj[i]
        valid = row[row >= 0]
        assert (valid != i).all()
        assert (valid < 800).all()
    for l, adj in enumerate(idx.level_adj, start=1):
        assert adj.shape[1] == idx.m


def test_layers_nested(tiny_index):
    """Every node at level l also exists at all lower levels (hierarchy)."""
    db, idx = tiny_index
    prev = None
    for l in range(len(idx.level_nodes), 0, -1):
        nodes = set(idx.level_nodes[l - 1].tolist())
        if prev is not None:
            assert prev <= nodes
        prev = nodes


def test_entry_point_at_top(tiny_index):
    db, idx = tiny_index
    if idx.max_level > 0:
        assert idx.entry_point in set(idx.level_nodes[-1].tolist())


def test_base_layer_connected_enough(tiny_index):
    """BFS from the entry point reaches nearly every node (the paper's
    long-range links keep the graph navigable)."""
    db, idx = tiny_index
    seen = {int(idx.entry_point)}
    frontier = [int(idx.entry_point)]
    while frontier:
        nxt = []
        for u in frontier:
            for v in idx.base_adj[u]:
                v = int(v)
                if v >= 0 and v not in seen:
                    seen.add(v)
                    nxt.append(v)
        frontier = nxt
    assert len(seen) >= 0.95 * idx.n


def test_search_recall_vs_bruteforce(tiny_index):
    db, idx = tiny_index
    q = queries_from_db(db, 16, seed=5)
    eng = HNSWEngine(db, index=idx, ef_search=64)
    ids, sims = eng.search(q, 10)
    # oracle
    import jax.numpy as jnp
    from repro.core import batched_tanimoto_scores
    s = np.asarray(batched_tanimoto_scores(jnp.asarray(q), jnp.asarray(db)))
    true = np.argsort(-s, axis=1, kind="stable")[:, :10]
    rec = recall_at_k(ids, true)
    assert rec >= 0.8, rec
    # self-query must find itself (similarity 1)
    assert (sims[:, 0] >= 1.0 - 1e-6).all()


def _truth(db, q, k=10):
    import jax.numpy as jnp
    from repro.core import batched_tanimoto_scores
    s = np.asarray(batched_tanimoto_scores(jnp.asarray(q), jnp.asarray(db)))
    return np.argsort(-s, axis=1, kind="stable")[:, :k]


@given(st.integers(0, 2**31 - 1), st.sampled_from([16, 48]),
       st.sampled_from([1, 2, 4]))
@settings(max_examples=4, deadline=None)
def test_backend_parity_jnp_vs_tpu(seed, ef, beam):
    """The jnp and tpu (Pallas gather kernel) backends run the same traversal
    with the same arithmetic — recall must match within 0.01 and sims must
    agree on small random databases (satellite of ISSUE 2)."""
    db = synthetic_fingerprints(SyntheticConfig(n=300, seed=seed % 1000))
    idx = hn.build_hnsw(np.asarray(db), m=4, ef_construction=20, seed=0)
    q = queries_from_db(db, 4, seed=seed % 977)
    true = _truth(db, q, 5)
    recalls = {}
    sims_by_backend = {}
    for backend in ("jnp", "tpu"):
        eng = HNSWEngine(db, index=idx, backend=backend, beam=beam)
        ids, sims = eng.search(q, 5, ef=ef)
        recalls[backend] = recall_at_k(ids, true)
        sims_by_backend[backend] = sims
    assert abs(recalls["jnp"] - recalls["tpu"]) <= 0.01, recalls
    np.testing.assert_allclose(sims_by_backend["jnp"],
                               sims_by_backend["tpu"], rtol=1e-6)


def test_numpy_backend_reference_recall(tiny_index):
    """Host reference traversal reaches the same recall ballpark as the
    device path on the same index."""
    db, idx = tiny_index
    q = queries_from_db(db, 8, seed=9)
    true = _truth(db, q, 10)
    recs = {}
    for backend in ("numpy", "jnp"):
        eng = HNSWEngine(db, index=idx, backend=backend, ef_search=64)
        ids, _ = eng.search(q, 10)
        recs[backend] = recall_at_k(ids, true)
    assert recs["numpy"] >= 0.9, recs
    assert abs(recs["numpy"] - recs["jnp"]) <= 0.05, recs


def test_traversal_stats_surface(tiny_index):
    """Telemetry contract: iterations / expansions / termination reasons come
    through HNSWEngine.stats (no private back-channel)."""
    db, idx = tiny_index
    q = queries_from_db(db, 8, seed=11)
    eng = HNSWEngine(db, index=idx, ef_search=32, backend="jnp")
    assert eng.stats == {}                      # nothing before a search
    eng.search(q, 5)
    st_ = eng.stats
    assert st_["iters"] > 0 and st_["expansions"] > 0
    assert st_["neighbour_evals"] == st_["expansions"] * idx.base_adj.shape[1]
    assert st_["converged"] + st_["max_iters_hit"] == len(q)
    assert st_["iters_per_query"].shape == (len(q),)
    assert not hasattr(eng, "_last_iters")      # back-channel removed
    # a tiny budget must terminate queries with the budget reason
    tight = HNSWEngine(db, index=idx, ef_search=64, backend="jnp", max_iters=2)
    tight.search(q, 5)
    assert tight.stats["max_iters_hit"] == len(q)


def test_beam_expansion_cuts_iterations(tiny_index):
    """Multi-candidate beam expansion amortises traversal: ~B fewer
    lock-step iterations at equivalent recall."""
    db, idx = tiny_index
    q = queries_from_db(db, 8, seed=12)
    true = _truth(db, q, 10)
    stats = {}
    recs = {}
    for beam in (1, 4):
        eng = HNSWEngine(db, index=idx, ef_search=64, backend="jnp", beam=beam)
        ids, _ = eng.search(q, 10)
        stats[beam] = eng.stats["iters"]
        recs[beam] = recall_at_k(ids, true)
    assert stats[4] < stats[1] / 2, stats
    assert recs[4] >= recs[1] - 0.05, recs


def test_beam_auto_tune_default(tiny_index):
    """ISSUE 3 satellite: ``HNSWEngine(beam=None)`` picks the beam from
    ``ef_search`` (ROADMAP telemetry rule), at equal recall vs ``beam=1``
    with fewer lock-step iterations."""
    db, idx = tiny_index
    # the rule itself
    assert hn.auto_beam(64) == 4 and hn.auto_beam(16) == 1
    assert hn.auto_beam(128) == 8 and hn.auto_beam(1024) == 8  # clamped
    assert HNSWEngine(db, index=idx, ef_search=64).beam == 4
    assert HNSWEngine(db, index=idx, ef_search=16).beam == 1
    # equal-recall pin vs beam=1 on the tiny grid
    q = queries_from_db(db, 16, seed=13)
    true = _truth(db, q, 10)
    auto = HNSWEngine(db, index=idx, ef_search=64, backend="jnp")
    ids_a, _ = auto.search(q, 10)
    iters_auto = auto.stats["iters"]
    one = HNSWEngine(db, index=idx, ef_search=64, backend="jnp", beam=1)
    ids_1, _ = one.search(q, 10)
    assert recall_at_k(ids_a, true) == recall_at_k(ids_1, true), \
        (recall_at_k(ids_a, true), recall_at_k(ids_1, true))
    assert iters_auto < one.stats["iters"]


def test_recall_increases_with_ef(tiny_index):
    db, idx = tiny_index
    q = queries_from_db(db, 16, seed=6)
    import jax.numpy as jnp
    from repro.core import batched_tanimoto_scores
    s = np.asarray(batched_tanimoto_scores(jnp.asarray(q), jnp.asarray(db)))
    true = np.argsort(-s, axis=1, kind="stable")[:, :10]
    eng = HNSWEngine(db, index=idx)
    recs = []
    for ef in (10, 40, 120):
        ids, _ = eng.search(q, 10, ef=ef)
        recs.append(recall_at_k(ids, true))
    assert recs[-1] >= recs[0] - 0.02, recs
    assert recs[-1] >= 0.85, recs


# -- the bulk build on the device ---------------------------------------------

def _sequential(db, **kw):
    """The sequential Alg. 1/2/4 insertion: every row inserted online, one
    node at a time, into an empty index."""
    idx = hn.build_hnsw(db[:0], **kw)
    hn.insert_hnsw(idx, db)
    return idx


def _assert_same_graph(a, b):
    np.testing.assert_array_equal(a.base_adj, b.base_adj)
    np.testing.assert_array_equal(a.level_of, b.level_of)
    assert (a.entry_point, a.max_level) == (b.entry_point, b.max_level)
    assert len(a.level_nodes) == len(b.level_nodes)
    for x, y in zip(a.level_nodes + a.level_adj, b.level_nodes + b.level_adj):
        np.testing.assert_array_equal(x, y)


def test_build_schedule_grows_with_the_graph():
    rounds = hn.build_schedule(20_000, 512)
    assert rounds[0] == (0, 1) and rounds[-1][1] == 20_000
    assert all(a == b for (_, a), (b, _) in zip(rounds, rounds[1:]))
    sizes = [b - a for a, b in rounds]
    assert sizes[:hn.ROUND_GROWTH] == [1] * hn.ROUND_GROWTH
    assert max(sizes) == 512
    assert all(b - a <= max(1, a // hn.ROUND_GROWTH) for a, b in rounds)
    assert hn.build_schedule(0, 8) == []
    with pytest.raises(ValueError):
        hn.build_schedule(10, 0)


@pytest.mark.parametrize("spec", ["tanimoto", "cosine"])
def test_one_row_a_round_is_the_sequential_build(spec):
    """At one row a round, and a beam of one (efConstruction 24), the
    batched device build walks the sequential insertion step for step: the
    same levels, entry point and adjacency, order included. Its searches
    order equal scores by id as the host's heaps do, so ties fall the
    same way."""
    from repro.core.fingerprints import resolve_metric
    db = synthetic_fingerprints(SyntheticConfig(n=500, seed=0))
    kw = dict(m=6, ef_construction=24, seed=3, metric=resolve_metric(spec))
    assert hn.auto_beam(kw["ef_construction"]) == 1
    _assert_same_graph(hn.build_hnsw(db, build_round=1, **kw),
                       _sequential(db, **kw))


def test_the_bulk_build_is_deterministic_and_never_inserts_on_the_host(
        monkeypatch):
    db = synthetic_fingerprints(SyntheticConfig(n=500, seed=5))

    def refuse(*a, **kw):
        raise AssertionError("the bulk build called _insert_node")
    monkeypatch.setattr(hn, "_insert_node", refuse)
    a = hn.build_hnsw(db, m=6, ef_construction=24, seed=7, build_round=64)
    b = hn.build_hnsw(db, m=6, ef_construction=24, seed=7, build_round=64)
    _assert_same_graph(a, b)
    assert (a.build_round, a.bulk_rows, a.seed) == (64, 500, 7)
    c = hn.build_hnsw(db, m=6, ef_construction=24, seed=7, build_round=16)
    assert not np.array_equal(a.base_adj, c.base_adj)  # the round defines it


@pytest.mark.parametrize("round_", [1024, 16])
def test_batched_build_graph_invariants(round_):
    db = synthetic_fingerprints(SyntheticConfig(n=500, seed=2))
    m = 6
    idx = hn.build_hnsw(db, m=m, ef_construction=24, seed=1,
                        build_round=round_)
    for adj, cap in [(idx.base_adj, 2 * m)] + [(a, m) for a in idx.level_adj]:
        assert adj.shape[1] == cap
        for r, row in enumerate(adj):
            valid = row[row >= 0]
            assert (row[len(valid):] < 0).all()          # a compact prefix
            assert len(set(valid.tolist())) == len(valid)  # no duplicates
            assert (valid < idx.n).all()
    assert all((idx.base_adj[i] != i).all() for i in range(idx.n))
    for l, (nodes, adj) in enumerate(zip(idx.level_nodes, idx.level_adj), 1):
        np.testing.assert_array_equal(nodes,
                                      np.flatnonzero(idx.level_of >= l))
        assert not (adj == nodes[:, None]).any()          # no self-links
        assert np.isin(adj[adj >= 0], nodes).all()        # links stay in level
    assert idx.level_of[idx.entry_point] == idx.max_level == idx.level_of.max()
    # nearly every row is reachable from the entry point
    seen, frontier = {idx.entry_point}, [idx.entry_point]
    while frontier:
        frontier = [int(v) for u in frontier for v in idx.base_adj[u]
                    if v >= 0 and int(v) not in seen]
        seen.update(frontier)
    assert len(seen) >= 0.98 * idx.n


def test_batched_build_recall_matches_the_sequential_build():
    """At 3,000 rows the batched build's recall@20 against the brute
    oracle is within 0.01 of the sequential build's, and every returned
    score is the oracle's score of that row."""
    import jax.numpy as jnp
    from repro.core import batched_tanimoto_scores
    db = synthetic_fingerprints(SyntheticConfig(n=3000, seed=0))
    q = queries_from_db(db, 64, seed=4)
    s = np.asarray(batched_tanimoto_scores(jnp.asarray(q), jnp.asarray(db)))
    kth = -np.sort(-s, axis=1)[:, 19]
    kw = dict(m=6, ef_construction=24, seed=3)
    recall = {}
    for name, idx in (("batched", hn.build_hnsw(db, build_round=256, **kw)),
                      ("sequential", _sequential(db, **kw))):
        eng = HNSWEngine(db, index=idx, ef_search=20, backend="jnp")
        ids, sims = eng.search(q, 20)
        assert (ids >= 0).all()
        np.testing.assert_array_equal(
            sims, np.take_along_axis(s, ids, axis=1))   # exact scores
        recall[name] = float(np.mean(sims >= kth[:, None]))
    assert recall["batched"] >= recall["sequential"] - 0.01, recall


# -- the traversal's queue merge ------------------------------------------------

def _searchsorted_merge(s_a, i_a, s_b, i_b):
    """The rank merge by two binary searches and a scatter that the
    traversal's queues used before: kept here only as an oracle."""
    import jax.numpy as jnp
    a, b = s_a.shape[0], s_b.shape[0]
    na, nb = -s_a, -s_b
    pos_a = jnp.arange(a) + jnp.searchsorted(nb, na, side="left")
    pos_b = jnp.arange(b) + jnp.searchsorted(na, nb, side="right")
    out_s = jnp.zeros((a + b,), s_a.dtype).at[pos_a].set(s_a).at[pos_b].set(s_b)
    out_i = jnp.zeros((a + b,), i_a.dtype).at[pos_a].set(i_a).at[pos_b].set(i_b)
    return out_s[:a], out_i[:a]


def test_search_is_bit_identical_to_the_searchsorted_merge(tiny_index,
                                                           monkeypatch):
    """The jnp-backend traversal returns the same ids, sims and
    TraversalStats, bit for bit, with the merge it has and with the
    binary-search merge: the queues hold the same entries in the same order
    at every iteration."""
    import jax
    import jax.numpy as jnp
    db, idx = tiny_index
    g = hn.to_device_graph(idx)
    q = jnp.asarray(queries_from_db(db, 16, seed=21))

    def run():
        out = jax.jit(lambda qs: hn.search_hnsw(g, qs, 10, 64, beam=4))(q)
        return jax.tree.map(np.asarray, out)

    ids, sims, stats = run()
    monkeypatch.setattr(hn, "merge_sorted", _searchsorted_merge)
    ids_o, sims_o, stats_o = run()
    np.testing.assert_array_equal(ids, ids_o)
    np.testing.assert_array_equal(sims.view(np.int32), sims_o.view(np.int32))
    for got, want in zip(stats, stats_o):
        np.testing.assert_array_equal(got, want)
    assert (ids >= 0).all() and stats.iters.sum() > 0


# -- the traversal's dedup against the result queue ----------------------------

def _digest(*arrays):
    import hashlib
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _search_digest(tiny_index):
    import jax
    import jax.numpy as jnp
    db, idx = tiny_index
    g = hn.to_device_graph(idx)
    q = jnp.asarray(queries_from_db(db, 16, seed=21))
    out = jax.jit(lambda qs: hn.search_hnsw(g, qs, 10, 64, beam=4))(q)
    ids, sims, st_ = jax.tree.map(np.asarray, out)
    return _digest(ids.astype("<i4"), sims.astype("<f4").view("<i4"),
                   st_.iters.astype("<i4"), st_.expansions.astype("<i4"),
                   st_.reason.astype("<i4"))


def _build_digest(tiny_index):
    db = synthetic_fingerprints(SyntheticConfig(n=3000, seed=0))
    idx = hn.build_hnsw(db, m=16, ef_construction=100, seed=0,
                        build_round=512)
    return _digest(idx.base_adj.astype("<i4"))


# taken on the traversal that marked a per-query visited bitset slot by
# slot: the dedup against the result queue must give the same searches
# (ids, sims as bits, iterations, expansions, stop reasons) and the same
# bulk build (the id-tied path), bit for bit
@pytest.mark.parametrize("what,digest", [
    (_search_digest,
     "430bc472afa1676574b2c8044733b6bb0be7b4275a4097b2b35ab8a50bee61c7"),
    (_build_digest,
     "ffb9e2882fccd6da3ae71f0a7909996bab9d5ca5ff9cc247c3fa4720f5af8cce"),
], ids=["search", "build"])
def test_traversal_is_bit_identical_to_the_visited_bitset(tiny_index, what,
                                                          digest):
    assert what(tiny_index) == digest


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_no_id_returned_twice(seed):
    """The whole result queue (k = ef) holds each node once, at every
    beam, with neighbours shared between the popped candidates."""
    import jax
    import jax.numpy as jnp
    db = synthetic_fingerprints(SyntheticConfig(n=600, seed=seed))
    idx = hn.build_hnsw(db, m=6, ef_construction=24, seed=seed)
    g = hn.to_device_graph(idx)
    q = jnp.asarray(queries_from_db(db, 16, seed=seed + 30))
    for beam in (1, 4):
        ids, _, _ = jax.tree.map(np.asarray, jax.jit(
            lambda qs: hn.search_hnsw(g, qs, 32, 32, beam=beam))(q))
        for row in ids:
            real = row[row >= 0]
            assert len(real) and len(set(real.tolist())) == len(real), row


def test_deduped_slots_are_counted(tiny_index):
    """``deduped`` counts the neighbour slots the traversal masked as
    already in the result queue or repeated in the expansion: some on
    every search that expands, never more than the slots expanded."""
    import jax
    import jax.numpy as jnp
    db, idx = tiny_index
    g = hn.to_device_graph(idx)
    m2 = idx.base_adj.shape[1]
    q = jnp.asarray(queries_from_db(db, 16, seed=22))
    _, _, st_ = jax.tree.map(np.asarray, jax.jit(
        lambda qs: hn.search_hnsw(g, qs, 10, 64, beam=4))(q))
    assert st_.deduped.shape == st_.expansions.shape
    assert (st_.deduped > 0).all()
    assert (st_.deduped <= st_.expansions * m2).all()
    eng = HNSWEngine(db, index=idx, ef_search=64, backend="jnp")
    eng.search(np.asarray(q), 10)
    assert 0 < eng.stats["deduped"] <= eng.stats["neighbour_evals"]
