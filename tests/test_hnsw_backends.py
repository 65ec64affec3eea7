"""HNSW backend parity at >=10k, and the device graph's neighbour blocks.

``HNSWEngine(backend="tpu")`` (Pallas kernels, interpret mode off-TPU) must
match the ``jnp`` backend's recall within 0.01 on a 10k-fingerprint random
database, and return bit-identical results: the fused expand kernel and its
jnp twin walk the same graph with the same arithmetic.
"""
import numpy as np
import pytest

from repro.core import BruteForceEngine, HNSWEngine, recall_at_k
from repro.core import hnsw as hn
from repro.data.molecules import (SyntheticConfig, queries_from_db,
                                  synthetic_fingerprints)
from repro.kernels import ops


@pytest.fixture(scope="module")
def big_index():
    db = synthetic_fingerprints(SyntheticConfig(n=10_000, seed=42))
    idx = hn.build_hnsw(np.asarray(db), m=8, ef_construction=40, seed=0)
    return db, idx


def test_tpu_matches_jnp_recall_at_10k(big_index):
    db, idx = big_index
    q = queries_from_db(db, 8, seed=43)
    true, _ = BruteForceEngine(db).search(q, 10)
    recalls = {}
    stats = {}
    results = {}
    for backend in ("jnp", "tpu"):
        eng = HNSWEngine(db, index=idx, backend=backend, ef_search=32)
        ids, sims = eng.search(q, 10)
        results[backend] = (ids, sims)
        recalls[backend] = recall_at_k(ids, true)
        stats[backend] = eng.stats
        # self-queries must find themselves at full similarity
        assert (sims[:, 0] >= 1.0 - 1e-6).all(), backend
    assert abs(recalls["jnp"] - recalls["tpu"]) <= 0.01, recalls
    assert recalls["jnp"] >= 0.6, recalls  # navigates at scale
    # the kernels are bit-exact with the jnp stages (same graph walk, same
    # arithmetic, same sort)
    np.testing.assert_array_equal(results["tpu"][0], results["jnp"][0])
    np.testing.assert_array_equal(results["tpu"][1], results["jnp"][1])
    expans = {k: s["expansions"] for k, s in stats.items()}
    assert len(set(expans.values())) == 1, expans


@pytest.mark.parametrize("kernel", [False, True])
def test_blocked_device_graph_carries_neighbour_blocks(big_index, kernel):
    """The device graph's neighbour blocks really are the packed adjacency
    fingerprints (nbr_fps[v, j] == db[base_adj[v, j]], zero rows for -1
    slots) — the layout the expand stage streams. A kernel graph carries
    them only as the expand kernel's slabs."""
    db, idx = big_index
    g = hn.to_device_graph(idx, kernel=kernel)
    base = np.asarray(g.base_adj)
    dbv = np.asarray(g.db)
    if kernel:
        assert g.nbr_fps is None and g.nbr_cnt is None
        packed = np.where((base >= 0)[:, :, None], dbv[np.maximum(base, 0)],
                          0).astype(np.uint32)
        np.testing.assert_array_equal(np.asarray(g.nbr_slabs),
                                      ops.nbr_slabs(packed))
        return
    assert g.nbr_slabs is None and g.db_lines is None
    nbr = np.asarray(g.nbr_fps)
    rng = np.random.default_rng(0)
    for v in rng.integers(0, idx.n, 32):
        for j in range(base.shape[1]):
            e = base[v, j]
            want = dbv[e] if e >= 0 else np.zeros(dbv.shape[1], dbv.dtype)
            np.testing.assert_array_equal(nbr[v, j], want)
    assert g.nbr_cnt.shape == base.shape
