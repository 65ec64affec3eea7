"""ISSUE 5 acceptance: sharded HNSW fan-out traversal + rank-merge.

Contracts pinned here:

* ``HNSWEngine(shards=1)`` is **bit-identical** to the unsharded engine
  (same build seed, identity merge).
* Multi-shard recall at the fig8 operating point is within 0.01 of the
  unsharded engine (partition-then-merge covers the global top-k as long as
  each shard covers its local share).
* The device backends stay bit-exact with each other *through the
  fan-out* (same per-shard graph walks, same merge).
* Online inserts route round-robin and stay rebuild-identical.
* On a forced multi-device host platform the per-shard graphs land on
  distinct devices and results don't change (subprocess, like
  ``tests/test_distributed.py``).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import BruteForceEngine, HNSWEngine, recall_at_k
from repro.core import hnsw as hn
from repro.data.molecules import (SyntheticConfig, queries_from_db,
                                  synthetic_fingerprints)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
K = 10


@pytest.fixture(scope="module")
def corpus():
    db = synthetic_fingerprints(SyntheticConfig(n=2_000, seed=42))
    q = queries_from_db(db, 8, seed=43)
    true_ids, _ = BruteForceEngine(db).search(q, K)
    return db, q, true_ids


@pytest.fixture(scope="module")
def unsharded(corpus):
    db, q, _ = corpus
    eng = HNSWEngine(db, m=8, ef_construction=40, ef_search=32,
                     backend="jnp")
    ids, sims = eng.search(q, K)
    return ids, sims


def test_one_shard_bit_parity(corpus, unsharded):
    """shards=1 == unsharded, bit for bit (ids and sims)."""
    db, q, _ = corpus
    ids0, sims0 = unsharded
    eng = HNSWEngine(db, m=8, ef_construction=40, ef_search=32,
                     backend="jnp", shards=1)
    ids, sims = eng.search(q, K)
    np.testing.assert_array_equal(ids, ids0)
    np.testing.assert_array_equal(sims, sims0)


def test_multi_shard_recall_pin(corpus, unsharded):
    """>= 2 shards: recall within 0.01 of unsharded at the fig8 point."""
    db, q, true_ids = corpus
    ids0, _ = unsharded
    r0 = recall_at_k(ids0, true_ids)
    for shards in (2, 4):
        eng = HNSWEngine(db, m=8, ef_construction=40, ef_search=32,
                         backend="jnp", shards=shards)
        ids, sims = eng.search(q, K)
        r = recall_at_k(ids, true_ids)
        assert r >= r0 - 0.01, (shards, r, r0)
        # self-queries still find themselves at full similarity
        assert (sims[:, 0] >= 1.0 - 1e-6).all(), shards
        assert eng.stats["shards"] == shards
        assert len(eng.stats["per_shard"]) == shards


def test_sharded_backend_layout_parity(corpus):
    """Every device backend is bit-exact through the fan-out."""
    db, q, _ = corpus
    db, q = db[:1200], q[:4]
    base = None
    for backend in ("jnp", "tpu"):
        eng = HNSWEngine(db, m=8, ef_construction=40, ef_search=32,
                         backend=backend, shards=2)
        ids, sims = eng.search(q, K)
        if base is None:
            base = (ids, sims)
        else:
            np.testing.assert_array_equal(ids, base[0], err_msg=backend)
            np.testing.assert_array_equal(sims, base[1], err_msg=backend)


def test_sharded_numpy_backend(corpus, unsharded):
    """Host-reference fan-out: same merge semantics, recall-pinned."""
    db, q, true_ids = corpus
    ids0, _ = unsharded
    eng = HNSWEngine(db, m=8, ef_construction=40, ef_search=32,
                     backend="numpy", shards=2)
    ids, sims = eng.search(q, K)
    assert recall_at_k(ids, true_ids) >= \
        recall_at_k(ids0, true_ids) - 0.01
    assert (sims[ids < 0] == 0.0).all()
    assert eng.scanned(len(q)) > 0


def test_sharded_insert_matches_rebuild(corpus):
    """Round-robin insert routing: an engine grown online in two batches is
    identical to one rebuilt by the same per-shard bulk build and the
    inserts in one batch (per-shard insert parity)."""
    db, q, _ = corpus
    grown = HNSWEngine(db[:1990], m=8, ef_construction=40, ef_search=32,
                       backend="jnp", shards=2)
    gids = np.concatenate([grown.insert(db[1990:1995]),
                           grown.insert(db[1995:])])
    np.testing.assert_array_equal(gids, np.arange(1990, 2000))
    assert grown.n_total == 2000
    rebuilt = HNSWEngine(db[:1990], m=8, ef_construction=40, ef_search=32,
                         backend="jnp", shards=2)
    rebuilt.insert(db[1990:])
    ids_g, sims_g = grown.search(q, K)
    ids_r, sims_r = rebuilt.search(q, K)
    np.testing.assert_array_equal(ids_g, ids_r)
    np.testing.assert_array_equal(sims_g, sims_r)


def test_shards_validation(corpus):
    db, _, _ = corpus
    with pytest.raises(ValueError, match="either index= or shards="):
        HNSWEngine(db[:64], m=4, index=hn.build_hnsw(db[:64], m=4),
                   shards=2)
    with pytest.raises(ValueError, match="cannot split"):
        HNSWEngine(db[:4], m=4, shards=8)


def test_round_robin_invariant_guard(corpus):
    """insert_hnsw_sharded refuses shard lists that break round-robin."""
    db, _, _ = corpus
    idxs = hn.build_hnsw_sharded(db[:100], 2, m=4, ef_construction=10)
    bad = [idxs[0], hn.build_hnsw(db[:30], m=4, ef_construction=10)]
    with pytest.raises(ValueError, match="round-robin"):
        hn.insert_hnsw_sharded(bad, db[100:104])


def test_sharded_search_hnsw_module_api(corpus, unsharded):
    """The core-module fan-out (build_hnsw_sharded -> to_device_graph_sharded
    -> search_hnsw_sharded) matches the engine path."""
    db, q, _ = corpus
    idxs = hn.build_hnsw_sharded(np.asarray(db), 2, m=8, ef_construction=40,
                                 seed=0)
    graphs = hn.to_device_graph_sharded(idxs)
    gids, sims, stats = hn.search_hnsw_sharded(graphs, q, K, ef=32,
                                               beam=hn.auto_beam(32))
    eng = HNSWEngine(db, m=8, ef_construction=40, ef_search=32,
                     backend="jnp", shards=2)
    ids_e, sims_e = eng.search(q, K)
    np.testing.assert_array_equal(np.asarray(gids), ids_e)
    np.testing.assert_array_equal(np.asarray(sims), sims_e)
    assert len(stats) == 2


def test_forced_multi_device_placement():
    """On an 8-device host platform the shard graphs land on distinct
    devices; parity and the recall pin hold."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    code = textwrap.dedent("""
        import jax, numpy as np
        assert len(jax.devices()) == 8
        from repro.core import HNSWEngine, BruteForceEngine, recall_at_k
        from repro.data.molecules import (SyntheticConfig, queries_from_db,
                                          synthetic_fingerprints)
        db = synthetic_fingerprints(SyntheticConfig(n=1200, seed=42))
        q = queries_from_db(db, 4, seed=43)
        true_ids, _ = BruteForceEngine(db).search(q, 10)
        base = HNSWEngine(db, m=8, ef_construction=40, ef_search=32,
                          backend="jnp")
        ids0, sims0 = base.search(q, 10)
        one = HNSWEngine(db, m=8, ef_construction=40, ef_search=32,
                         backend="jnp", shards=1)
        ids1, sims1 = one.search(q, 10)
        np.testing.assert_array_equal(ids0, ids1)
        np.testing.assert_array_equal(sims0, sims1)
        sh = HNSWEngine(db, m=8, ef_construction=40, ef_search=32,
                        backend="jnp", shards=4)
        devs = {next(iter(g.db.devices())) for g in sh._shard_graphs}
        assert len(devs) == 4, devs
        ids, _ = sh.search(q, 10)
        r0, r = recall_at_k(ids0, true_ids), recall_at_k(ids, true_ids)
        assert r >= r0 - 0.01, (r, r0)
        print("SHARDED_8DEV_OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SHARDED_8DEV_OK" in out.stdout
