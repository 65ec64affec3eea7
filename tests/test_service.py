"""SearchService behaviour: dynamic micro-batching, engine routing, insert
broadcast + compaction, telemetry, and the service-level insert-then-search
parity acceptance (ISSUE 3)."""
import numpy as np
import pytest

from repro.core import BruteForceEngine, BitBoundFoldingEngine, HNSWEngine
from repro.data.molecules import (SyntheticConfig, queries_from_db,
                                  synthetic_fingerprints)
from repro.serve import SearchService


@pytest.fixture(scope="module")
def data():
    db = synthetic_fingerprints(SyntheticConfig(n=600, seed=0))
    extra = synthetic_fingerprints(SyntheticConfig(n=80, seed=5))
    q = queries_from_db(db, 12, seed=2)
    return db, extra, q


def test_micro_batching_matches_direct_engine(data):
    db, extra, q = data
    svc = SearchService(db, engines=("brute", "bitbound-folding"),
                        backend="jnp", k=8, cutoff=0.4, fold_m=2)
    # mixed request sizes -> one (engine, k) batch padded to a pow2 bucket
    bb = "bitbound-folding"
    r1 = svc.submit(q[0], engine=bb)            # single-row request
    r2 = svc.submit(q[1:4], engine=bb)
    r3 = svc.submit(q[4:9], engine=bb)
    r4 = svc.submit(q[:2], engine="brute")
    done = svc.flush()
    assert set(done) == {r1, r2, r3, r4}
    # per-request slices equal a direct engine call on the same batch
    eng = svc.engines["bitbound-folding"]
    ids, sims = eng.search(q[:9], 8)
    for rid, sl in ((r1, slice(0, 1)), (r2, slice(1, 4)), (r3, slice(4, 9))):
        np.testing.assert_array_equal(done[rid][0], ids[sl])
        np.testing.assert_array_equal(done[rid][1], sims[sl])
    bids, bsims = svc.engines["brute"].search(q[:2], 8)
    np.testing.assert_array_equal(done[r4][0], bids)
    np.testing.assert_array_equal(done[r4][1], bsims)
    # batches padded to power-of-two buckets; zero-pad queries are dropped
    buckets = [b["bucket"] for b in svc.batches]
    assert all(b & (b - 1) == 0 for b in buckets)
    assert sorted(buckets) == [2, 16]          # 9 queries -> 16, 2 -> 2
    assert len(svc.latencies_ms) == 4


def test_router_rejects_unknown_engine(data):
    db, _, q = data
    svc = SearchService(db, engines=("brute",))
    with pytest.raises(ValueError, match="engine"):
        svc.submit(q[0], engine="hnsw")
    with pytest.raises(ValueError, match="engine"):
        SearchService(db, engines=("fpga",))


def test_insert_broadcast_and_compaction_counts(data):
    db, extra, q = data
    svc = SearchService(db, engines=("brute", "bitbound-folding"),
                        backend="jnp", compact_threshold=50)
    g = svc.insert(extra[:30])
    np.testing.assert_array_equal(g, np.arange(600, 630))
    assert svc.compactions == 0
    svc.insert(extra[30:60])                   # both stores cross threshold
    assert svc.compactions == 2                # one per store-backed engine
    for eng in svc.engines.values():
        assert eng.n_total == 660
    s = svc.summary()
    assert s["n_inserts"] == 60 and s["compactions"] == 2


def _hnsw_rebuilt(d, n_bulk, **kw):
    eng = HNSWEngine(d[:n_bulk], **kw)
    eng.insert(d[n_bulk:])
    return eng


def test_service_parity_with_rebuilt_engines(data):
    """Acceptance: a service interleaving inserts and queries (across a
    compaction) returns bit-identical results to rebuilt engines — for all
    three engines behind one service. The exhaustive engines are rebuilt
    from scratch on the concatenated database; HNSW by the same bulk build
    over the first rows and the inserts in one batch (its graph is defined
    by where the bulk build stopped)."""
    db, extra, q = data
    svc = SearchService(db, engines=("brute", "bitbound-folding", "hnsw"),
                        backend="jnp", k=10, cutoff=0.4, fold_m=2,
                        compact_threshold=48, hnsw_m=6,
                        hnsw_ef_construction=24, hnsw_ef_search=24, seed=3)
    svc.search(q[:4], 10)                      # pre-insert traffic
    svc.insert(extra[:20])
    mids = {n: svc.search(q, 10, engine=n) for n in svc.engines}
    svc.insert(extra[20:60])                   # crosses the threshold
    assert svc.compactions == 2
    finals = {n: svc.search(q, 10, engine=n) for n in svc.engines}

    mid_db = np.concatenate([db, extra[:20]])
    full_db = np.concatenate([db, extra[:60]])
    rebuilds = {
        "brute": lambda d: BruteForceEngine(d, backend="jnp"),
        "bitbound-folding": lambda d: BitBoundFoldingEngine(
            d, cutoff=0.4, m=2, backend="jnp"),
        "hnsw": lambda d: _hnsw_rebuilt(d, len(db), m=6, ef_construction=24,
                                        ef_search=24, seed=3, backend="jnp"),
    }
    for name, make in rebuilds.items():
        for stage, d, got in (("mid", mid_db, mids[name]),
                              ("final", full_db, finals[name])):
            rids, rsims = make(d).search(q, 10)
            np.testing.assert_array_equal(got[0], rids,
                                          err_msg=f"{name} {stage}")
            np.testing.assert_array_equal(got[1], rsims,
                                          err_msg=f"{name} {stage}")


def test_telemetry_summary_fields(data):
    db, _, q = data
    fake_t = [0.0]

    def clock():
        fake_t[0] += 0.001                     # deterministic 1ms steps
        return fake_t[0]

    svc = SearchService(db, engines=("brute",), backend="jnp", k=5,
                        clock=clock)
    for i in range(6):
        svc.submit(q[i])
    svc.flush()
    svc.search(q[:3], 5)
    s = svc.summary()
    assert s["n_queries"] == 9
    assert s["qps"] > 0 and s["search_time_s"] > 0
    assert s["p50_ms"] > 0 and s["p99_ms"] >= s["p50_ms"]
    assert s["batch_buckets"] == {8: 1, 4: 1}  # 6 -> 8, 3 -> 4
    assert s["scanned"]["brute"] > 0
    assert s["engines"] == {"brute": "jnp"}
    assert svc.compiled_pipelines() > 0


def test_flush_chunks_oversized_batches(data):
    db, _, q = data
    svc = SearchService(db, engines=("brute",), backend="jnp", k=5,
                        max_batch=4)
    rid = svc.submit(q[:10])                   # > max_batch -> 3 chunks
    done = svc.flush()
    ids, sims = done[rid]
    assert ids.shape == (10, 5)
    rids, rsims = svc.engines["brute"].search(q[:10], 5)
    np.testing.assert_array_equal(ids, rids)
    np.testing.assert_array_equal(sims, rsims)
    assert [b["bucket"] for b in svc.batches] == [4, 4, 2]


def test_compact_all_pins_delta_phase(data):
    db, extra, _ = data
    svc = SearchService(db, engines=("brute",), compact_threshold=1000)
    svc.insert(extra[:7])
    assert svc.engines["brute"].store.n_delta == 7
    svc.compact_all()
    assert svc.engines["brute"].store.n_delta == 0
    assert svc.compactions == 1


# -- lifecycle under concurrency (ISSUE 9 satellite) --------------------------

def test_close_idempotent(data, tmp_path):
    db, extra, _ = data
    svc = SearchService(db, engines=("brute",), durable_dir=str(tmp_path))
    svc.insert(extra[:5])
    svc.close()
    svc.close()                          # second close: no-op, no raise
    with pytest.raises(RuntimeError, match="closed"):
        svc.snapshot()
    reopened = SearchService.open(tmp_path)
    assert reopened.n_total == len(db) + 5
    reopened.close()


def test_close_during_background_snapshot_from_other_thread(data, tmp_path):
    """close() racing a background snapshot writer from another thread must
    wait the writer out (the snapshot publishes; the WAL closes after its
    final unpin) instead of closing the WAL underneath it."""
    import threading

    from repro.checkpoint.fs import Fs

    class GatedFs(Fs):
        def __init__(self):
            self.armed = False
            self.entered = threading.Event()
            self.gate = threading.Event()

        def replace(self, src, dst):
            if self.armed:
                self.entered.set()
                assert self.gate.wait(30), "test gate never released"
            super().replace(src, dst)

    db, extra, _ = data
    fs = GatedFs()
    svc = SearchService(db, engines=("brute",), durable_dir=str(tmp_path),
                        fs=fs)
    svc.insert(extra[:10])
    fs.armed = True
    sid = svc.snapshot(background=True)
    assert fs.entered.wait(30), "background writer never started publishing"
    closer = threading.Thread(target=svc.close)
    closer.start()
    closer.join(timeout=0.5)
    assert closer.is_alive(), "close() returned while the writer was gated"
    fs.gate.set()
    closer.join(timeout=30)
    assert not closer.is_alive(), "close() never finished after the writer"
    assert svc._wal is None
    svc.close()                          # still idempotent afterwards
    reopened = SearchService.open(tmp_path)
    assert reopened._snap_id == sid      # the raced snapshot did publish
    assert reopened.n_total == len(db) + 10
    reopened.close()
