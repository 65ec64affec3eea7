"""Snapshot serialization round-trips (ISSUE 6 tentpole, snapshot half).

The core contract: for any engine × backend × layout × shards and any
interleaving of inserts/compactions, serializing the search state and
restoring it yields **byte-equal** extracted state — and *continuing* to
insert into the restored replica tracks the live engine exactly (the HNSW
level-stream rng and the store counters survive the round-trip).
"""
import tempfile

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _propcheck import given, settings, strategies as st

from repro.checkpoint.manager import (load_array_snapshot,
                                      load_latest_intact,
                                      save_array_snapshot, snapshot_steps)
from repro.core import BitBoundFoldingEngine, BruteForceEngine, HNSWEngine
from repro.data.molecules import (SyntheticConfig, queries_from_db,
                                  synthetic_fingerprints)
from repro.serve import SearchService, snapshot as snap

POOL = synthetic_fingerprints(SyntheticConfig(n=420, seed=0))
BASE = POOL[:140]
EXTRA = POOL[140:]
QUERIES = queries_from_db(POOL, 6, seed=4)

# engine-kind × backend × layout × shards grid the property sweep samples
# from ("tpu" rides the interpret-mode Pallas path — covered by the
# service-level tests below to keep the sweep's compile count at zero)
CASES = [
    ("brute", "jnp", None, None),
    ("bitbound", "numpy", None, None),
    ("bitbound", "jnp", None, None),
    ("hnsw", "numpy", "rows", None),
    ("hnsw", "jnp", "rows", None),
    ("hnsw", "jnp", "blocked", None),
    ("hnsw", "numpy", "rows", 2),
    ("hnsw", "jnp", "blocked", 2),
]


def _mk_engine(kind, backend, layout, shards, db):
    if kind == "brute":
        return BruteForceEngine(db, backend=backend, compact_threshold=24)
    if kind == "bitbound":
        return BitBoundFoldingEngine(db, cutoff=0.3, m=2, backend=backend,
                                     compact_threshold=24)
    return HNSWEngine(db, m=4, ef_construction=12, ef_search=16, seed=3,
                      backend=backend, layout=layout, shards=shards)


def _restore_kwargs(kind, backend, layout):
    if kind == "brute":
        return dict(backend=backend, compact_threshold=24)
    if kind == "bitbound":
        return dict(cutoff=0.3, m=2, backend=backend, compact_threshold=24)
    return dict(m=4, ef_construction=12, ef_search=16, seed=3,
                backend=backend, layout=layout)


def _assert_state_equal(e_live, e_restored, label=""):
    a1, m1 = snap.engine_state(e_live)
    a2, m2 = snap.engine_state(e_restored)
    assert m1 == m2, f"{label}: meta diverged"
    assert sorted(a1) == sorted(a2), f"{label}: array names diverged"
    for k in a1:
        assert a1[k].dtype == a2[k].dtype, f"{label}/{k}: dtype"
        assert a1[k].shape == a2[k].shape, f"{label}/{k}: shape"
        assert a1[k].tobytes() == a2[k].tobytes(), f"{label}/{k}: bytes"


def _roundtrip_via_disk(engine):
    arrays, meta = snap.engine_state(engine)
    with tempfile.TemporaryDirectory() as d:
        save_array_snapshot(d, 0, arrays, {"engine": meta})
        loaded, lmeta = load_array_snapshot(d, 0)
    return loaded, lmeta["engine"]


@settings(max_examples=12, deadline=None)
@given(st.sampled_from(CASES),
       st.lists(st.tuples(st.sampled_from(["insert", "compact"]),
                          st.integers(min_value=1, max_value=9)),
                min_size=0, max_size=6),
       st.integers(min_value=0, max_value=200))
def test_snapshot_roundtrip_interleavings(case, ops, off):
    """Random insert/compact/snapshot interleavings: restored state is
    byte-equal to live state, and inserting *after* the restore tracks the
    live engine exactly (rng-stream + counter continuation)."""
    kind, backend, layout, shards = case
    eng = _mk_engine(kind, backend, layout, shards, BASE)
    pos = off % (len(EXTRA) - 64)
    for op, size in ops:
        if op == "insert":
            eng.insert(EXTRA[pos:pos + size])
            pos += size
        elif getattr(eng, "store", None) is not None and eng.store.n_delta:
            eng.store.compact()
    arrays, meta = _roundtrip_via_disk(eng)
    restored = snap.engine_from_state(arrays, meta,
                                      **_restore_kwargs(kind, backend,
                                                        layout))
    label = f"{kind}/{backend}/{layout}/shards={shards}"
    _assert_state_equal(eng, restored, label)
    # continuation: both sides take the same two extra batches
    for a, b in ((pos, pos + 5), (pos + 5, pos + 12)):
        eng.insert(EXTRA[a:b])
        restored.insert(EXTRA[a:b])
    _assert_state_equal(eng, restored, label + " after continuation")
    if backend == "numpy":        # host path: search parity is compile-free
        ids1, sims1 = eng.search(QUERIES, 8)
        ids2, sims2 = restored.search(QUERIES, 8)
        np.testing.assert_array_equal(ids1, ids2, err_msg=label)
        np.testing.assert_array_equal(sims1, sims2, err_msg=label)


@pytest.mark.parametrize("engines,backend,shards", [
    (("brute", "bitbound-folding", "hnsw"), None, None),
    (("bitbound-folding",), "tpu", None),
    (("hnsw",), "jnp", 2),
])
def test_service_snapshot_restore_search_parity(tmp_path, engines, backend,
                                                shards):
    """SearchService.open hydrates a replica whose results are bit-identical
    to the live service and to a never-crashed rebuild — including sharded
    HNSW graphs re-committed to their devices and the tpu kernel path.
    The rebuild is built over the same base rows and inserts the rest, as
    an HNSW graph is defined by where its bulk build stopped."""
    d = tmp_path / "svc"
    svc = SearchService(BASE, engines=engines, durable_dir=str(d),
                        backend=backend, compact_threshold=20,
                        hnsw_m=4, hnsw_ef_construction=12, hnsw_ef_search=16,
                        hnsw_shards=shards)
    for i in range(0, 42, 6):
        svc.insert(EXTRA[i:i + 6])
    svc.snapshot()
    svc.insert(EXTRA[42:50])                    # WAL tail past the snapshot
    live = {e: svc.search(QUERIES, 8, engine=e) for e in engines}
    svc.close()

    svc2 = SearchService.open(d)
    reb = SearchService(BASE, engines=engines,
                        backend=backend, compact_threshold=20, hnsw_m=4,
                        hnsw_ef_construction=12, hnsw_ef_search=16,
                        hnsw_shards=shards)
    reb.insert(EXTRA[:50])      # the bulk build stopped at BASE, as it did
    for e in engines:
        got = svc2.search(QUERIES, 8, engine=e)
        ref = reb.search(QUERIES, 8, engine=e)
        np.testing.assert_array_equal(live[e][0], got[0], err_msg=e)
        np.testing.assert_array_equal(live[e][1], got[1], err_msg=e)
        np.testing.assert_array_equal(ref[0], got[0], err_msg=e)
        np.testing.assert_array_equal(ref[1], got[1], err_msg=e)
    # restored replica keeps inserting in lockstep with the rebuild
    svc2.insert(EXTRA[50:58])
    reb.insert(EXTRA[50:58])
    for e in engines:
        got = svc2.search(QUERIES, 8, engine=e)
        ref = reb.search(QUERIES, 8, engine=e)
        np.testing.assert_array_equal(ref[0], got[0], err_msg=e)
        np.testing.assert_array_equal(ref[1], got[1], err_msg=e)
    svc2.close()


def test_snapshot_retention_and_walkback(tmp_path):
    svc = SearchService(BASE, engines=("brute",), durable_dir=str(tmp_path),
                        compact_threshold=1000, snapshot_keep=2)
    for i in range(4):
        svc.insert(EXTRA[i * 4:(i + 1) * 4])
        svc.snapshot()
    svc.close()
    steps = snapshot_steps(tmp_path / "snapshots")
    assert len(steps) == 2                       # retention honoured
    # corrupt the newest generation: open() must walk back to the previous
    newest = tmp_path / "snapshots" / f"snap_{steps[-1]:08d}"
    victim = sorted(newest.glob("arr_*.npy"))[0]
    victim.write_bytes(victim.read_bytes()[:-7])
    svc2 = SearchService.open(tmp_path)
    # the walk-back snapshot plus the WAL tail still recovers everything
    assert svc2.engines["brute"].n_total == len(BASE) + 16
    svc2.close()


def test_fresh_service_refuses_existing_durable_dir(tmp_path):
    svc = SearchService(BASE[:16], engines=("brute",),
                        durable_dir=str(tmp_path))
    svc.close()
    with pytest.raises(ValueError, match="open"):
        SearchService(BASE[:16], engines=("brute",),
                      durable_dir=str(tmp_path))


def test_open_missing_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        SearchService.open(tmp_path / "void")


def test_load_latest_intact_skips_partial(tmp_path):
    save_array_snapshot(tmp_path, 0, {"x": np.arange(5)}, {"v": 1})
    save_array_snapshot(tmp_path, 1, {"x": np.arange(9)}, {"v": 2})
    (tmp_path / "snap_00000001" / "manifest.json").unlink()
    step, arrays, meta = load_latest_intact(tmp_path)
    assert step == 0 and meta == {"v": 1}
    np.testing.assert_array_equal(arrays["x"], np.arange(5))


# -- background snapshots (ISSUE 7 satellite) -------------------------------

def test_background_snapshot_inserts_keep_acking(tmp_path):
    """Inserts must keep acking while a snapshot writer is parked mid-write:
    the state is handed off as copy-on-write arrays on the serving thread
    and the serialization + fsync runs on a daemon thread."""
    import threading

    from repro.checkpoint.fs import Fs

    gate = threading.Event()
    entered = threading.Event()

    class GateFs(Fs):
        armed = False

        def open(self, path, mode="wb"):
            if self.armed and "snap_" in str(path):
                entered.set()
                assert gate.wait(timeout=30), "test gate never released"
            return super().open(path, mode)

    fs = GateFs()
    svc = SearchService(BASE, engines=("brute",), durable_dir=str(tmp_path),
                        compact_threshold=10_000, fs=fs)
    fs.armed = True
    svc.snapshot(background=True)
    assert entered.wait(timeout=30), "background writer never started"
    # writer is blocked inside the gated open(); the serving thread acks
    gids = []
    for i in range(6):
        gids.extend(svc.insert(EXTRA[i * 3:(i + 1) * 3]).tolist())
    assert svc._snap_thread is not None and svc._snap_thread.is_alive()
    assert gids == list(range(len(BASE), len(BASE) + 18))
    gate.set()
    svc.snapshot_join()
    svc.close()
    # every insert acked during the in-flight snapshot is recoverable
    svc2 = SearchService.open(tmp_path)
    assert svc2.engines["brute"].n_total == len(BASE) + 18
    svc2.close()


def test_background_snapshot_error_surfaces_at_join(tmp_path):
    from repro.checkpoint.fs import Fs

    class BoomFs(Fs):
        armed = False

        def open(self, path, mode="wb"):
            if self.armed and "snap_" in str(path):
                raise IOError("boom")
            return super().open(path, mode)

    fs = BoomFs()
    svc = SearchService(BASE[:32], engines=("brute",),
                        durable_dir=str(tmp_path), fs=fs)
    fs.armed = True
    svc.snapshot(background=True)
    with pytest.raises(IOError, match="boom"):
        svc.snapshot_join()
    svc.close()                               # error already consumed


def test_snapshot_carries_metric_and_refuses_mismatch(tmp_path):
    """Snapshot meta records the similarity metric and fingerprint width;
    a reopen inherits them, and a reopen *overriding* the metric must be
    refused — scores, BitBound windows and HNSW graphs are metric-specific.
    """
    from repro.core.fingerprints import resolve_metric

    svc = SearchService(BASE, engines=("brute", "bitbound-folding", "hnsw"),
                        durable_dir=str(tmp_path), metric="dice",
                        fp_bits=1024, hnsw_m=4, hnsw_ef_construction=12,
                        hnsw_ef_search=16)
    svc.insert(EXTRA[:8])
    svc.snapshot()
    live = svc.search(QUERIES, 8, engine="bitbound-folding")
    svc.close()

    meta = load_latest_intact(str(tmp_path / "snapshots"))[2]
    assert resolve_metric(meta["config"]["metric"]).name == "dice"
    assert int(meta["config"]["fp_bits"]) == 1024

    svc2 = SearchService.open(tmp_path)        # inherits dice from the meta
    assert resolve_metric(svc2.config.metric).name == "dice"
    got = svc2.search(QUERIES, 8, engine="bitbound-folding")
    np.testing.assert_array_equal(np.asarray(live[0]), np.asarray(got[0]))
    np.testing.assert_array_equal(np.asarray(live[1]), np.asarray(got[1]))
    svc2.close()

    with pytest.raises(ValueError, match="metric"):
        SearchService.open(tmp_path, metric="cosine")
    # explicitly restating the persisted metric is fine
    svc3 = SearchService.open(tmp_path, metric="dice")
    svc3.close()


def test_hnsw_extraction_never_aliases_live_arrays():
    """COW contract behind background snapshots: extracted arrays must be
    private copies, never views of the live (still-mutating) state."""
    eng = HNSWEngine(POOL[:80])
    arrays, _ = snap.hnsw_index_state(eng.index)
    for name, a in arrays.items():
        assert not np.shares_memory(a, eng.index.db), name
        assert not np.shares_memory(a, eng.index.base_adj), name
