"""Crash-fault-injection harness (ISSUE 6 acceptance).

Fault models:

* **Injected crashes** — the service runs on a :class:`CrashPointFs` that
  tears the write exhausting a byte budget (torn WAL records, truncated
  snapshot leaves) or raises between metadata ops (fsync / rename / mkdir /
  remove) on an op budget. Budgets are swept three ways: a mixed
  ingest+snapshot workload, an ingest-only workload (every crash lands in a
  WAL append / compaction rotation), and a snapshot-only workload (every
  crash lands in the snapshot write/publish/GC sequence). After every crash
  the directory is reopened with the real filesystem and the recovered
  service must (a) contain every acknowledged insert and (b) have state
  byte-equal to a never-crashed service driven with the same prefix of the
  workload — across all three engines at once.
* **SIGKILL** — a subprocess ingests with fsync-per-ack and prints each
  acked batch; the parent SIGKILLs it mid-ingest and reopens the directory,
  asserting acked-implies-recovered and search parity against a
  never-crashed rebuild.
"""
import os
import signal
import subprocess
import sys
import tempfile
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.checkpoint.fs import CrashPointFs, InjectedCrash
from repro.data.molecules import (SyntheticConfig, queries_from_db,
                                  synthetic_fingerprints)
from repro.serve import SearchService, snapshot as snap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENGINES = ("brute", "bitbound-folding", "hnsw")
SVC_KW = dict(compact_threshold=12, hnsw_m=4, hnsw_ef_construction=12,
              hnsw_ef_search=16)

POOL = synthetic_fingerprints(SyntheticConfig(n=260, seed=0))
BASE = POOL[:150]
BATCH = 5
BATCHES = [POOL[150 + i * BATCH:150 + (i + 1) * BATCH] for i in range(10)]
QUERIES = queries_from_db(POOL, 5, seed=4)

_ref_cache: dict = {}


def _reference_state(n_batches: int):
    """State of a never-crashed service after the same workload prefix
    (snapshots never mutate engine state, so one cache serves every test)."""
    if n_batches not in _ref_cache:
        svc = SearchService(BASE, engines=ENGINES, **SVC_KW)
        for b in BATCHES[:n_batches]:
            svc.insert(b)
        _ref_cache[n_batches] = snap.service_state(svc)
    return _ref_cache[n_batches]


def _crash_run(tmp: Path, fs: CrashPointFs, workload):
    """One swept run: real-fs service creation, faulty-fs workload, real-fs
    recovery. ``workload(svc, stage)`` drives the service and keeps
    ``stage[0]`` naming the op in flight. Returns
    ``(acked_batches, crashed_stage_or_None, recovered_service)``."""
    svc = SearchService(BASE, engines=ENGINES, durable_dir=str(tmp),
                        **SVC_KW)
    stage = ["setup"]
    crashed = None
    try:
        svc._set_fs(fs)                # even the swap rotation may crash
        workload(svc, stage)
        stage[0] = "done"
    except InjectedCrash:
        crashed = stage[0]
        try:                           # drop the torn WAL buffer quietly
            svc._wal._f.close()
        except Exception:
            pass
    acked = svc.n_inserts // BATCH     # insert() returned for exactly these
    recovered = SearchService.open(tmp)
    return acked, crashed, recovered


def _workload_mixed(svc, stage):
    for i, batch in enumerate(BATCHES):
        stage[0] = "insert"            # WAL append + apply (+ rotation when
        svc.insert(batch)              # the batch trips a compaction)
        if (i + 1) % 3 == 0:
            stage[0] = "snapshot"
            svc.snapshot()


def _workload_ingest(svc, stage):
    for batch in BATCHES:
        stage[0] = "insert"
        svc.insert(batch)


def _workload_snapshot(svc, stage):
    for batch in BATCHES[:4]:          # a WAL tail for the snapshot to cover
        stage[0] = "insert"
        svc.insert(batch)
    stage[0] = "snapshot"
    svc.snapshot()
    stage[0] = "snapshot"              # second generation: retention prune +
    svc.snapshot()                     # WAL GC crash windows


def _assert_recovered(acked: int, recovered: SearchService, label: str):
    n_rec = recovered.engines["brute"].n_total - len(BASE)
    assert n_rec % BATCH == 0, f"{label}: partial batch recovered"
    n_batches = n_rec // BATCH
    # acked-implies-recovered (an fsync'd-but-unapplied batch may add one)
    assert n_batches >= acked, f"{label}: lost acked batches"
    arrays, meta = snap.service_state(recovered)
    ref_arrays, ref_meta = _reference_state(n_batches)
    assert meta == ref_meta, f"{label}: meta diverged from never-crashed run"
    assert sorted(arrays) == sorted(ref_arrays), f"{label}: array set"
    for k in arrays:
        assert arrays[k].dtype == ref_arrays[k].dtype, f"{label}/{k}"
        assert arrays[k].tobytes() == ref_arrays[k].tobytes(), \
            f"{label}/{k}: state diverged from never-crashed run"
    return n_batches


def _search_parity(recovered: SearchService, n_batches: int, label: str):
    reb = SearchService(BASE, engines=ENGINES, **SVC_KW)
    if n_batches:           # the bulk build stopped at BASE, as it did
        reb.insert(np.concatenate(BATCHES[:n_batches]))
    for e in ENGINES:
        got = recovered.search(QUERIES, 6, engine=e)
        ref = reb.search(QUERIES, 6, engine=e)
        np.testing.assert_array_equal(got[0], ref[0],
                                      err_msg=f"{label}/{e}")
        np.testing.assert_array_equal(got[1], ref[1],
                                      err_msg=f"{label}/{e}")


def _probe_totals(tmp: Path, workload):
    """Fault-free instrumented run: returns the byte/op totals the budget
    sweeps are placed across (and sanity-checks the fault-free roundtrip)."""
    probe = CrashPointFs()             # unlimited budgets: counts only
    acked, crashed, recovered = _crash_run(tmp, probe, workload)
    assert crashed is None
    _assert_recovered(acked, recovered, "fault-free")
    recovered.close()
    assert probe.bytes_written > 0 and probe.ops > 0
    return probe.bytes_written, probe.ops


def _sweep(tmp_path, workload, budgets, expect_stages):
    """Run ``workload`` once per budget; assert every recovery is lossless
    and bit-identical, and that the sweep crossed ``expect_stages``."""
    stages_hit = set()
    parity_checked = set()
    for kind, budget in budgets:
        fs = (CrashPointFs(byte_budget=budget) if kind == "bytes"
              else CrashPointFs(op_budget=budget))
        with tempfile.TemporaryDirectory(dir=tmp_path) as d:
            acked, crashed, recovered = _crash_run(Path(d), fs, workload)
            label = f"{kind}={budget} crash@{crashed}"
            n_batches = _assert_recovered(acked, recovered, label)
            if crashed is not None:
                stages_hit.add(crashed)
                # full search parity once per distinct crash stage (the
                # extra compiles make per-budget checks too slow; state
                # byte-equality already covers the rest)
                if crashed not in parity_checked:
                    parity_checked.add(crashed)
                    _search_parity(recovered, n_batches, label)
            recovered.close()
    missing = expect_stages - stages_hit
    assert not missing, f"sweep never crashed in {missing} (hit {stages_hit})"
    return stages_hit


def test_fault_injection_sweep_mixed(tmp_path):
    """Byte and op budgets swept across the full ingest/compaction/snapshot
    write sequence of a mixed workload."""
    total_bytes, total_ops = _probe_totals(tmp_path / "probe",
                                           _workload_mixed)
    budgets = ([("bytes", max(1, total_bytes * i // 7)) for i in range(7)]
               + [("ops", max(1, total_ops * i // 4)) for i in range(4)])
    # snapshot leaves dominate the byte stream, so the mixed sweep is
    # guaranteed to land there; the ingest-only sweep below pins the rest
    _sweep(tmp_path, _workload_mixed, budgets, {"snapshot"})


def test_fault_injection_sweep_ingest(tmp_path):
    """Ingest-only workload: every budget exhausts inside a WAL append,
    fsync, or compaction rotation — the acked-implies-recovered hot path."""
    total_bytes, total_ops = _probe_totals(tmp_path / "probe",
                                           _workload_ingest)
    budgets = ([("bytes", max(1, total_bytes * i // 6)) for i in range(6)]
               + [("ops", max(1, total_ops * i // 6)) for i in range(6)])
    stages = _sweep(tmp_path, _workload_ingest, budgets, {"insert"})
    assert stages <= {"setup", "insert"}   # nothing else runs here


def test_fault_injection_sweep_snapshot(tmp_path):
    """Snapshot-targeted workload: budgets land in the leaf writes, the
    manifest, the atomic publish, the retention prune and the WAL GC of a
    snapshot generation (including the second-generation windows)."""
    total_bytes, total_ops = _probe_totals(tmp_path / "probe",
                                           _workload_snapshot)
    budgets = ([("bytes", max(1, total_bytes * (i + 3) // 8))
                for i in range(5)]      # skip the ingest prefix: crash late
               + [("ops", max(1, total_ops * (i + 2) // 6))
                  for i in range(4)])
    _sweep(tmp_path, _workload_snapshot, budgets, {"snapshot"})


def test_crash_between_tempwrite_and_rename(tmp_path):
    """Pin the classic window explicitly: the snapshot temp dir is fully
    written but the atomic rename never happens — recovery must use the
    previous generation + WAL, losing nothing."""
    svc = SearchService(BASE, engines=("brute",), durable_dir=str(tmp_path),
                        compact_threshold=1000)
    svc.insert(BATCHES[0])

    class NoRenameFs(CrashPointFs):
        def replace(self, src, dst):
            raise InjectedCrash("crash before atomic rename")

    svc._set_fs(NoRenameFs())
    with pytest.raises(InjectedCrash):
        svc.snapshot()
    recovered = SearchService.open(tmp_path)
    assert recovered.engines["brute"].n_total == len(BASE) + BATCH
    tmps = list((tmp_path / "snapshots").glob(".tmp_*"))
    assert tmps, "expected an orphaned temp dir from the crashed publish"
    recovered.close()


@pytest.mark.parametrize("fsync_every", [1, 4])
def test_sigkill_mid_ingest_recovers_acked(tmp_path, fsync_every):
    """Subprocess driver: SIGKILL the serving process mid-ingest; every
    batch it acked before dying must be searchable after reopen, and the
    results bit-identical to a never-crashed rebuild (group commit is
    allowed to lose only its documented unsynced window)."""
    d = tmp_path / "svc"
    code = textwrap.dedent(f"""
        import numpy as np
        from repro.data.molecules import SyntheticConfig, synthetic_fingerprints
        from repro.serve import SearchService

        pool = synthetic_fingerprints(SyntheticConfig(n=260, seed=0))
        svc = SearchService(pool[:150], engines=("brute", "bitbound-folding",
                                                 "hnsw"),
                            durable_dir={str(d)!r}, compact_threshold=12,
                            hnsw_m=4, hnsw_ef_construction=12,
                            hnsw_ef_search=16,
                            wal_fsync_every={fsync_every})
        rng = np.random.default_rng(7)
        for i in range(4000):
            svc.insert(rng.integers(0, 2**32, size=(2, pool.shape[1]),
                                    dtype=np.uint32))
            print(f"ACK {{i}}", flush=True)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    acked = -1
    try:
        for line in proc.stdout:
            if line.startswith("ACK "):
                acked = int(line.split()[1])
            if acked >= 8:              # mid-ingest, well before batch 4000
                proc.send_signal(signal.SIGKILL)
                break
    finally:
        proc.kill()
        proc.wait(timeout=60)
    assert acked >= 8, proc.stderr.read()

    recovered = SearchService.open(d)
    n_rec = recovered.engines["brute"].n_total
    n_acked_rows = 150 + 2 * (acked + 1)
    if fsync_every == 1:
        assert n_rec >= n_acked_rows, "lost an acked, fsync'd insert"
    else:                               # documented group-commit window
        assert n_rec >= n_acked_rows - 2 * (fsync_every - 1)
    assert (n_rec - 150) % 2 == 0, "partial batch recovered"

    # bit-identical to a never-crashed rebuild on the recovered database
    rng = np.random.default_rng(7)
    pool = synthetic_fingerprints(SyntheticConfig(n=260, seed=0))
    inserted = [rng.integers(0, 2**32, size=(2, pool.shape[1]),
                             dtype=np.uint32)
                for _ in range((n_rec - 150) // 2)]
    reb = SearchService(pool[:150], engines=ENGINES, compact_threshold=12,
                        hnsw_m=4, hnsw_ef_construction=12, hnsw_ef_search=16)
    if inserted:            # the bulk build stopped at 150 rows, as it did
        reb.insert(np.concatenate(inserted))
    q = queries_from_db(pool, 5, seed=4)
    for e in ENGINES:
        got = recovered.search(q, 6, engine=e)
        ref = reb.search(q, 6, engine=e)
        np.testing.assert_array_equal(got[0], ref[0], err_msg=e)
        np.testing.assert_array_equal(got[1], ref[1], err_msg=e)
    recovered.close()


def test_sigkill_with_concurrent_clients_through_frontend(tmp_path):
    """ISSUE 9: acked-implies-recovered must survive the concurrent tier.
    A subprocess serves query clients through a 2-replica SearchFrontend
    while the main thread ingests (each ACK printed only after the durable
    insert call returned); the parent SIGKILLs it mid-stream and reopens
    the front-end-owned directory with plain SearchService.open — every
    acked batch must be searchable, bit-identical to a never-crashed
    rebuild."""
    d = tmp_path / "fe"
    code = textwrap.dedent(f"""
        import threading
        import numpy as np
        from repro.data.molecules import (SyntheticConfig,
                                          synthetic_fingerprints)
        from repro.serve import FrontendConfig, SearchFrontend

        pool = synthetic_fingerprints(SyntheticConfig(n=260, seed=0))
        fe = SearchFrontend(pool[:150], engines=("bitbound-folding",),
                            durable_dir={str(d)!r}, compact_threshold=64,
                            cutoff=0.4, fold_m=2,
                            frontend=FrontendConfig(
                                replicas=2, high_water=64,
                                default_deadline_ms=None,
                                flush_interval_ms=1.0))

        def client(seed):
            rng = np.random.default_rng(seed)
            while True:
                q = pool[int(rng.integers(0, 150))]
                try:
                    fe.search(q, 5, timeout=60)
                except Exception:
                    return
        for s in range(3):
            threading.Thread(target=client, args=(s,), daemon=True).start()

        rng = np.random.default_rng(7)
        for i in range(4000):
            fe.insert(rng.integers(0, 2**32, size=(2, pool.shape[1]),
                                   dtype=np.uint32))
            print(f"ACK {{i}}", flush=True)
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.Popen([sys.executable, "-c", code], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True)
    acked = -1
    try:
        for line in proc.stdout:
            if line.startswith("ACK "):
                acked = int(line.split()[1])
            if acked >= 8:              # mid-stream, clients in flight
                proc.send_signal(signal.SIGKILL)
                break
    finally:
        proc.kill()
        proc.wait(timeout=60)
    assert acked >= 8, proc.stderr.read()

    recovered = SearchService.open(d)
    n_rec = recovered.n_total
    assert n_rec >= 150 + 2 * (acked + 1), \
        "lost an insert the front end acked before SIGKILL"
    assert (n_rec - 150) % 2 == 0, "partial batch recovered"

    rng = np.random.default_rng(7)
    pool = synthetic_fingerprints(SyntheticConfig(n=260, seed=0))
    inserted = [rng.integers(0, 2**32, size=(2, pool.shape[1]),
                             dtype=np.uint32)
                for _ in range((n_rec - 150) // 2)]
    reb = SearchService(np.concatenate([pool[:150]] + inserted),
                        engines=("bitbound-folding",), compact_threshold=64,
                        cutoff=0.4, fold_m=2)
    q = queries_from_db(pool, 5, seed=4)
    got = recovered.search(q, 6)
    ref = reb.search(q, 6)
    np.testing.assert_array_equal(got[0], ref[0])
    np.testing.assert_array_equal(got[1], ref[1])
    recovered.close()
