"""Concurrent serving tier (ISSUE 9): deterministic-core parity, admission
control + shedding, deadlines, graceful degradation, replica fan-out and
failover, and warm restart of a front-end-owned durable directory."""
import time

import numpy as np
import pytest

from repro.data.molecules import (SyntheticConfig, queries_from_db,
                                  synthetic_fingerprints)
from repro.obs.schema import (FRONTEND_METRIC_FAMILIES,
                              REQUIRED_METRIC_FAMILIES,
                              validate_metrics_jsonl)
from repro.serve import (DeadlineExceeded, FrontendConfig, Overloaded,
                         SearchFrontend, SearchService, ServiceConfig,
                         Unavailable)

ENGINES = ("brute", "bitbound-folding", "hnsw")
SVC_KW = dict(compact_threshold=64, hnsw_m=4, hnsw_ef_construction=12,
              hnsw_ef_search=16, cutoff=0.4, fold_m=2)

#: no shedding, no deadline pressure — the parity configuration
CALM = dict(high_water=10_000, default_deadline_ms=None,
            flush_interval_ms=0.5)


@pytest.fixture(scope="module")
def data():
    db = synthetic_fingerprints(SyntheticConfig(n=400, seed=0))
    extra = synthetic_fingerprints(SyntheticConfig(n=90, seed=5))
    q = queries_from_db(db, 10, seed=2)
    return db, extra, q


def _wait(cond, timeout=20.0, msg="condition"):
    t0 = time.time()
    while not cond():
        if time.time() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {msg}")
        time.sleep(0.02)


# -- deterministic-core parity (the ISSUE 9 correctness anchor) --------------

def test_single_replica_parity_all_engines(data):
    """Front end with 1 replica, shedding disabled, no deadlines: ids AND
    sims bit-identical to the direct synchronous SearchService path, across
    all three engines, interleaved with inserts."""
    db, extra, q = data
    ref = SearchService(db, engines=ENGINES, **SVC_KW)
    fe = SearchFrontend(db, engines=ENGINES,
                        frontend=FrontendConfig(replicas=1, **CALM),
                        **SVC_KW)
    try:
        for e in ENGINES:
            got = fe.search(q, 6, engine=e)
            want = ref.search(q, 6, engine=e)
            np.testing.assert_array_equal(got[0], want[0], err_msg=e)
            np.testing.assert_array_equal(got[1], want[1], err_msg=e)
        # interleave inserts and re-check (delta path + HNSW graph inserts)
        for lo in range(0, len(extra), 30):
            batch = extra[lo:lo + 30]
            np.testing.assert_array_equal(fe.insert(batch),
                                          ref.insert(batch))
            for e in ENGINES:
                got = fe.search(q, 6, engine=e)
                want = ref.search(q, 6, engine=e)
                np.testing.assert_array_equal(got[0], want[0], err_msg=e)
                np.testing.assert_array_equal(got[1], want[1], err_msg=e)
        assert fe.shed_count == 0 and fe.expired_count == 0
        assert fe.degradation_level == 0
    finally:
        fe.close()


def test_replicas_serve_identical_results(data):
    """Every replica answers any query identically — load balancing is
    invisible to results."""
    db, extra, q = data
    fe = SearchFrontend(db, engines=("bitbound-folding",),
                        frontend=FrontendConfig(replicas=3, **CALM),
                        **SVC_KW)
    try:
        fe.insert(extra[:40])
        ref = fe.search(q, 6)
        # many rounds so the balancer exercises different replicas
        for _ in range(12):
            got = fe.search(q, 6)
            np.testing.assert_array_equal(got[0], ref[0])
            np.testing.assert_array_equal(got[1], ref[1])
    finally:
        fe.close()


# -- admission control, deadlines, degradation -------------------------------

def test_overload_sheds_typed_and_bounded(data):
    db, _, q = data
    fe = SearchFrontend(db, engines=("brute",),
                        frontend=FrontendConfig(
                            replicas=1, high_water=4,
                            default_deadline_ms=None,
                            flush_interval_ms=1.0),
                        **SVC_KW)
    try:
        futs, shed = [], 0
        for i in range(200):
            try:
                futs.append(fe.submit(q[i % len(q)], 4))
            except Overloaded:
                shed += 1
        assert shed > 0, "200 instant submits never hit high_water=4"
        # bounded admission: never more in flight than the high-water mark
        assert len(futs) <= 4 or fe.summary()["shed"] == shed
        for f in futs:
            f.result(timeout=30)
        fe.drain(30)
        s = fe.summary()
        assert s["shed"] == shed
        assert s["n_completed"] == len(futs)
    finally:
        fe.close()


def test_overload_window_sheds_and_completes_nothing_late(data):
    """Instant submits far past the high-water mark: the front end sheds,
    and every admitted request either completes inside its deadline or is
    dropped with ``DeadlineExceeded`` — none completes late."""
    db, _, q = data
    deadline_ms = 5000.0
    fe = SearchFrontend(db, engines=("brute",),
                        frontend=FrontendConfig(
                            replicas=1, high_water=16,
                            default_deadline_ms=deadline_ms,
                            flush_interval_ms=1.0),
                        **SVC_KW)
    try:
        for n in (1, 2, 4, 8, 16):             # warm every batch bucket
            fe.search(np.resize(q, (n, q.shape[1])), 4, deadline_ms=None)
        futs, shed = [], 0
        for i in range(200):
            try:
                futs.append(fe.submit(q[i % len(q)], 4))
            except Overloaded:
                shed += 1
        completed = expired = 0
        for f in futs:
            try:
                f.result(timeout=60)
                completed += 1
            except DeadlineExceeded:
                expired += 1
        fe.drain(30)
        assert shed > 0 and completed > 0
        assert completed + expired == len(futs)
        assert fe.summary()["shed"] == shed
        lat = fe.metrics.family("frontend_request_latency_ms")
        assert lat.quantile(1.0) <= deadline_ms
    finally:
        fe.close()


def test_expired_requests_dropped_before_scoring(data):
    db, _, q = data
    fe = SearchFrontend(db, engines=("brute",),
                        frontend=FrontendConfig(
                            replicas=1, high_water=1000,
                            flush_interval_ms=30.0),
                        **SVC_KW)
    try:
        fe.search(q[:1], 4, deadline_ms=None)     # warm the compile cache
        queries_before = fe.replicas[0].svc.n_queries
        f = fe.submit(q[0], 4, deadline_ms=0.001)  # expires immediately
        with pytest.raises(DeadlineExceeded):
            f.result(timeout=30)
        fe.drain(30)
        assert fe.expired_count == 1
        # dropped pre-dispatch: the engine never scored it
        assert fe.replicas[0].svc.n_queries == queries_before
        fam = fe.metrics.family("frontend_deadline_expired_total")
        assert fam.total() == 1
    finally:
        fe.close()


def test_degradation_ladder_engages_and_recovers(data):
    db, _, q = data
    fe = SearchFrontend(db, engines=("hnsw",),
                        frontend=FrontendConfig(
                            replicas=1, high_water=4,
                            default_deadline_ms=None,
                            flush_interval_ms=1.0,
                            degrade_ticks=2, degrade_high=0.5,
                            degrade_low=0.1),
                        **SVC_KW)
    try:
        eng = fe.replicas[0].svc.engines["hnsw"]
        ef0, beam0 = int(eng.ef_search), int(eng.beam)
        futs = []
        t0 = time.time()
        while fe.max_level_engaged == 0 and time.time() - t0 < 20:
            try:
                futs.append(fe.submit(q[0], 4))
            except Overloaded:
                pass
        assert fe.max_level_engaged >= 1, "sustained shedding never stepped"
        assert fe.metrics.family(
            "frontend_degradation_shifts_total").value(direction="down") >= 1
        for f in futs:
            f.result(timeout=60)
        fe.drain(60)
        # recovery: idle load steps the ladder back to full quality
        _wait(lambda: fe.degradation_level == 0, timeout=20,
              msg="ladder step-up")
        # level 0 restores the exact configured knobs (scales, not deltas)
        fe.search(q[:1], 4)
        fe.drain(30)
        assert (int(eng.ef_search), int(eng.beam)) == (ef0, beam0)
        assert fe.metrics.family(
            "frontend_degradation_shifts_total").value(direction="up") >= 1
    finally:
        fe.close()


def test_ladder_level_zero_must_be_identity():
    from repro.serve.frontend import DegradeLevel
    with pytest.raises(ValueError, match="identity"):
        FrontendConfig(ladder=(DegradeLevel("broken", k_scale=0.5),))


# -- replicas + failover ------------------------------------------------------

@pytest.mark.parametrize("durable", [False, True])
def test_replica_failover_rehydrates_byte_identical(data, tmp_path, durable):
    """Kill one of two replicas mid-stream: no acked insert lost, queries
    keep serving, and the re-hydrated replica is byte-identical to the
    survivor (post-compaction normalization)."""
    db, extra, q = data
    cfg = dict(SVC_KW)
    if durable:
        cfg["durable_dir"] = str(tmp_path / "fe")
    fe = SearchFrontend(db, engines=("bitbound-folding", "hnsw"),
                        frontend=FrontendConfig(replicas=2, **CALM), **cfg)
    try:
        gids = fe.insert(extra[:30])
        assert list(gids) == list(range(len(db), len(db) + 30))
        ref = fe.search(q, 6)
        fe.kill_replica(0)
        # still serving from the survivor while slot 0 rehydrates
        got = fe.search(q, 6)
        np.testing.assert_array_equal(got[0], ref[0])
        _wait(lambda: fe.live_replicas() == 2, msg="rehydration")
        assert fe.replicas[0].generation == 1
        # acked inserts fan to the rebuilt replica too
        fe.insert(extra[30:60])
        a0, m0 = fe.replica_state(0)
        a1, m1 = fe.replica_state(1)
        assert m0 == m1
        assert sorted(a0) == sorted(a1)
        for k in a0:
            assert a0[k].tobytes() == a1[k].tobytes(), \
                f"{k}: rehydrated replica diverged from survivor"
        assert fe.summary()["failovers"] == 1
        assert fe.metrics.family("frontend_replica_live").value(
            replica=0) == 1
    finally:
        fe.close()


def test_wedged_replica_detected_and_failed_over(data):
    """A worker stuck inside a task past health_timeout_s is marked dead by
    the monitor and its queued work re-dispatched to the survivor."""
    db, _, q = data
    fe = SearchFrontend(db, engines=("brute",),
                        frontend=FrontendConfig(
                            replicas=2, health_timeout_s=1.0,
                            rehydrate=False, **CALM),
                        **SVC_KW)
    gate = {"blocked": True}   # bound before try: the finally reads it
    try:
        # warm the compile caches so a first-call compile on the healthy
        # replica cannot trip the wedge detector
        for _ in range(4):
            fe.search(q, 6, timeout=60)

        def wedge(svc):
            while gate["blocked"]:
                time.sleep(0.01)

        fe.replicas[0].call(wedge, label="wedge")
        _wait(lambda: fe.live_replicas() == 1, msg="wedge detection")
        got = fe.search(q, 6, timeout=30)     # survivor still serves
        assert got[0].shape == (len(q), 6)
        assert fe.summary()["failovers"] == 1
        gate["blocked"] = False
    finally:
        gate["blocked"] = False
        fe.close()


def test_insert_unavailable_when_all_dead(data):
    db, extra, _ = data
    fe = SearchFrontend(db, engines=("brute",),
                        frontend=FrontendConfig(replicas=1, rehydrate=False,
                                                **CALM), **SVC_KW)
    try:
        fe.kill_replica(0)
        with pytest.raises(Unavailable):
            fe.insert(extra[:5])
    finally:
        fe.close()


# -- durable warm restart -----------------------------------------------------

def test_frontend_open_round_trip(data, tmp_path):
    """Front-end durable dir round-trips through SearchFrontend.open AND
    plain SearchService.open (one on-disk format)."""
    db, extra, q = data
    d = tmp_path / "fe"
    fe = SearchFrontend(db, engines=("bitbound-folding",),
                        frontend=FrontendConfig(replicas=2, **CALM),
                        durable_dir=str(d), **SVC_KW)
    fe.insert(extra[:40])
    fe.snapshot()
    fe.insert(extra[40:70])                   # WAL tail past the snapshot
    ref = fe.search(q, 6)
    fe.close()

    fe2 = SearchFrontend.open(d, frontend=FrontendConfig(replicas=2, **CALM))
    try:
        assert fe2.n_total == len(db) + 70
        got = fe2.search(q, 6)
        np.testing.assert_array_equal(got[0], ref[0])
        np.testing.assert_array_equal(got[1], ref[1])
        a0, _ = fe2.replica_state(0)
        a1, _ = fe2.replica_state(1)
        for k in a0:
            assert a0[k].tobytes() == a1[k].tobytes(), k
    finally:
        fe2.close()

    svc = SearchService.open(d)
    try:
        assert svc.n_total == len(db) + 70
        got = svc.search(q, 6)
        np.testing.assert_array_equal(got[0], ref[0])
    finally:
        svc.close()


def test_frontend_refuses_existing_dir_without_open(data, tmp_path):
    db, _, _ = data
    d = tmp_path / "fe"
    fe = SearchFrontend(db, engines=("brute",), durable_dir=str(d), **SVC_KW)
    fe.close()
    with pytest.raises(ValueError, match="open"):
        SearchFrontend(db, engines=("brute",), durable_dir=str(d), **SVC_KW)


# -- lifecycle ----------------------------------------------------------------

def test_frontend_close_idempotent_and_rejects_after(data):
    db, _, q = data
    fe = SearchFrontend(db, engines=("brute",), **SVC_KW)
    fe.search(q[:1], 4)
    fe.close()
    fe.close()                                # second close is a no-op
    with pytest.raises(RuntimeError, match="closed"):
        fe.submit(q[0], 4)
    with pytest.raises(RuntimeError, match="closed"):
        fe.insert(db[:1])


def test_export_metrics_merges_frontend_and_replicas(data, tmp_path):
    import json
    db, _, q = data
    fe = SearchFrontend(db, engines=("brute",),
                        frontend=FrontendConfig(replicas=2, **CALM),
                        **SVC_KW)
    try:
        fe.search(q, 4)
        p = tmp_path / "m.jsonl"
        n = fe.export_metrics(p, ts=1.0)
        rows = [json.loads(line) for line in open(p)]
        assert len(rows) == n
        names = {r["name"] for r in rows}
        assert {"frontend_queue_depth", "frontend_inflight",
                "frontend_request_latency_ms",
                "service_queries_total"} <= names
        # replica rows carry the replica label; frontend rows don't
        svc_rows = [r for r in rows if r["name"].startswith("service_")]
        assert svc_rows and all("replica" in r["labels"] for r in svc_rows)
        assert (tmp_path / "m.jsonl.prom").exists()
        # the serving floor and every front-end family, schema-valid
        assert validate_metrics_jsonl(
            p, require_families=REQUIRED_METRIC_FAMILIES
            + FRONTEND_METRIC_FAMILIES) == []
    finally:
        fe.close()

