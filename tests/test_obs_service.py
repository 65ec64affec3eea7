"""Service-level observability (ISSUE 8): the SearchService registry is
populated consistently with the legacy telemetry (summary() keys stable),
write-only runs report explicit null percentiles, the recent-window views
are bounded under sustained load, tiered residency populates the per-stage
stream gauges/spans (and device residency does not), and a profiler trace
shows the double-buffer overlap — a chunk's host->HBM transfer span
concurrent with the previous chunk's compute span — the served path's span
tree from the front end to the engine with one request id, and the
engines' device->host reads all inside ``engine.fetch``."""
import time

import jax
import numpy as np
import pytest
from jax._src import array as jax_array

from _spans import record
from repro.core import BitBoundFoldingEngine, BruteForceEngine
from repro.core.hnsw import build_schedule
from repro.data.molecules import (SyntheticConfig, queries_from_db,
                                  synthetic_fingerprints)
from repro.obs import trace as obs_trace
from repro.obs.schema import (BITBOUND_METRIC_FAMILIES,
                               REQUIRED_METRIC_FAMILIES,
                               validate_metrics_jsonl)
from repro.obs.trace import TRACER
from repro.serve import SearchService
from repro.serve.frontend import FrontendConfig, SearchFrontend

K = 8


@pytest.fixture(scope="module")
def data():
    db = synthetic_fingerprints(SyntheticConfig(n=2000, seed=0))
    extra = synthetic_fingerprints(SyntheticConfig(n=64, seed=5))
    q = queries_from_db(db, 16, seed=2)
    return db, extra, q


def test_registry_matches_legacy_telemetry(data):
    db, extra, q = data
    svc = SearchService(db, engines=("brute",), backend="jnp", k=K)
    svc.insert(extra[:8])
    for i in range(6):
        svc.submit(q[i], engine="brute")
    svc.flush()
    svc.submit(q[6:10], engine="brute")
    svc.flush()
    m = svc.metrics
    assert m.family("service_queries_total").value(engine="brute") \
        == svc.n_queries == 10
    assert m.family("service_inserts_total").value() == svc.n_inserts == 8
    # scanned attribution: registry counter == Counter view == engine
    # contract (scanned-per-batch summed over the flush buckets)
    assert m.family("service_scanned_total").value(engine="brute") \
        == svc.scanned_total["brute"]
    assert m.family("service_request_latency_ms").count() \
        == len(svc.latencies_ms) == 7      # 7 requests, 10 query rows
    # batch buckets: one 8-bucket flush + one 4-bucket flush
    assert m.family("service_batches_total").value(engine="brute",
                                                   bucket="8") == 1
    assert m.family("service_batches_total").value(engine="brute",
                                                   bucket="4") == 1
    s = svc.summary()
    assert s["batch_buckets"] == {8: 1, 4: 1}
    assert s["p50_ms"] > 0 and s["p99_ms"] >= s["p50_ms"]
    assert s["mean_ms"] > 0
    # reset_telemetry zeroes values but keeps the family declarations
    svc.reset_telemetry()
    assert m.family("service_queries_total").total() == 0
    assert svc.summary()["p50_ms"] is None


def test_write_only_run_reports_null_percentiles(data):
    db, extra, _ = data
    svc = SearchService(db, engines=("brute",), backend="jnp")
    svc.insert(extra[:4])
    s = svc.summary()
    assert s["n_queries"] == 0 and s["n_inserts"] == 4
    # keys present with explicit nulls — not missing, not 0.0
    assert s["p50_ms"] is None and s["p99_ms"] is None \
        and s["mean_ms"] is None
    assert s["qps"] == 0.0


def test_metrics_disabled_service_falls_back(data):
    db, _, q = data
    svc = SearchService(db, engines=("brute",), backend="jnp", k=K,
                        metrics=False)
    assert svc.metrics.enabled is False
    for i in range(4):
        svc.submit(q[i], engine="brute")
    svc.flush()
    s = svc.summary()                     # percentiles from the deque window
    assert s["n_queries"] == 4 and s["p50_ms"] > 0
    assert svc.metrics.collect() == []


def test_telemetry_windows_bounded(data, monkeypatch):
    db, _, q = data
    monkeypatch.setattr(SearchService, "TELEMETRY_WINDOW", 8)
    svc = SearchService(db, engines=("brute",), backend="jnp", k=K)
    for i in range(24):                   # 3x the window, one batch each
        svc.submit(q[i % len(q)], engine="brute")
        svc.flush()
    # recent-window views are bounded; full-run aggregates are not
    assert len(svc.latencies_ms) == 8
    assert len(svc.batches) == 8
    assert svc.n_queries == 24
    assert svc.metrics.family("service_request_latency_ms").count() == 24
    s = svc.summary()
    assert s["batch_buckets"] == {1: 24}  # full-run histogram, not windowed
    assert s["n_queries"] == 24


def _tiered_service(db, **kw):
    # 2000 rows -> 2048-capacity main segment; 256-row chunks -> 8 streamed
    # chunks through the double buffer on every brute tiered search
    return SearchService(db, engines=("brute",), backend="jnp", k=K,
                         residency="tiered", tier_chunk_rows=256, **kw)


def test_tiered_stage_gauges_populated(data):
    db, _, q = data
    svc = _tiered_service(db)
    svc.submit(q[:4], engine="brute")
    svc.flush()
    m = svc.metrics
    assert m.family("service_tiered_chunks").value(engine="brute") == 8
    assert m.family("service_tiered_stall_seconds").value(engine="brute") >= 0
    frac = m.family("service_tiered_stall_fraction").value(engine="brute")
    assert 0.0 <= frac <= 1.0
    # scanned attribution still matches the engine contract under tiering
    assert m.family("service_scanned_total").value(engine="brute") \
        == svc.scanned_total["brute"] > 0


def test_device_residency_leaves_tier_gauges_empty(data):
    db, _, q = data
    svc = SearchService(db, engines=("brute",), backend="jnp", k=K)
    svc.submit(q[:4], engine="brute")
    svc.flush()
    m = svc.metrics
    # no tiered child was ever materialized on the device-resident path
    assert m.family("service_tiered_chunks").value(engine="brute") == 0
    assert all(r["name"] != "service_tiered_chunks" for r in m.collect())


def test_stage1_tile_counter(data, tmp_path):
    """``service_stage1_tiles_total``: the row-window kernel's streamed
    query-block union tiles never exceed the per-query window tiles they
    serve, and equal them for a one-query batch; the brute engine, which
    has no such kernel, adds no child; the export carries the family."""
    db, _, q = data
    svc = SearchService(db, engines=("bitbound-folding", "brute"),
                        backend="tpu", k=K, cutoff=0.6, fold_m=2)
    fam = svc.metrics.family("service_stage1_tiles_total")

    def tiles():
        return (fam.value(engine="bitbound-folding", kind="window"),
                fam.value(engine="bitbound-folding", kind="streamed"))

    svc.submit(q[:1], engine="bitbound-folding")
    svc.flush()
    window1, streamed1 = tiles()
    assert window1 == streamed1 > 0
    svc.submit(q[:16], engine="bitbound-folding")
    svc.submit(q[:4], engine="brute")
    svc.flush()
    window, streamed = tiles()
    assert 0 < streamed - streamed1 <= window - window1
    assert {r["labels"]["engine"] for r in svc.metrics.collect()
            if r["name"] == "service_stage1_tiles_total"} \
        == {"bitbound-folding"}
    out = tmp_path / "metrics.jsonl"
    svc.metrics.export_jsonl(out)
    assert validate_metrics_jsonl(
        out, require_families=REQUIRED_METRIC_FAMILIES
        + BITBOUND_METRIC_FAMILIES) == []


def test_tiered_trace_shows_double_buffer_overlap(data, tmp_path):
    db, _, q = data
    svc = _tiered_service(db)
    with record(tmp_path) as rec:
        svc.submit(q[:4], engine="brute")
        svc.flush()
    puts = rec.named("tier.device_put")
    scans = rec.named("tier.scan_chunk")
    assert len(puts) == 8 and len(scans) == 8
    assert len(rec.named("tier.stall")) == 8
    # acceptance: chunk i+1's host->HBM transfer span overlaps chunk i's
    # compute span on the timeline (the double buffer actually pipelines)
    scan_by_chunk = {e.args["chunk"]: e for e in scans}
    put_by_chunk = {e.args["chunk"]: e for e in puts}
    overlapped = [c for c in range(7)
                  if put_by_chunk[c + 1].overlaps(scan_by_chunk[c])]
    assert overlapped, "no transfer span overlapped the previous compute span"
    # the service-level request path is present and nested
    assert {"service.batch", "service.engine_search", "service.pad",
            "engine.plan", "engine.dispatch", "engine.fetch"} <= rec.names()
    assert "service.queue_wait" not in rec.names()
    batch, = rec.named("service.batch")
    search, = rec.named("service.engine_search")
    assert batch.contains(search)
    assert all(search.contains(e) for e in scans)


def test_disabled_tracer_records_nothing_through_service(data, monkeypatch):
    db, extra, q = data
    assert TRACER.enabled is False

    def boom(*a, **kw):
        raise AssertionError("a span was built without a profiler session")
    # without a session not one span object is built on the whole path
    monkeypatch.setattr(obs_trace, "_Span", boom)
    svc = _tiered_service(db)
    svc.insert(extra[:4])
    svc.submit(q[:4], engine="brute")
    done = svc.flush()
    assert len(done) == 1


def test_wal_and_snapshot_spans(data, tmp_path):
    db, extra, q = data
    with record(tmp_path / "trace") as rec:
        svc = SearchService(db, engines=("brute",), backend="jnp", k=K,
                            durable_dir=str(tmp_path / "durable"))
        svc.insert(extra[:4])
        svc.submit(q[:2], engine="brute")
        svc.flush()
        svc.snapshot()
        svc.close()
    assert {"wal.append", "wal.fsync", "service.insert",
            "snapshot.extract", "snapshot.write"} <= rec.names()
    # the WAL append (and its fsync) is recorded inside the insert span
    insert, = rec.named("service.insert")
    appends = rec.named("wal.append")
    assert appends and all(insert.contains(e) for e in appends)
    assert rec.inside(insert, "wal.fsync")
    assert rec.named("snapshot.extract")[0].args["engines"] == "brute"


def test_phase_histograms_count_every_phase(data, tmp_path):
    """The per-phase latency families a latency breakdown reads count one
    observation per request (queue wait), flush group (engine batch),
    insert and WAL append, and each reads back a median."""
    db, extra, q = data
    svc = SearchService(db, engines=("brute",), backend="jnp", k=K,
                        durable_dir=str(tmp_path / "durable"))
    try:
        for i in range(3):
            svc.insert(extra[i:i + 1])
        for i in range(5):
            svc.submit(q[i], engine="brute")
        svc.flush()
        svc.submit(q[5:7], engine="brute")
        svc.flush()
        m = svc.metrics
        counts = {"service_queue_wait_ms": 6, "service_engine_batch_ms": 2,
                  "service_insert_ms": 3, "service_wal_append_ms": 3}
        for name, want in counts.items():
            fam = m.family(name)
            assert fam.count() == want, name
            assert fam.quantile(0.5) is not None and fam.quantile(0.5) >= 0
    finally:
        svc.close()


ENGINES = ("brute", "bitbound-folding")


@pytest.mark.parametrize("engine", ENGINES)
def test_served_path_span_tree(data, tmp_path, engine):
    """One ``SearchFrontend.search`` under a profiler session: admission,
    the dispatcher's tick around its dispatch, the replica's task around
    the service's batch, around the engine's plan, launch and fetch; the
    request's id on every span that carries ids."""
    db, _, q = data
    fe = SearchFrontend(db, engines=(engine,), backend="jnp", k=K,
                        frontend=FrontendConfig(default_deadline_ms=None))
    try:
        fe.search(q[:2], K)                       # compile outside the trace
        with record(tmp_path) as rec:
            ids, sims = fe.search(q[:2], K)
    finally:
        fe.close()
    assert ids.shape == (2, K)
    admit, = rec.named("frontend.admit")
    rid = admit.args["rid"]
    dispatch, = rec.named("frontend.dispatch")
    tick, = [t for t in rec.named("frontend.tick") if t.contains(dispatch)]
    assert rec.inside(tick, "frontend.maintenance")
    task, = [t for t in rec.named("replica.task")
             if t.args.get("label") == "batch"]
    for span in (dispatch, task):
        assert str(span.args["rid"]).split() == [str(rid)]
    assert dispatch.args["batch"] == task.args["batch"]
    assert not tick.line == task.line              # dispatcher vs worker
    batch, = rec.inside(task, "service.batch")
    search, = rec.inside(batch, "service.engine_search")
    for name in ("engine.plan", "engine.dispatch", "engine.fetch"):
        assert len(rec.inside(search, name)) == 1, name
    plan, = rec.inside(search, "engine.plan")
    launch, = rec.inside(search, "engine.dispatch")
    fetch, = rec.inside(search, "engine.fetch")
    assert plan.end <= launch.start and launch.end <= fetch.start
    assert rec.inside(batch, "service.pad")
    assert not {"service.queue_wait", "frontend.shed"} & rec.names()


def _probe_reads(monkeypatch):
    """Every device->host read becomes a ``probe.d2h`` span on the reading
    thread: ``ArrayImpl._value`` (``int()``, ``jax.device_get``) and
    ``np.asarray`` / ``np.array`` of a jax array (on the CPU numpy takes
    those through the buffer protocol, past ``_value``)."""
    def mark():
        with jax.profiler.TraceAnnotation("probe.d2h"):
            time.sleep(1e-5)

    value = jax_array.ArrayImpl._value

    def read_value(self):
        mark()
        return value.fget(self)
    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(read_value))
    for fn in ("asarray", "array"):
        orig = getattr(np, fn)

        def wrapped(a, *args, _orig=orig, **kw):
            if isinstance(a, jax.Array):
                mark()
            return _orig(a, *args, **kw)
        monkeypatch.setattr(np, fn, wrapped)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("with_delta", [False, True])
def test_engine_fetch_holds_the_batchs_only_device_reads(
        data, tmp_path, monkeypatch, engine, with_delta):
    """A served batch reads the device once, inside ``engine.fetch``: the
    launch never syncs, and the scanned count behind
    ``engine.stage1_scan_share`` comes back in the same fetch with its
    value unchanged."""
    db, extra, q = data
    if engine == "brute":
        eng = BruteForceEngine(db, backend="jnp")
    else:
        eng = BitBoundFoldingEngine(db, cutoff=0.6, m=2, backend="jnp")
        ref = BitBoundFoldingEngine(db, cutoff=0.6, m=2)
    if with_delta:
        eng.insert(extra[:8])
        if engine != "brute":
            ref.insert(extra[:8])
    qs = np.ascontiguousarray(q[:8])
    want = eng.search(qs, K)                      # compile outside the trace
    _probe_reads(monkeypatch)
    with record(tmp_path) as rec:
        got = eng.search(qs, K)
    monkeypatch.undo()
    np.testing.assert_array_equal(got[0], want[0])
    probes = rec.named("probe.d2h")
    fetch, = rec.named("engine.fetch")
    assert probes, "the probe saw no read at all"
    assert all(fetch.contains(p) for p in probes), \
        [p.start for p in probes if not fetch.contains(p)]
    if engine != "brute":
        ref.search(qs, K)
        assert eng.scanned(len(qs)) == ref.scanned(len(qs)) > 0


def test_hnsw_service_publishes_build_and_traversal_families(data, tmp_path):
    """An HNSW service exports the build gauges (kept through a telemetry
    reset: they describe the graph) and the traversal counters of each
    batch; the build's spans nest under ``hnsw.build``."""
    from repro.obs.schema import HNSW_METRIC_FAMILIES
    db, _, q = data
    with record(tmp_path / "trace") as rec:
        svc = SearchService(db[:500], engines=("hnsw",), backend="jnp", k=K,
                            hnsw_m=6, hnsw_ef_construction=24,
                            hnsw_ef_search=24, hnsw_build_round=16)
    builds = rec.named("hnsw.build")
    assert len(builds) == 1
    rounds = rec.inside(builds[0], "hnsw.build_round")
    assert builds[0].args["rows"] == 500
    assert len(rounds) == builds[0].args["rounds"] \
        == len(build_schedule(500, 16))
    for name in ("hnsw.build_search", "hnsw.build_select", "hnsw.build_link"):
        inner = rec.named(name)
        assert inner and all(any(r.contains(s) for r in rounds)
                             for s in inner), name
    m = svc.metrics
    svc.reset_telemetry()
    assert m.family("service_hnsw_build_rows").value(engine="hnsw") == 500
    assert m.family("service_hnsw_build_rounds").value(engine="hnsw") \
        == len(rounds)
    assert m.family("service_hnsw_build_seconds").value(engine="hnsw") > 0
    svc.submit(q[:8], engine="hnsw")
    svc.flush()
    eng = svc.engines["hnsw"]
    assert m.family("service_hnsw_neighbour_evals_total").value(
        engine="hnsw") == eng.stats["neighbour_evals"] > 0
    assert m.family("service_hnsw_iters_total").value(engine="hnsw") \
        == eng.stats["iters"] > 0
    assert m.family("service_hnsw_max_iters_hit_total").value(
        engine="hnsw") == eng.stats["max_iters_hit"]
    out = tmp_path / "metrics.jsonl"
    m.export_jsonl(out)
    assert validate_metrics_jsonl(
        out, require_families=REQUIRED_METRIC_FAMILIES
        + HNSW_METRIC_FAMILIES) == []
