"""Observability primitives (ISSUE 8): metrics registry semantics
(counters/gauges/log-bucketed histograms, quantile error bound, thread
safety, Prometheus + JSONL exposition round-tripping through the schema
validator) and trace spans recorded into a ``jax.profiler`` session
(nesting and metadata, non-nested handles, the session as the only
switch, the Perfetto file, and the allocation-free fast path without a
session)."""
import glob
import json
import threading
import time

import pytest

from _spans import record
from repro.obs import trace as obs_trace
from repro.obs.metrics import (GROWTH, MetricsRegistry, NULL_METRICS,
                               NullMetrics)
from repro.obs.schema import validate_metrics_jsonl
from repro.obs.trace import NULL_HANDLE, NULL_SPAN, Tracer, session


# -- registry ---------------------------------------------------------------

def test_counter_gauge_labels():
    m = MetricsRegistry()
    c = m.counter("q_total", "queries", labels=("engine",))
    c.inc(engine="brute")
    c.inc(3, engine="hnsw")
    assert c.value(engine="brute") == 1
    assert c.value(engine="hnsw") == 3
    assert c.value(engine="never-touched") == 0
    assert c.total() == 4
    g = m.gauge("depth")
    g.set(7)
    g.set(2)                      # last write wins
    assert g.value() == 2
    # same name re-registration must return the same family ...
    assert m.counter("q_total", labels=("engine",)) is c
    # ... and a kind/label mismatch is a hard error, not silent aliasing
    with pytest.raises(ValueError, match="re-registered"):
        m.gauge("q_total")
    with pytest.raises(ValueError, match="labels"):
        c.inc(backend="jnp")


def test_histogram_quantile_error_bound():
    m = MetricsRegistry()
    h = m.histogram("lat_ms")
    for v in range(1, 1001):
        h.observe(float(v))
    assert h.count() == 1000
    assert h.mean() == pytest.approx(500.5)     # sum/count is exact
    for q, truth in ((0.5, 500.0), (0.99, 990.0)):
        est = h.quantile(q)
        # log-bucketed with 8 buckets/doubling: ~9% max relative error
        assert abs(est - truth) / truth < GROWTH - 1 + 0.02, (q, est)


def test_histogram_single_value_exact():
    m = MetricsRegistry()
    h = m.histogram("lat_ms")
    for _ in range(3):
        h.observe(7.3)
    # quantiles clamp to the observed [min, max] -> exact here
    assert h.quantile(0.5) == 7.3
    assert h.quantile(0.99) == 7.3
    assert m.histogram("empty").quantile(0.5) is None
    assert m.histogram("empty").mean() is None


def test_registry_thread_safety():
    m = MetricsRegistry()
    c = m.counter("n", labels=("t",))
    h = m.histogram("h")

    def work(tid):
        for i in range(5000):
            c.inc(t=str(tid % 2))
            h.observe(float(i % 17) + 0.5)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.total() == 8 * 5000
    assert h.count() == 8 * 5000


def test_prometheus_render_shape():
    m = MetricsRegistry()
    m.counter("req_total", "requests", labels=("engine",)).inc(5,
                                                               engine="brute")
    h = m.histogram("lat_ms", "latency")
    h.observe(1.0)
    h.observe(100.0)
    text = m.render_prometheus()
    assert "# TYPE req_total counter" in text
    assert 'req_total{engine="brute"} 5' in text
    assert "# TYPE lat_ms histogram" in text
    assert "lat_ms_count 2" in text
    assert "lat_ms_sum 101" in text
    # cumulative bucket exposition ends at the +Inf edge with the count
    bucket_lines = [l for l in text.splitlines() if "lat_ms_bucket" in l]
    assert bucket_lines and bucket_lines[-1].endswith(" 2")


def test_jsonl_export_round_trips_schema(tmp_path):
    m = MetricsRegistry()
    m.counter("service_queries_total", labels=("engine",)).inc(4,
                                                               engine="brute")
    m.counter("service_scanned_total", labels=("engine",)).inc(1024,
                                                               engine="brute")
    h = m.histogram("service_request_latency_ms", labels=("engine",))
    for v in (0.5, 1.5, 2.5, 200.0):
        h.observe(v, engine="brute")
    m.gauge("service_compactions").set(2)
    path = tmp_path / "metrics.jsonl"
    n = m.export_jsonl(path, ts=123.0)
    assert n == 4
    assert validate_metrics_jsonl(path) == []     # serving-family floor met
    rows = {r["name"]: r for r in map(json.loads, path.read_text().splitlines())}
    lat = rows["service_request_latency_ms"]
    assert lat["count"] == 4 and lat["min"] == 0.5 and lat["max"] == 200.0
    assert sum(lat["buckets"].values()) == 4
    # reset zeroes children but keeps family declarations
    m.reset()
    assert m.family("service_queries_total").total() == 0
    assert m.collect() == []


def test_metrics_schema_catches_corruption(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({"name": "x", "type": "histogram",
                               "labels": {}, "count": 5, "sum": 1.0,
                               "buckets": {"1": 3}}) + "\n")
    errs = validate_metrics_jsonl(bad, require_families=())
    assert any("bucket counts sum" in e for e in errs)


def test_null_metrics_surface():
    n = NULL_METRICS
    assert isinstance(n, NullMetrics) and n.enabled is False
    fam = n.counter("anything", labels=("x",))
    fam.inc(5, x="y")
    fam.observe(1.0)
    fam.set(2.0)
    assert fam.total() == 0 and fam.quantile(0.5) is None
    assert n.collect() == [] and n.render_prometheus() == ""


# -- tracer -----------------------------------------------------------------

def test_disabled_span_fast_path(tmp_path, monkeypatch):
    tr = Tracer()
    assert tr.enabled is False
    # acceptance: no span object is allocated without a profiler session —
    # every call returns the module-level singletons and records nothing
    assert tr.span("a") is NULL_SPAN
    assert tr.span("b", key="val") is tr.span("c")
    assert tr.begin("d") is NULL_HANDLE

    def boom(*a, **kw):
        raise AssertionError("a span was built without a session")
    monkeypatch.setattr(obs_trace, "_Span", boom)
    with tr.span("e") as s:
        s.set(answer=42)
    tr.begin("f").end(done=True)
    monkeypatch.undo()
    with record(tmp_path) as rec:
        with tr.span("marker"):
            pass
    assert {"a", "b", "c", "d", "e", "f"}.isdisjoint(rec.names())
    assert len(rec.named("marker")) == 1


def test_span_nesting_and_parent_linkage(tmp_path):
    tr = Tracer()
    with record(tmp_path) as rec:
        with tr.span("outer", engine="brute"):
            with tr.span("inner") as s:
                time.sleep(0.001)
                s.set(rows=8)
    (outer,), (inner,) = rec.named("outer"), rec.named("inner")
    # nesting is containment on one thread's line of the profiler trace
    assert outer.contains(inner) and not inner.contains(outer)
    assert inner.end - inner.start >= 1_000_000
    assert outer.args == {"engine": "brute"}
    assert inner.args == {"rows": 8}


def test_flow_handles_and_tracks(tmp_path):
    """A handle's span need not nest: chunk 1's transfer opens before chunk
    0's ends and outlives it, on the thread's own line, with metadata
    added at its end."""
    tr = Tracer()
    with record(tmp_path) as rec:
        h0 = tr.begin("transfer", chunk=0)
        h1 = tr.begin("transfer", chunk=1)
        time.sleep(0.001)
        h0.end(bytes=4096)
        with tr.span("scan", chunk=0):
            time.sleep(0.001)
        h1.end()
    spans = {s.args["chunk"]: s for s in rec.named("transfer")}
    scan, = rec.named("scan")
    assert spans[0].args["bytes"] == 4096
    assert spans[0].overlaps(spans[1]) and not spans[1].contains(spans[0])
    assert spans[1].contains(scan) and not spans[0].overlaps(scan)
    assert spans[0].line == spans[1].line == scan.line


def test_tracer_enabled_follows_the_session(tmp_path):
    """The profiler session is the only switch: no configure step, no
    buffer, nothing recorded before the session or after it."""
    tr = Tracer()
    assert not hasattr(tr, "configure") and not hasattr(tr, "events")
    with tr.span("before"):
        pass
    seen = []
    with record(tmp_path) as rec:
        seen.append(tr.enabled)
        with tr.span("during"):
            pass
    seen.append(tr.enabled)
    with tr.span("after"):
        pass
    assert seen == [True, False]
    assert rec.names() & {"before", "during", "after"} == {"during"}


def test_chrome_export_file(tmp_path):
    """``session(perfetto=True)``, the operator's ``--trace-out``, writes
    the profiler trace with a Perfetto file beside it."""
    with session(tmp_path, perfetto=True):
        with obs_trace.TRACER.span("service.batch", engine="brute"):
            pass
    assert glob.glob(f"{tmp_path}/**/perfetto_trace.json.gz", recursive=True)
    from _spans import read
    batch, = [s for s in read(tmp_path) if s.name == "service.batch"]
    assert batch.args == {"engine": "brute"}


def test_batch_ids_round_trip(tmp_path):
    """Ids go in as metadata and come back as the reduction reads them: a
    span over several requests carries their rids space-separated, one
    request's span its rid alone."""
    tr = Tracer()
    with record(tmp_path) as rec:
        with tr.span("frontend.dispatch", batch=3, rid="4 5 6"):
            pass
        with tr.span("frontend.dispatch", batch=4, rid=str(7)):
            pass
    got = sorted((s.args["batch"], str(s.args["rid"]).split())
                 for s in rec.named("frontend.dispatch"))
    assert got == [(3, ["4", "5", "6"]), (4, ["7"])]
