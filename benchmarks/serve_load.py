"""Online-serving load benchmark: latency/QPS under mixed insert+query load.

Drives a :class:`repro.serve.service.SearchService` (dynamic micro-batching,
LSM-compacting mutable store) with deterministic workloads at configurable
write ratios and emits ``experiments/bench/serve_load[_backend].json``:
per-(engine, write_ratio) rows with p50/p99 request latency, QPS, scanned
candidates, compaction counts — and ``compiles_in_window``, the number of
pipeline compilations that happened inside the steady-state timed window.

The warmup phase replays enough of the workload to touch every pipeline
shape the steady state can need — all power-of-two batch buckets, every
delta-bucket size below the compaction threshold (one full delta
0 -> threshold cycle), and at least one compaction — so the timed window
measures pure serving: ``compiles_in_window`` must be 0 (asserted by the CI
smoke leg via the JSON).

``--wal`` adds the durability axis (ISSUE 6): each (engine, write_ratio)
cell is re-measured with the write-ahead log on — fsync-per-ack and
8-record group commit — against a temp directory, so the p99 rows quantify
what the acked-implies-recovered contract costs per insert.

``--slo`` switches to the SLO-tracking harness (ISSUE 8 tentpole): a
target-QPS load loop — ``--loop closed`` issues ops back-to-back (service
at capacity), ``--loop open`` paces submissions to ``--target-qps`` on a
wall-clock schedule so queueing delay shows up in the latency when the
service falls behind — with a per-phase latency breakdown read from the
service's metrics registry (queue wait / engine batch / insert / WAL
append) and a **hard p99 SLO verdict**: the run exits non-zero when
request p99 exceeds ``--slo-p99-ms``. ``--measure-overhead`` replays the
identical window against a ``metrics=False`` twin service and reports the
observability overhead as a QPS fraction (``--overhead-budget 0.05`` turns
the 5% acceptance bound into a hard failure).

``--frontend`` switches to the concurrent-tier harness (ISSUE 9): the same
workload is driven through :class:`repro.serve.frontend.SearchFrontend`
(bounded admission, deadlines, degradation ladder, N read replicas) in an
open-loop window paced past capacity, with a hard verdict on *zero
deadline misses among accepted requests* (admitted work either completes
inside its deadline or is dropped un-scored with a typed error — never
silently late). ``--failover`` kills a replica mid-window and asserts
availability plus post-rehydrate byte parity.
"""
from __future__ import annotations

import argparse
import tempfile
import time

import numpy as np

from repro.data.molecules import SyntheticConfig, synthetic_fingerprints, queries_from_db
from repro.launch.compile_cache import use_compile_cache
from repro.launch.search_serve import make_workload
from repro.serve.service import SearchService
from .common import emit

WRITE_RATIOS = (0.0, 0.01, 0.1)

# durability axis (ISSUE 6): "off" = in-memory service (the historical rows,
# names unchanged), "fsync" = WAL with fsync-per-ack, "group8" = WAL with
# 8-record group commit. The p99 delta between fsync and group8 is the price
# of the strict acked-implies-recovered contract per insert.
WAL_MODES = ("off", "fsync", "group8")
_WAL_FSYNC_EVERY = {"fsync": 1, "group8": 8}


def _capacities(svc):
    return {name: eng.store.main.capacity
            for name, eng in svc.engines.items() if hasattr(eng, "store")}


def _run_ops(svc, ops, engine, k, flush_every):
    since = 0
    for op, payload in ops:
        if op == "insert":
            svc.insert(payload)
        else:
            svc.submit(payload, k=k, engine=engine)
            since += 1
            if since >= flush_every:
                svc.flush()
                since = 0
    svc.flush()


def run(n_db=20_000, n_ops=256, k=10, backend="jnp",
        engines=("brute", "bitbound-folding"), write_ratios=WRITE_RATIOS,
        compact_threshold=None, flush_every=8, suffix=None,
        wal_modes=("off",)):
    db = synthetic_fingerprints(SyntheticConfig(n=n_db, seed=0))
    pool = synthetic_fingerprints(SyntheticConfig(n=max(4 * n_ops, 256),
                                                  seed=7))
    queries = queries_from_db(db, min(n_db, 256))
    rows = []
    for engine in engines:
        for wr, wal in ((wr, wal) for wr in write_ratios
                        for wal in wal_modes):
            # threshold low enough that the warmup pass crosses >= 1
            # compaction (and thereby visits every delta bucket) when the
            # workload writes at all
            expected_writes = max(int(n_ops * wr), 1)
            ct = compact_threshold or max(2, expected_writes // 2)
            tmpdir = (tempfile.TemporaryDirectory(prefix="serve_load_wal_")
                      if wal != "off" else None)
            durable = dict(durable_dir=tmpdir.name,
                           wal_fsync_every=_WAL_FSYNC_EVERY[wal]) \
                if tmpdir else {}
            svc = SearchService(db, engines=(engine,), backend=backend, k=k,
                                compact_threshold=ct, **durable)
            ops = make_workload(n_ops, wr, pool[:2 * n_ops], queries, seed=3)
            warm_pool = pool[2 * n_ops:]
            warm_ops = [("insert", warm_pool[i % len(warm_pool):][:1])
                        if op == "insert" else (op, payload)
                        for i, (op, payload) in enumerate(ops)]
            # warmup: same op mix, different insert rows — compiles every
            # (batch bucket, delta bucket, window bucket) shape and forces
            # the first compaction outside the timed window
            _run_ops(svc, warm_ops, engine, k, flush_every)
            # pin the delta phase: the timed window then replays exactly the
            # warmup's (batch bucket, delta bucket) shape trajectory
            svc.compact_all()
            warm_compactions = svc.compactions
            # reset telemetry; keep the engines (and their compile caches)
            svc.reset_telemetry()
            compiled_before = svc.compiled_pipelines()
            caps_before = _capacities(svc)
            _run_ops(svc, ops, engine, k, flush_every)
            compiled_after = svc.compiled_pipelines()
            capacity_crossed = _capacities(svc) != caps_before
            s = svc.summary()
            wal_sfx = "" if wal == "off" else f"_wal-{wal}"
            rows.append({
                "name": f"serve_{engine}_wr{wr}{wal_sfx}",
                "engine": engine, "backend": backend,
                "n_db": n_db, "k": k, "n_ops": n_ops,
                "write_ratio": wr, "wal": wal,
                "compact_threshold": ct,
                # summary() reports explicit None when no queries ran
                "p50_ms": s.get("p50_ms") or 0.0,
                "p99_ms": s.get("p99_ms") or 0.0,
                "qps": s["qps"],
                "n_queries": s["n_queries"],
                "n_inserts": s["n_inserts"],
                "compactions": int(svc.compactions - warm_compactions),
                "warmup_compactions": int(warm_compactions),
                "batch_buckets": s["batch_buckets"],
                "scanned": s["scanned"].get(engine, 0),
                "compiles_in_window": int(compiled_after - compiled_before),
                # a compaction crossing a main-capacity power-of-two inside
                # the window legitimately recompiles (new array shapes) —
                # reported so the hard no-recompile check can exempt it
                "capacity_crossed": bool(capacity_crossed),
            })
            svc.close()
            if tmpdir is not None:
                tmpdir.cleanup()
    sfx = suffix if suffix is not None else (
        "" if backend in (None, "jnp") else f"_{backend}")
    emit(f"serve_load{sfx}", rows)
    return rows


# -- SLO-tracking harness (ISSUE 8) -----------------------------------------

#: (report key, registry family) pairs for the per-phase latency breakdown
PHASE_FAMILIES = (
    ("queue_wait", "service_queue_wait_ms"),
    ("engine_batch", "service_engine_batch_ms"),
    ("insert", "service_insert_ms"),
    ("wal_append", "service_wal_append_ms"),
)


def _phase_breakdown(svc):
    """Read the per-phase latency families out of the service registry."""
    out = {}
    for phase, fam_name in PHASE_FAMILIES:
        fam = svc.metrics.family(fam_name)
        if fam is None:
            continue
        n = fam.count()
        if not n:
            continue
        out[phase] = {"count": int(n), "mean_ms": fam.mean(),
                      "p50_ms": fam.quantile(0.5),
                      "p99_ms": fam.quantile(0.99)}
    return out


def _run_window(svc, ops, engine, k, flush_every, loop, target_qps):
    """One timed load window; returns (wall seconds, missed deadlines).

    ``loop="closed"`` issues ops back-to-back — the service runs at
    capacity and the measured QPS *is* the capacity. ``loop="open"``
    schedules op i at ``t0 + i/target_qps`` and sleeps until its deadline:
    arrival rate is fixed, so when the service falls behind, the backlog
    shows up as queue-wait and request latency instead of silently slowing
    the generator (coordinated omission)."""
    interval = (1.0 / target_qps) if (loop == "open" and target_qps) else 0.0
    missed = 0
    since = 0
    t0 = time.perf_counter()
    for i, (op, payload) in enumerate(ops):
        if interval:
            deadline = t0 + i * interval
            now = time.perf_counter()
            if now < deadline:
                time.sleep(deadline - now)
            elif now > deadline + interval:
                missed += 1
        if op == "insert":
            svc.insert(payload)
        else:
            svc.submit(payload, k=k, engine=engine)
            since += 1
            if since >= flush_every:
                svc.flush()
                since = 0
    svc.flush()
    return time.perf_counter() - t0, missed


def _measured_service(db, pool, queries, *, engine, backend, k, n_ops,
                      write_ratio, flush_every, loop, target_qps,
                      metrics=True, **svc_kwargs):
    """Build + warm a service, run one timed window, return
    (service, wall seconds, missed deadlines). Caller closes."""
    expected_writes = max(int(n_ops * write_ratio), 1)
    ct = max(2, expected_writes // 2)
    svc = SearchService(db, engines=(engine,), backend=backend, k=k,
                        compact_threshold=ct, metrics=metrics, **svc_kwargs)
    ops = make_workload(n_ops, write_ratio, pool[:2 * n_ops], queries, seed=3)
    warm_pool = pool[2 * n_ops:]
    warm_ops = [("insert", warm_pool[i % len(warm_pool):][:1])
                if op == "insert" else (op, payload)
                for i, (op, payload) in enumerate(ops)]
    _run_ops(svc, warm_ops, engine, k, flush_every)   # compile everything
    svc.compact_all()
    svc.reset_telemetry()
    dt, missed = _run_window(svc, ops, engine, k, flush_every, loop,
                             target_qps)
    return svc, dt, missed


def run_slo(n_db=20_000, n_ops=256, k=10, backend="jnp",
            engines=("brute",), write_ratio=0.01, flush_every=8,
            loop="closed", target_qps=None, slo_p99_ms=50.0,
            measure_overhead=False, residency="device",
            tier_chunk_rows=None, tier_chunk=None, suffix=None):
    """SLO harness: per-engine load window + registry phase breakdown +
    hard p99 verdict. Emits ``experiments/bench/serve_slo*.json`` rows and
    returns them; the CLI exits non-zero when any ``slo_ok`` is false."""
    db = synthetic_fingerprints(SyntheticConfig(n=n_db, seed=0))
    pool = synthetic_fingerprints(SyntheticConfig(n=max(4 * n_ops, 256),
                                                  seed=7))
    queries = queries_from_db(db, min(n_db, 256))
    svc_kwargs = dict(residency=residency, tier_chunk_rows=tier_chunk_rows,
                      tier_chunk=tier_chunk)
    common = dict(backend=backend, k=k, n_ops=n_ops,
                  write_ratio=write_ratio, flush_every=flush_every,
                  loop=loop, target_qps=target_qps, **svc_kwargs)
    rows = []
    for engine in engines:
        svc, dt, missed = _measured_service(db, pool, queries, engine=engine,
                                            **common)
        s = svc.summary()
        phases = _phase_breakdown(svc)
        svc.close()
        p99 = s.get("p99_ms")
        achieved_qps = s["n_queries"] / dt if dt > 0 else 0.0
        row = {
            "name": f"slo_{engine}_{loop}"
                    + (f"_q{target_qps:g}" if target_qps else ""),
            "engine": engine, "backend": backend, "loop": loop,
            "n_db": n_db, "k": k, "n_ops": n_ops,
            "write_ratio": write_ratio, "residency": residency,
            "target_qps": target_qps, "achieved_qps": round(achieved_qps, 1),
            # alias for the bench-regression guard's QPS comparison key
            "host_qps": round(achieved_qps, 1),
            "missed_deadlines": missed,
            "p50_ms": s.get("p50_ms"), "p99_ms": p99,
            "mean_ms": s.get("mean_ms"),
            "slo_p99_ms": slo_p99_ms,
            "slo_ok": bool(p99 is not None and p99 <= slo_p99_ms),
            "phases": phases,
        }
        if measure_overhead:
            # identical window against a metrics-off twin: the QPS delta is
            # the whole observability bill (acceptance bound: <= 5%)
            svc2, dt2, _ = _measured_service(db, pool, queries,
                                             engine=engine, metrics=False,
                                             **common)
            n_q2 = len(svc2.latencies_ms) or s["n_queries"]
            svc2.close()
            qps_off = n_q2 / dt2 if dt2 > 0 else 0.0
            row["qps_metrics_off"] = round(qps_off, 1)
            row["overhead_frac"] = (
                round(max(0.0, 1.0 - achieved_qps / qps_off), 4)
                if qps_off > 0 else None)
        rows.append(row)
        print(f"[serve-slo] {row['name']}: p99={p99}ms "
              f"(SLO {slo_p99_ms}ms -> {'OK' if row['slo_ok'] else 'FAIL'}) "
              f"qps={row['achieved_qps']}"
              + (f" overhead={row.get('overhead_frac')}"
                 if measure_overhead else ""))
    sfx = suffix if suffix is not None else (
        "" if backend in (None, "jnp") else f"_{backend}")
    emit(f"serve_slo{sfx}", rows)
    return rows


# -- concurrent front-end harness (ISSUE 9) ----------------------------------


def _frontend_warm(fe, queries, pool, k, engine):
    """Compile every pipeline shape the overload window can touch, on every
    replica: each pow2 micro-batch bucket (the dispatcher submits
    per-request singles; the service's micro-batcher groups them) x each
    delta bucket the window's inserts can reach x each degradation level's
    effective k. Batch warming runs through each replica's worker queue so
    no compile can land inside the timed window on *any* replica; inserts
    go through the front end so the delta grows identically everywhere.
    Ends with an aligned compaction so the window starts at delta 0 with
    the (delta 1, 2, ...) shapes already cached. Returns rows consumed
    from ``pool``."""
    import math

    sizes, b = [], 1
    while b <= min(fe.fcfg.high_water, 256):
        sizes.append(b)
        b *= 2
    k_effs = sorted({max(1, int(math.floor(k * lvl.k_scale)))
                     for lvl in fe.fcfg.ladder})

    def _warm_buckets(svc):
        for k_eff in k_effs:
            for n in sizes:
                for j in range(n):
                    svc.submit(queries[j % len(queries)], k_eff, engine)
                svc.flush()

    def _wait_all(futs):
        for f in futs:
            f.result(timeout=600.0)

    inserted = 0
    for target in (0, 1, 2, 4):
        while inserted < target:
            fe.insert(pool[inserted:inserted + 1])
            inserted += 1
        _wait_all([rep.call(_warm_buckets, label="warm")
                   for rep in fe.replicas])
    _wait_all([rep.call(lambda svc: svc.compact_all(), label="warm")
               for rep in fe.replicas])
    return inserted


def run_frontend_slo(n_db=20_000, n_ops=256, k=10, backend="jnp",
                     engine="brute", replicas=1, write_ratio=0.01,
                     high_water=64, deadline_ms=1000.0, target_qps=None,
                     overload_factor=2.0, failover=False,
                     metrics_out=None, suffix=None):
    """Overload / failover harness for the concurrent serving tier.

    Builds a :class:`repro.serve.frontend.SearchFrontend` with ``replicas``
    read replicas, measures its closed-loop capacity, then runs an
    open-loop window paced to ``target_qps`` (default ``overload_factor``
    x capacity — overloaded *by construction*, so bounded admission must
    shed). The verdict (``slo_ok``) demands **zero deadline misses among
    accepted requests**: every admitted query either completes inside its
    deadline or is dropped un-scored with a typed ``DeadlineExceeded``.

    ``failover=True`` (requires ``replicas >= 2``) kills one replica at the
    window midpoint and additionally asserts availability (completions
    after the kill), rehydration (the slot comes back at a higher
    generation), and post-rehydrate byte parity between the rebuilt
    replica and a survivor.

    Emits one ``experiments/bench/serve_slo*.json`` row with the
    ``replicas`` / ``degradation`` measurement-shape keys; ``target_qps``
    is recorded as None when auto-derived (the actual pace lands in
    ``paced_qps``) so rows stay regression-comparable across machines.
    """
    from repro.serve.frontend import (DeadlineExceeded, FrontendConfig,
                                      Overloaded, SearchFrontend,
                                      Unavailable)
    if failover and replicas < 2:
        raise ValueError("failover run needs replicas >= 2 (one dies, one "
                         "keeps serving)")
    db = synthetic_fingerprints(SyntheticConfig(n=n_db, seed=0))
    pool = synthetic_fingerprints(SyntheticConfig(n=max(4 * n_ops, 256),
                                                  seed=7))
    queries = queries_from_db(db, min(n_db, 256))
    fcfg = FrontendConfig(replicas=replicas, high_water=high_water,
                          default_deadline_ms=deadline_ms,
                          flush_interval_ms=1.0,
                          # first-compile stalls are not wedges; failover is
                          # exercised via the explicit kill hook below
                          health_timeout_s=60.0)
    fe = SearchFrontend(db, engines=(engine,), backend=backend, k=k,
                        compact_threshold=2 ** 30, frontend=fcfg)
    try:
        used = _frontend_warm(fe, queries, pool, k, engine)
        # closed-loop capacity probe in concurrent waves: sequential
        # single-client search would measure the dispatcher-tick latency
        # floor, not the micro-batched throughput the admission bound is
        # sized against — waves of in-flight requests measure the latter
        n_probe = min(max(32, n_ops // 2), 128)
        wave = max(1, min(high_water // 2, 16))
        done = 0
        t0 = time.perf_counter()
        while done < n_probe:
            futs = [fe.submit(queries[(done + j) % len(queries)], k, engine,
                              deadline_ms=None)
                    for j in range(min(wave, n_probe - done))]
            for f in futs:
                f.result(timeout=60.0)
            done += len(futs)
        cap_qps = n_probe / max(time.perf_counter() - t0, 1e-9)

        paced = target_qps if target_qps else overload_factor * cap_qps
        if failover and not target_qps:
            # the failover leg measures sustained availability through a
            # kill + rehydrate, not shedding: pace the window to span ~2s
            # of wall time so "mid-run" leaves real traffic after the kill
            paced = min(paced, max(n_ops / 2.0, 1.0))
        interval = 1.0 / paced
        ops = make_workload(n_ops, write_ratio, pool[used:used + 2 * n_ops],
                            queries, seed=3)
        kill_at = n_ops // 2 if failover else None
        kill_idx = replicas - 1

        import queue as queue_mod
        import threading
        stats = {"expired": 0, "unavailable": 0, "lat_ms": [],
                 "after_kill": 0}
        pend: queue_mod.Queue = queue_mod.Queue()

        def _collect():
            # futures complete roughly FIFO (dispatch order), so a single
            # sequential collector measures completion latency with at most
            # scheduling-noise overestimate — conservative for miss counting
            while True:
                item = pend.get()
                if item is None:
                    return
                fut, t_sub, after_kill = item
                try:
                    fut.result(timeout=120.0)
                except DeadlineExceeded:
                    stats["expired"] += 1
                    continue
                except Unavailable:
                    stats["unavailable"] += 1
                    continue
                stats["lat_ms"].append((time.perf_counter() - t_sub) * 1e3)
                if after_kill:
                    stats["after_kill"] += 1

        collector = threading.Thread(target=_collect, daemon=True)
        collector.start()
        shed = 0
        killed = False
        t0 = time.perf_counter()
        for i, (op, payload) in enumerate(ops):
            slot = t0 + i * interval
            now = time.perf_counter()
            if now < slot:
                time.sleep(slot - now)
            if kill_at is not None and i == kill_at and not killed:
                fe.kill_replica(kill_idx)
                killed = True
            if op == "insert":
                try:
                    fe.insert(payload)
                except Unavailable:
                    stats["unavailable"] += 1
            else:
                try:
                    fut = fe.submit(payload, k=k, engine=engine)
                except Overloaded:
                    shed += 1
                    continue
                pend.put((fut, time.perf_counter(), killed))
        fe.drain(timeout=120.0)
        dt = time.perf_counter() - t0
        pend.put(None)
        collector.join(timeout=120.0)

        failover_ok = None
        if failover:
            wait_until = time.perf_counter() + 60.0
            while (fe.live_replicas() < replicas
                   and time.perf_counter() < wait_until):
                time.sleep(0.05)
            rehydrated = (fe.live_replicas() == replicas
                          and fe.replicas[kill_idx].generation > 0)
            parity = False
            if rehydrated:
                # a write after rehydration must land on the rebuilt slot
                # too, and both replicas must extract identical bytes
                fe.insert(pool[used + 2 * n_ops:used + 2 * n_ops + 2])
                a0, _ = fe.replica_state(0)
                a1, _ = fe.replica_state(kill_idx)
                parity = (set(a0) == set(a1)
                          and all(np.array_equal(a0[name], a1[name])
                                  for name in a0))
            availability = stats["after_kill"] > 0
            failover_ok = bool(rehydrated and parity and availability)

        lat = stats["lat_ms"]
        misses = (sum(1 for v in lat if v > deadline_ms)
                  if deadline_ms is not None else 0)
        s = fe.summary()
        if metrics_out:
            fe.export_metrics(metrics_out, ts=time.time())
        row = {
            "name": f"frontend_{engine}_r{replicas}"
                    + ("_failover" if failover else ""),
            "engine": engine, "backend": backend, "loop": "open",
            "n_db": n_db, "k": k, "n_ops": n_ops,
            "write_ratio": write_ratio,
            "replicas": replicas, "degradation": len(fe.fcfg.ladder),
            "high_water": high_water, "deadline_ms": deadline_ms,
            "target_qps": target_qps if target_qps else None,
            "paced_qps": round(paced, 1),
            "capacity_qps": round(cap_qps, 1),
            "achieved_qps": round(len(lat) / dt, 1) if dt > 0 else 0.0,
            "host_qps": round(len(lat) / dt, 1) if dt > 0 else 0.0,
            "completed": len(lat),
            "shed": int(s["shed"]), "expired": int(s["expired"]),
            "unavailable": int(stats["unavailable"]),
            "deadline_misses": int(misses),
            "failovers": int(s["failovers"]),
            "max_degradation_level": int(s["max_degradation_level"]),
            "p50_ms": (round(float(np.percentile(lat, 50)), 3)
                       if lat else None),
            "p99_ms": (round(float(np.percentile(lat, 99)), 3)
                       if lat else None),
            "slo_ok": bool(misses == 0 and failover_ok is not False),
        }
        if failover:
            row["failover_ok"] = failover_ok
            row["completed_after_kill"] = int(stats["after_kill"])
        print(f"[serve-frontend] {row['name']}: paced={row['paced_qps']}qps "
              f"(capacity {row['capacity_qps']}) completed={row['completed']}"
              f" shed={row['shed']} expired={row['expired']} "
              f"misses={row['deadline_misses']} p99={row['p99_ms']}ms "
              f"degrade<= {row['max_degradation_level']} "
              f"-> {'OK' if row['slo_ok'] else 'FAIL'}"
              + (f" failover_ok={failover_ok}" if failover else ""))
    finally:
        fe.close()
    rows = [row]
    sfx = suffix if suffix is not None else (
        "" if backend in (None, "jnp") else f"_{backend}")
    emit(f"serve_slo{sfx}", rows)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="jnp",
                    choices=["numpy", "jnp", "tpu"])
    ap.add_argument("--n-db", type=int, default=20_000)
    ap.add_argument("--ops", type=int, default=256)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--engines", default="brute,bitbound-folding",
                    help="comma-separated: brute,bitbound-folding,hnsw")
    ap.add_argument("--write-ratio", type=float, default=None,
                    help="run a single write ratio instead of the sweep "
                         f"{WRITE_RATIOS}")
    ap.add_argument("--compact-threshold", type=int, default=None)
    ap.add_argument("--flush-every", type=int, default=8)
    ap.add_argument("--wal", action="store_true",
                    help=f"sweep the durability axis {WAL_MODES} (WAL into "
                         "a temp dir; p99 delta fsync-per-ack vs group "
                         "commit vs in-memory)")
    ap.add_argument("--slo", action="store_true",
                    help="SLO-tracking mode: load loop + per-phase registry "
                         "breakdown + hard p99 verdict (exit non-zero on "
                         "violation)")
    ap.add_argument("--loop", default="closed", choices=["closed", "open"],
                    help="SLO mode: closed = back-to-back (capacity), open "
                         "= wall-clock paced to --target-qps (queueing "
                         "visible when the service falls behind)")
    ap.add_argument("--target-qps", type=float, default=None,
                    help="SLO mode, open loop: arrival rate to pace to")
    ap.add_argument("--slo-p99-ms", type=float, default=50.0,
                    help="SLO mode: request-latency p99 bound (ms)")
    ap.add_argument("--measure-overhead", action="store_true",
                    help="SLO mode: replay the window against a "
                         "metrics=False twin and report overhead_frac")
    ap.add_argument("--overhead-budget", type=float, default=None,
                    help="SLO mode: fail when overhead_frac exceeds this "
                         "(the acceptance bound is 0.05)")
    ap.add_argument("--residency", default="device",
                    choices=["device", "tiered"])
    ap.add_argument("--tier-chunk-rows", type=int, default=None)
    ap.add_argument("--tier-chunk", type=int, default=None)
    ap.add_argument("--frontend", action="store_true",
                    help="concurrent-tier mode (ISSUE 9): open-loop "
                         "overload window through SearchFrontend; paced to "
                         "--target-qps or 2x measured capacity; exits "
                         "non-zero on any deadline miss among accepted "
                         "requests")
    ap.add_argument("--replicas", type=int, default=1,
                    help="frontend mode: read replicas behind the front end")
    ap.add_argument("--high-water", type=int, default=64,
                    help="frontend mode: admission bound (in-flight "
                         "requests before typed Overloaded shedding)")
    ap.add_argument("--deadline-ms", type=float, default=1000.0,
                    help="frontend mode: per-request deadline (<= 0 "
                         "disables deadlines)")
    ap.add_argument("--failover", action="store_true",
                    help="frontend mode: kill one replica mid-window and "
                         "assert availability + post-rehydrate byte parity "
                         "(needs --replicas >= 2)")
    ap.add_argument("--expect-shed", action="store_true",
                    help="frontend mode: fail unless the window actually "
                         "shed (guards the overload-by-construction smoke)")
    ap.add_argument("--metrics-out", default=None,
                    help="frontend mode: export the merged front-end + "
                         "replica metrics registries as JSONL here")
    ap.add_argument("--out-suffix", default=None,
                    help="override the emitted artifact suffix (e.g. "
                         "_smoke keeps CI runs off the committed rows)")
    args = ap.parse_args()
    use_compile_cache()
    if args.frontend:
        rows = run_frontend_slo(
            n_db=args.n_db, n_ops=args.ops, k=args.k, backend=args.backend,
            engine=args.engines.split(",")[0],
            replicas=args.replicas,
            write_ratio=(args.write_ratio
                         if args.write_ratio is not None else 0.01),
            high_water=args.high_water,
            deadline_ms=(args.deadline_ms if args.deadline_ms > 0 else None),
            target_qps=args.target_qps, failover=args.failover,
            metrics_out=args.metrics_out, suffix=args.out_suffix)
        bad = [r["name"] for r in rows if not r["slo_ok"]]
        if args.expect_shed:
            bad += [f"{r['name']} (no shedding at {r['paced_qps']} qps)"
                    for r in rows if not r["shed"]]
        if bad:
            raise SystemExit(f"frontend SLO violated: {bad}")
        return
    if args.slo:
        if args.loop == "open" and not args.target_qps:
            ap.error("--loop open requires --target-qps")
        rows = run_slo(n_db=args.n_db, n_ops=args.ops, k=args.k,
                       backend=args.backend,
                       engines=tuple(args.engines.split(",")),
                       write_ratio=(args.write_ratio
                                    if args.write_ratio is not None
                                    else 0.01),
                       flush_every=args.flush_every, loop=args.loop,
                       target_qps=args.target_qps,
                       slo_p99_ms=args.slo_p99_ms,
                       measure_overhead=(args.measure_overhead
                                         or args.overhead_budget is not None),
                       residency=args.residency,
                       tier_chunk_rows=args.tier_chunk_rows,
                       tier_chunk=args.tier_chunk, suffix=args.out_suffix)
        bad = [r["name"] for r in rows if not r["slo_ok"]]
        if args.overhead_budget is not None:
            bad += [f"{r['name']} (overhead {r['overhead_frac']} > "
                    f"{args.overhead_budget})" for r in rows
                    if (r.get("overhead_frac") or 0) > args.overhead_budget]
        if bad:
            raise SystemExit(f"SLO violated: {bad}")
        return
    ratios = (args.write_ratio,) if args.write_ratio is not None \
        else WRITE_RATIOS
    rows = run(n_db=args.n_db, n_ops=args.ops, k=args.k,
               backend=args.backend,
               engines=tuple(args.engines.split(",")),
               write_ratios=ratios,
               compact_threshold=args.compact_threshold,
               flush_every=args.flush_every,
               wal_modes=WAL_MODES if args.wal else ("off",),
               suffix=args.out_suffix)
    bad = [r for r in rows
           if r["compiles_in_window"] and not r["capacity_crossed"]]
    if bad:
        raise SystemExit(
            f"steady-state window recompiled: "
            f"{[(r['name'], r['compiles_in_window']) for r in bad]}")


if __name__ == "__main__":
    main()
