"""Online molecular-similarity search service (paper §V deployment shape).

The paper's host streams queries into fixed-interval pipelines and appends
new compounds without stalling the scan engines. :class:`SearchService` is
that host for the TPU engines:

* **request queue + dynamic micro-batcher** — :meth:`submit` enqueues
  requests (any per-request ``k`` / engine); :meth:`flush` groups pending
  requests by ``(engine, k)``, concatenates their queries and pads each
  chunk to a **power-of-two batch bucket** (zero queries, results dropped)
  so every flush replays one of O(log max_batch) compiled pipeline shapes —
  steady-state serving never recompiles.
* **engine router** — one service fronts any subset of the three engines
  (``brute`` / ``bitbound-folding`` / ``hnsw``) over the same logical
  database; requests pick their engine per call.
* **online inserts** — :meth:`insert` broadcasts new fingerprints to every
  engine (delta append + threshold-triggered LSM compaction in the store;
  incremental graph inserts for HNSW) and checks the engines agree on the
  assigned global ids. Search results at any interleaving are bit-identical
  to engines rebuilt from scratch on the concatenated database
  (``tests/test_insert_parity.py`` / ``tests/test_service.py``).
* **telemetry** — per-request latency (submit -> flush completion),
  p50/p99/QPS, batch-bucket histogram, per-engine scanned counters and
  compaction counts (:meth:`summary`). Since ISSUE 8 the backing store is a
  :class:`repro.obs.metrics.MetricsRegistry` (:attr:`metrics`) — bounded
  log-bucketed latency histograms and labeled counters/gauges with
  Prometheus/JSONL exposition — plus spans through
  ``repro.obs.trace.TRACER`` on the profiler's timeline (``service.batch``
  per (engine, k) group, ``service.pad`` for the bucket padding and the
  concatenation of queries and results, ``service.engine_search`` per
  engine call, ``service.insert`` with its WAL append, snapshot writes).
  ``latencies_ms`` / ``batches`` remain as *bounded* recent
  windows (``TELEMETRY_WINDOW``) so sustained load cannot grow host memory;
  ``summary()`` keys are unchanged and always present (``None`` percentiles
  on a write-only run).

The service is synchronous and deterministic by design (no threads): a
driver loop decides when to flush, which keeps parity tests and benchmark
replays exact. ``launch/search_serve.py --engine service`` drives it with
mixed insert+query workloads.
The store -> service -> engine request path is documented in
docs/ARCHITECTURE.md.
"""
from __future__ import annotations

import threading
import time
from collections import Counter, deque
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from ..checkpoint import manager as ckpt
from ..checkpoint.fs import DEFAULT_FS, Fs
from ..core.engine import (BitBoundFoldingEngine, BruteForceEngine,
                           HNSWEngine)
from ..core.fingerprints import resolve_metric
from ..core.hnsw import DEFAULT_BUILD_ROUND
from ..obs.metrics import MetricsRegistry, NULL_METRICS
from ..obs.trace import TRACER as _TR
from . import snapshot as snap
from . import wal as wal_mod
from .store import next_pow2

ENGINE_NAMES = ("brute", "bitbound-folding", "hnsw")


@dataclass
class _Request:
    rid: int
    queries: np.ndarray          # (n, W) uint32
    k: int
    engine: str
    t_submit: float


@dataclass
class ServiceConfig:
    """Engine-construction knobs shared by the service entry points."""
    backend: str | None = None
    metric: str = "tanimoto"     # similarity spec: "tanimoto" | "dice" |
    #   "cosine" | "tversky(a,b)" — every engine scores, prunes and builds
    #   graphs under this metric (core/fingerprints.Metric)
    fp_bits: int | None = None   # fingerprint width in bits; None = infer
    #   from the database rows (words * 32)
    k: int = 10
    max_batch: int = 256
    compact_threshold: int = 4096
    cutoff: float = 0.6
    fold_m: int = 4
    fold_scheme: int = 1
    hnsw_m: int = 8
    hnsw_ef_construction: int = 40
    hnsw_ef_search: int = 32
    hnsw_layout: str = "blocked"  # the one expansion layout; kept only
    #   because bench/configs/chembl27-hnsw.json names it, and goes when
    #   that configuration drops it
    hnsw_shards: int | None = None  # fan-out HNSW over N per-device shards
    hnsw_build_round: int = DEFAULT_BUILD_ROUND  # the bulk build's largest
    #   round of rows inserted together; like efConstruction it defines
    #   the graph
    residency: str = "device"    # "tiered" = host-resident full rows,
    #   double-buffered host->HBM streaming rescore (store-backed engines)
    tier_chunk_rows: int | None = None  # brute tiered: rows per streamed
    #   chunk (None = engine default); small values force multi-chunk
    #   streams for tests / trace captures
    tier_chunk: int | None = None       # bitbound tiered: candidate columns
    #   per streamed rescore chunk (None = engine default)
    seed: int = 0
    # --- observability (ISSUE 8; docs/ARCHITECTURE.md §Observability) ---
    metrics: bool = True         # False = NULL_METRICS no-op registry;
    #   summary() falls back to the bounded recent-window deque for
    #   percentiles
    # --- durability (ISSUE 6; docs/ARCHITECTURE.md §On-disk format) ---
    durable_dir: str | None = None  # snapshots/ + wal/ live here; None = RAM
    wal_fsync_every: int = 1     # 1 = fsync per ack; N = group commit (the
    #   last N-1 acked inserts may be lost on crash — benchmark axis only)
    snapshot_keep: int = 2       # retained snapshot generations (walk-back)

    def __post_init__(self):
        if self.hnsw_layout != "blocked":
            raise ValueError(f"hnsw_layout must be 'blocked', got "
                             f"{self.hnsw_layout!r}")


class SearchService:
    """Request-queue front end over the online-insertable search engines."""

    #: completed-but-unredeemed results kept before FIFO eviction — bounds
    #: memory for drivers that consume flush() returns and never result()
    RESULT_BUFFER = 1024

    #: recent-window bound for the legacy ``latencies_ms`` / ``batches``
    #: telemetry views — under sustained load they are rolling windows, not
    #: append-only lists; full-run aggregates live in :attr:`metrics`
    TELEMETRY_WINDOW = 4096

    def __init__(self, db, engines=("bitbound-folding",),
                 config: ServiceConfig | None = None,
                 clock=time.perf_counter, fs: Fs | None = None, **overrides):
        cfg = config or ServiceConfig(**overrides)
        if overrides and config is not None:
            raise ValueError("pass either config= or keyword overrides")
        self.config = cfg
        self.clock = clock
        db = np.atleast_2d(np.asarray(db, dtype=np.uint32))
        self.words = int(db.shape[1])
        self.engines = {name: self._build_engine(name, db) for name in engines}
        self.default_engine = engines[0]
        self._pending: list[_Request] = []
        self._results: dict[int, tuple] = {}
        self._next_rid = 0
        self._fs = fs or DEFAULT_FS
        self._wal = None
        self._snap_id = -1
        self._snap_thread = None
        self._snap_error = None
        self._lifecycle_lock = threading.RLock()
        self._closed = False
        self.reset_telemetry()
        if cfg.durable_dir is not None:
            self._attach_durable_dir(fresh=True)

    def reset_telemetry(self) -> None:
        """Zero the telemetry counters and the metrics registry (engines and
        their compile caches are untouched). Benchmarks call this between
        warmup and timed windows."""
        if not hasattr(self, "metrics"):
            self._init_metrics()
        self.metrics.reset()
        # bounded recent windows (back-compat views; see TELEMETRY_WINDOW)
        self.latencies_ms: deque = deque(maxlen=self.TELEMETRY_WINDOW)
        self.batches: deque = deque(maxlen=self.TELEMETRY_WINDOW)
        self._batch_buckets: Counter = Counter()   # full-run, O(log batch)
        self.scanned_total: Counter = Counter()
        self.n_queries = 0
        self.n_inserts = 0
        self.search_time = 0.0
        self.insert_time = 0.0
        # the build gauges describe the graph, not a window: set again
        for name, eng in getattr(self, "engines", {}).items():
            b = getattr(eng, "build_stats", None)
            if b:
                self._m_hnsw_build_rows.set(b["rows"], engine=name)
                self._m_hnsw_build_s.set(b["seconds"], engine=name)
                self._m_hnsw_build_rounds.set(b["rounds"], engine=name)

    def _init_metrics(self) -> None:
        """Declare the service metric families (ISSUE 8). Families are
        stable across :meth:`reset_telemetry`; only the values reset.
        ``ServiceConfig.metrics=False`` swaps in the no-op registry."""
        self.metrics = (MetricsRegistry() if self.config.metrics
                        else NULL_METRICS)
        m = self.metrics
        self._m_queries = m.counter(
            "service_queries_total", "queries completed", labels=("engine",))
        self._m_inserts = m.counter(
            "service_inserts_total", "fingerprint rows inserted")
        self._m_scanned = m.counter(
            "service_scanned_total", "candidates scored", labels=("engine",))
        self._m_stage1_tiles = m.counter(
            "service_stage1_tiles_total",
            "row-window kernel tiles: per-query windows (kind=window), "
            "query-block unions streamed (kind=streamed)",
            labels=("engine", "kind"))
        self._m_batches = m.counter(
            "service_batches_total", "engine flush batches",
            labels=("engine", "bucket"))
        self._m_req_lat = m.histogram(
            "service_request_latency_ms", "submit -> flush completion",
            labels=("engine",))
        self._m_queue_wait = m.histogram(
            "service_queue_wait_ms", "submit -> batch formation",
            labels=("engine",))
        self._m_batch_ms = m.histogram(
            "service_engine_batch_ms", "one (engine, k) flush group",
            labels=("engine",))
        self._m_insert_ms = m.histogram(
            "service_insert_ms", "insert broadcast incl. WAL")
        self._m_wal_ms = m.histogram(
            "service_wal_append_ms", "WAL append+fsync before ack")
        self._m_compactions = m.gauge(
            "service_compactions", "store compactions to date")
        self._m_tier_stall = m.gauge(
            "service_tiered_stall_seconds",
            "double-buffer stall in the last tiered search",
            labels=("engine",))
        self._m_tier_chunks = m.gauge(
            "service_tiered_chunks",
            "chunks streamed in the last tiered search", labels=("engine",))
        self._m_tier_stall_frac = m.gauge(
            "service_tiered_stall_fraction",
            "stall fraction of the last tiered search", labels=("engine",))
        self._m_hnsw_build_rows = m.gauge(
            "service_hnsw_build_rows", "rows the HNSW bulk build inserted",
            labels=("engine",))
        self._m_hnsw_build_s = m.gauge(
            "service_hnsw_build_seconds", "seconds the HNSW bulk build took",
            labels=("engine",))
        self._m_hnsw_build_rounds = m.gauge(
            "service_hnsw_build_rounds", "rounds of the HNSW bulk build",
            labels=("engine",))
        self._m_hnsw_evals = m.counter(
            "service_hnsw_neighbour_evals_total",
            "neighbours the HNSW traversal scored", labels=("engine",))
        self._m_hnsw_iters = m.counter(
            "service_hnsw_iters_total",
            "HNSW traversal iterations, summed over queries",
            labels=("engine",))
        self._m_hnsw_max_iters = m.counter(
            "service_hnsw_max_iters_hit_total",
            "queries whose HNSW traversal stopped at its iteration budget",
            labels=("engine",))

    def _engine_kwargs(self, name: str) -> dict:
        """ServiceConfig -> engine constructor knobs (shared by fresh builds
        and snapshot restores, which pass data separately)."""
        cfg = self.config
        if name == "brute":
            # brute has no host reference path; map "numpy" to the jnp path
            be = cfg.backend if cfg.backend in ("jnp", "tpu") else None
            kw = dict(backend=be, compact_threshold=cfg.compact_threshold,
                      residency=cfg.residency, metric=cfg.metric,
                      fp_bits=cfg.fp_bits)
            if cfg.tier_chunk_rows is not None:
                kw["tier_chunk_rows"] = cfg.tier_chunk_rows
            return kw
        if name == "bitbound-folding":
            kw = dict(cutoff=cfg.cutoff, m=cfg.fold_m,
                      scheme=cfg.fold_scheme, backend=cfg.backend,
                      compact_threshold=cfg.compact_threshold,
                      residency=cfg.residency, metric=cfg.metric,
                      fp_bits=cfg.fp_bits)
            if cfg.tier_chunk is not None:
                kw["tier_chunk"] = cfg.tier_chunk
            return kw
        if name == "hnsw":
            return dict(m=cfg.hnsw_m,
                        ef_construction=cfg.hnsw_ef_construction,
                        ef_search=cfg.hnsw_ef_search, seed=cfg.seed,
                        backend=cfg.backend,
                        shards=cfg.hnsw_shards, metric=cfg.metric,
                        fp_bits=cfg.fp_bits,
                        build_round=cfg.hnsw_build_round)
        raise ValueError(
            f"unknown engine {name!r}; expected one of {ENGINE_NAMES}")

    def _build_engine(self, name: str, db: np.ndarray):
        kind = {"brute": BruteForceEngine,
                "bitbound-folding": BitBoundFoldingEngine,
                "hnsw": HNSWEngine}.get(name)
        if kind is None:
            raise ValueError(
                f"unknown engine {name!r}; expected one of {ENGINE_NAMES}")
        return kind(db, **self._engine_kwargs(name))

    # -- write path ---------------------------------------------------------
    def _apply_insert(self, fps: np.ndarray) -> np.ndarray:
        """Apply one insert batch to every engine (no WAL, no telemetry) —
        the shared path under :meth:`insert` and WAL replay."""
        gids = None
        for name, eng in self.engines.items():
            g = eng.insert(fps)
            if gids is None:
                gids = g
            elif not np.array_equal(g, gids):
                raise RuntimeError(
                    f"engine {name} assigned ids {g}, expected {gids}")
        return gids

    def insert(self, fps) -> np.ndarray:
        """Append fingerprints online to every engine; returns the global
        ids (engines must agree — one logical database). On a durable
        service the batch is WAL-logged and fsync'd **before** it is
        applied, so a return from this method means the insert survives any
        subsequent crash (modulo an explicit group-commit window)."""
        t0 = self.clock()
        fps = np.atleast_2d(np.asarray(fps, dtype=np.uint32))
        comp0 = self.compactions
        with _TR.span("service.insert", rows=int(fps.shape[0])):
            if self._wal is not None and fps.shape[0]:
                first_gid = next(iter(self.engines.values())).n_total
                tw = self.clock()
                self._wal.append(first_gid, fps)
                self._m_wal_ms.observe((self.clock() - tw) * 1e3)
            gids = self._apply_insert(fps)
            if self._wal is not None and self.compactions != comp0:
                self._wal.rotate()     # segment rotation on compaction
        self.n_inserts += fps.shape[0]
        self._m_inserts.inc(fps.shape[0])
        self._m_compactions.set(self.compactions)
        dt = self.clock() - t0
        self.insert_time += dt
        self._m_insert_ms.observe(dt * 1e3)
        return gids

    # -- read path ----------------------------------------------------------
    def submit(self, queries, k: int | None = None,
               engine: str | None = None) -> int:
        """Enqueue a search request (single query row or a (n, W) batch);
        returns a request id redeemed by :meth:`flush` / :meth:`result`."""
        engine = engine or self.default_engine
        if engine not in self.engines:
            raise ValueError(f"engine {engine!r} not served "
                             f"(have {tuple(self.engines)})")
        queries = np.atleast_2d(np.asarray(queries, dtype=np.uint32))
        req = _Request(self._next_rid, queries, int(k or self.config.k),
                       engine, self.clock())
        self._pending.append(req)
        self._next_rid += 1
        return req.rid

    def flush(self) -> dict[int, tuple]:
        """Run every pending request through its engine, micro-batched by
        (engine, k) and padded to power-of-two batch buckets. Returns
        {rid: (ids, sims)} for the requests completed by this flush."""
        pending, self._pending = self._pending, []
        done: dict[int, tuple] = {}
        groups: dict[tuple, list[_Request]] = {}
        for r in pending:
            groups.setdefault((r.engine, r.k), []).append(r)
        for (ename, k), reqs in groups.items():
            with _TR.span("service.batch", engine=ename, k=int(k),
                          n_queries=sum(r.queries.shape[0] for r in reqs),
                          n_requests=len(reqs)):
                self._flush_group(ename, k, reqs, done)
        self._results.update(done)
        # FIFO-evict beyond the buffer bound: callers that consume flush()'s
        # return and never result() must not leak arrays forever
        while len(self._results) > self.RESULT_BUFFER:
            self._results.pop(next(iter(self._results)))
        return done

    def _flush_group(self, ename: str, k: int, reqs: list, done: dict):
        """One (engine, k) group: concatenate, pad each chunk to its
        bucket, search, split the results back per request."""
        eng = self.engines[ename]
        with _TR.span("service.pad"):
            qs = np.concatenate([r.queries for r in reqs])
        n, w = qs.shape
        ids_parts, sims_parts = [], []
        t0 = self.clock()
        for r in reqs:
            self._m_queue_wait.observe((t0 - r.t_submit) * 1e3, engine=ename)
        off = 0
        while off < n:
            chunk = qs[off:off + self.config.max_batch]
            bucket = next_pow2(chunk.shape[0])
            with _TR.span("service.pad", bucket=int(bucket)):
                padded = np.zeros((bucket, w), dtype=np.uint32)
                padded[:chunk.shape[0]] = chunk
            with _TR.span("service.engine_search", engine=ename,
                          bucket=int(bucket)):
                ids, sims = eng.search(padded, k)
            ids_parts.append(np.asarray(ids)[:chunk.shape[0]])
            sims_parts.append(np.asarray(sims)[:chunk.shape[0]])
            self.batches.append({"engine": ename, "k": k,
                                 "bucket": int(bucket),
                                 "n": int(chunk.shape[0])})
            self._batch_buckets[int(bucket)] += 1
            self._m_batches.inc(engine=ename, bucket=int(bucket))
            sc = eng.scanned(bucket)
            self.scanned_total[ename] += sc
            self._m_scanned.inc(sc, engine=ename)
            window, streamed = eng.stage1_tiles()
            if streamed:
                self._m_stage1_tiles.inc(window, engine=ename, kind="window")
                self._m_stage1_tiles.inc(streamed, engine=ename,
                                         kind="streamed")
            self._fold_engine_stats(ename, eng)
            off += chunk.shape[0]
        dt = self.clock() - t0
        self.search_time += dt
        self._m_batch_ms.observe(dt * 1e3, engine=ename)
        with _TR.span("service.pad"):
            ids = np.concatenate(ids_parts)
            sims = np.concatenate(sims_parts)
        t_done = self.clock()
        off = 0
        for r in reqs:
            nr = r.queries.shape[0]
            done[r.rid] = (ids[off:off + nr], sims[off:off + nr])
            off += nr
            self.latencies_ms.append((t_done - r.t_submit) * 1e3)
            self._m_req_lat.observe((t_done - r.t_submit) * 1e3,
                                    engine=ename)
            self.n_queries += nr
            self._m_queries.inc(nr, engine=ename)

    def result(self, rid: int):
        """Redeem a completed request (pops it from the result buffer).
        Raises ``KeyError`` for unknown rids, including results evicted past
        :attr:`RESULT_BUFFER` unredeemed completions."""
        return self._results.pop(rid)

    def search(self, queries, k: int | None = None,
               engine: str | None = None):
        """Convenience synchronous path: submit + flush + redeem."""
        rid = self.submit(queries, k, engine)
        self.flush()
        return self._results.pop(rid)

    def compact_all(self) -> None:
        """Force-compact every store-backed engine's delta (operational
        hook: benchmarks use it to pin the delta phase before a measurement
        window; a deployment would call it off-peak)."""
        comp0 = self.compactions
        for eng in self.engines.values():
            store = getattr(eng, "store", None)
            if store is not None and store.n_delta:
                store.compact()
        if self._wal is not None and self.compactions != comp0:
            self._wal.rotate()

    # -- durability (ISSUE 6) ------------------------------------------------
    def _attach_durable_dir(self, fresh: bool) -> None:
        base = Path(self.config.durable_dir)
        self._snap_dir = base / "snapshots"
        self._wal_dir = base / "wal"
        if fresh and (ckpt.snapshot_steps(self._snap_dir)
                      or wal_mod.segment_seqs(self._wal_dir)):
            raise ValueError(
                f"{base} already holds durable state; use "
                f"SearchService.open() to warm-restart from it")
        self._wal = wal_mod.WriteAheadLog(
            self._wal_dir, self.words, fs=self._fs,
            fsync_every=self.config.wal_fsync_every)
        if fresh:
            self.snapshot()    # base DB is recoverable before any insert

    def snapshot(self, *, background: bool = False) -> int:
        """Write a full-state snapshot generation; rotates the WAL first so
        the snapshot's ``wal_from_seq`` covers exactly the records after it,
        then garbage-collects segments no retained snapshot needs. Crash
        windows: before the atomic publish the old snapshot + full WAL
        recover everything; after it the GC'd segments are redundant.

        ``background=True`` moves the serialization + fsync work off the
        serving thread: the state is **extracted synchronously** as
        copy-on-write numpy arrays (extraction is a copy — the writer never
        aliases live store/graph arrays, so inserts keep acking while the
        snapshot is in flight), then a daemon thread saves, prunes and
        WAL-GCs. At most one writer is in flight; a second ``snapshot()``
        (or :meth:`close`) joins the previous one first, and any writer
        exception is re-raised at the next :meth:`snapshot_join` /
        :meth:`snapshot` / :meth:`close`.

        The current **recovery floor** — the oldest *published* snapshot's
        ``wal_from_seq``, i.e. what a crash-before-publish recovery still
        replays from — is **pinned** in the WAL before the writer starts
        (ISSUE 9): any concurrent ``gc_below``, even one erroneously
        flooring at this snapshot's mid-write rotate point, is clamped
        above it until the writer publishes."""
        with self._lifecycle_lock:
            if self._closed:
                raise RuntimeError("snapshot() on a closed service")
            if self._wal is None:
                raise RuntimeError("snapshot() requires durable_dir")
            self.snapshot_join()
            sid = self._snap_id + 1
            pin = self._wal.pin(self._recovery_floor())
            from_seq = self._wal.rotate()
            arrays, meta = snap.service_state(self)
            meta["wal_from_seq"] = int(from_seq)
            meta["words"] = int(self.words)
            if background:
                t = threading.Thread(target=self._snapshot_worker,
                                     args=(sid, arrays, meta, pin),
                                     name=f"snapshot-{sid}", daemon=True)
                self._snap_thread = t
                t.start()
                return sid
        try:
            self._write_snapshot(sid, arrays, meta)
        finally:
            self._wal.unpin(pin)
        return sid

    def _recovery_floor(self) -> int:
        """Lowest ``wal_from_seq`` across published snapshot generations —
        the first WAL segment a walk-back recovery can still need. 0 when
        no generation has published yet (everything is needed)."""
        floors = []
        for s in ckpt.snapshot_steps(self._snap_dir):
            try:
                floors.append(int(ckpt.read_snapshot_meta(
                    self._snap_dir, s)["wal_from_seq"]))
            except (IOError, KeyError, ValueError):
                continue
        return min(floors) if floors else 0

    def _write_snapshot(self, sid: int, arrays, meta) -> None:
        """Persist one extracted snapshot + retention prune + WAL GC (the
        serialization half of :meth:`snapshot`; runs on the serving thread
        or the background writer — the trace span's tid shows which)."""
        with _TR.span("snapshot.write", sid=int(sid)):
            ckpt.save_array_snapshot(self._snap_dir, sid, arrays, meta,
                                     fs=self._fs, durable=True)
        self._snap_id = sid
        steps = ckpt.snapshot_steps(self._snap_dir)
        for s in steps[:-max(self.config.snapshot_keep, 1)]:
            self._fs.rmtree(self._snap_dir / f"snap_{s:08d}")
        # WAL GC floor: the oldest *retained* snapshot's from_seq (walk-back
        # restores must still find their records). A concurrent in-flight
        # snapshot's rotate point is protected by its WAL pin.
        floors = []
        for s in ckpt.snapshot_steps(self._snap_dir):
            try:
                floors.append(int(ckpt.read_snapshot_meta(
                    self._snap_dir, s)["wal_from_seq"]))
            except (IOError, KeyError, ValueError):
                continue
        if floors:
            self._wal.gc_below(min(floors))

    def _snapshot_worker(self, sid: int, arrays, meta, pin: int) -> None:
        try:
            self._write_snapshot(sid, arrays, meta)
        except BaseException as e:   # surfaced at the next join point
            self._snap_error = e
        finally:
            wal = self._wal
            if wal is not None:
                wal.unpin(pin)

    def snapshot_join(self) -> None:
        """Wait for an in-flight background snapshot (no-op otherwise) and
        re-raise any exception its writer hit."""
        t = self._snap_thread
        if t is not None:
            t.join()
            self._snap_thread = None
        if self._snap_error is not None:
            e, self._snap_error = self._snap_error, None
            raise e

    @classmethod
    def from_state(cls, arrays, meta, *, clock=time.perf_counter,
                   fs: Fs | None = None, **overrides) -> "SearchService":
        """Hydrate a service from an extracted ``(arrays, meta)`` snapshot
        state (no durable attachment — ``_wal`` stays None). This is the
        shared hydration body under :meth:`open` and the concurrent front
        end's replica construction/rehydration (``serve/replica.py``): a
        read replica is exactly a service built this way plus a replayed
        WAL tail it does not own."""
        saved = dict(meta["config"])
        # older snapshots name the retired "rows" expansion layout, which
        # returned the same answers
        saved.pop("hnsw_layout", None)
        cfg = ServiceConfig(**{**saved, **overrides})
        snap_metric = resolve_metric(meta["config"].get("metric", "tanimoto"))
        want_metric = resolve_metric(cfg.metric)
        if want_metric.spec != snap_metric.spec:
            raise ValueError(
                f"snapshot was taken under metric {snap_metric.spec!r}; "
                f"refusing to serve it as {want_metric.spec!r} — scores, "
                f"BitBound windows and HNSW graphs are metric-specific; "
                f"rebuild the index under the new metric instead")
        svc = cls.__new__(cls)
        svc.config = cfg
        svc.clock = clock
        svc.words = int(meta["words"])
        svc._fs = fs or DEFAULT_FS
        svc.engines = {}
        for name in meta["engines"]:
            svc.engines[name] = snap.engine_from_state(
                snap.split_engine_arrays(arrays, name),
                meta["engine_state"][name], **svc._engine_kwargs(name))
        svc.default_engine = meta["default_engine"]
        svc._pending = []
        svc._results = {}
        svc._next_rid = 0
        svc._wal = None
        svc._snap_id = -1
        svc._snap_thread = None
        svc._snap_error = None
        svc._lifecycle_lock = threading.RLock()
        svc._closed = False
        svc.reset_telemetry()
        return svc

    def apply_wal_records(self, records) -> int:
        """Replay ``(first_gid, rows)`` WAL records into every engine,
        skipping those already folded in (idempotent); a gid gap means lost
        segments — refuse to serve rather than drop acked data. Returns the
        number of rows applied."""
        applied = 0
        for first_gid, rows in records:
            n_now = next(iter(self.engines.values())).n_total
            if first_gid + rows.shape[0] <= n_now:
                continue
            if first_gid != n_now:
                raise IOError(f"WAL gap: record at gid {first_gid}, "
                              f"index at {n_now}")
            self._apply_insert(rows)
            applied += int(rows.shape[0])
        return applied

    @classmethod
    def open(cls, directory, *, clock=time.perf_counter,
             fs: Fs | None = None, **overrides) -> "SearchService":
        """Warm-restart a replica from a durable directory: load the latest
        intact snapshot (walking back over corrupt/partial generations),
        hydrate every engine bit-identically — sharded HNSW graphs are
        re-committed to their devices — then replay the WAL tail and reopen
        the log. ``overrides`` patch the persisted ServiceConfig (serving
        knobs like backend; data-shape knobs must match the snapshot)."""
        fs = fs or DEFAULT_FS
        base = Path(directory)
        step, arrays, meta = ckpt.load_latest_intact(base / "snapshots")
        if step is None:
            raise FileNotFoundError(f"no intact snapshot under {base}")
        svc = cls.from_state(arrays, meta, clock=clock, fs=fs, **overrides)
        svc.config.durable_dir = str(base)
        svc._snap_id = step
        svc._snap_dir = base / "snapshots"
        svc._wal_dir = base / "wal"
        # replay acknowledged inserts logged after the snapshot
        records, _ = wal_mod.replay(svc._wal_dir,
                                    from_seq=int(meta["wal_from_seq"]),
                                    words=svc.words, truncate=True, fs=fs)
        svc.apply_wal_records(records)
        svc._wal = wal_mod.WriteAheadLog(
            svc._wal_dir, svc.words, fs=fs,
            fsync_every=svc.config.wal_fsync_every)
        return svc

    def close(self) -> None:
        """Flush and close the WAL (no final snapshot — reopen replays).
        Joins any in-flight background snapshot first. Idempotent and safe
        to call from a thread other than the one running a
        ``snapshot(background=True)`` — the lifecycle lock orders it after
        the snapshot's synchronous phase, and the join waits out the
        writer before the WAL handle goes away (pinned by
        ``tests/test_service.py::test_close_*``)."""
        with self._lifecycle_lock:
            already = self._closed
            self._closed = True
        if already:
            # second close still drains a writer the first one raced with,
            # but swallows nothing new and never double-closes the WAL
            t = self._snap_thread
            if t is not None:
                t.join()
            return
        self.snapshot_join()
        with self._lifecycle_lock:
            if self._wal is not None:
                self._wal.close()
                self._wal = None

    def _set_fs(self, fs: Fs) -> None:
        """Swap the filesystem layer (crash-fault harness hook)."""
        self._fs = fs
        if self._wal is not None:
            self._wal.set_fs(fs)

    # -- telemetry ----------------------------------------------------------
    def _fold_engine_stats(self, ename: str, eng) -> None:
        """Fold the engine's per-batch ``stats`` dict into the registry —
        tiered double-buffer telemetry becomes per-engine gauges so the
        stream-stall cost is visible without scraping engine objects."""
        st = getattr(eng, "stats", None)
        if not st:
            return
        if isinstance(eng, HNSWEngine):
            self._m_hnsw_evals.inc(st.get("neighbour_evals", 0), engine=ename)
            self._m_hnsw_iters.inc(st.get("iters", 0), engine=ename)
            self._m_hnsw_max_iters.inc(st.get("max_iters_hit", 0),
                                       engine=ename)
        if st.get("residency") == "tiered":
            self._m_tier_stall.set(st.get("tiered_stall_s", 0.0),
                                   engine=ename)
            self._m_tier_chunks.set(st.get("tiered_chunks", 0), engine=ename)
            self._m_tier_stall_frac.set(st.get("tiered_stall_fraction", 0.0),
                                        engine=ename)

    @property
    def n_total(self) -> int:
        """Rows in the logical database (engines agree by construction)."""
        return int(next(iter(self.engines.values())).n_total)

    @property
    def compactions(self) -> int:
        return sum(eng.store.compactions for eng in self.engines.values()
                   if hasattr(eng, "store"))

    def compiled_pipelines(self) -> int:
        """Total compiled-executable count across engine pipeline caches —
        flat in steady state (the no-recompile acceptance criterion)."""
        total = 0
        for eng in self.engines.values():
            for fn in eng._jit_cache.values():
                size = getattr(fn, "_cache_size", None)
                total += int(size()) if callable(size) else 1
        return total

    def summary(self) -> dict:
        out = {
            "engines": {n: e.backend for n, e in self.engines.items()},
            "n_queries": int(self.n_queries),
            "n_inserts": int(self.n_inserts),
            "compactions": int(self.compactions),
            "search_time_s": round(self.search_time, 4),
            "insert_time_s": round(self.insert_time, 4),
            "qps": round(self.n_queries / self.search_time, 1)
            if self.search_time > 0 else 0.0,
            "batch_buckets": dict(self._batch_buckets),
            "scanned": {k: int(v) for k, v in self.scanned_total.items()},
        }
        # percentiles from the full-run registry histogram (exact mean,
        # log-bucket quantile estimate); the no-op registry falls back to
        # the bounded recent window. Keys are always present — a write-only
        # run reports explicit nulls, never a KeyError downstream.
        p50 = p99 = mean = None
        if self.n_queries:
            if self.metrics.enabled:
                p50 = self._m_req_lat.quantile(0.5)
                p99 = self._m_req_lat.quantile(0.99)
                mean = self._m_req_lat.mean()
            elif self.latencies_ms:
                lat = np.asarray(self.latencies_ms, dtype=np.float64)
                p50, p99 = (float(np.percentile(lat, q)) for q in (50, 99))
                mean = float(lat.mean())
        out.update(
            p50_ms=round(p50, 3) if p50 is not None else None,
            p99_ms=round(p99, 3) if p99 is not None else None,
            mean_ms=round(mean, 3) if mean is not None else None)
        return out
