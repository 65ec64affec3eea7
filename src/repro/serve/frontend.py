"""Concurrent serving tier: admission control, deadlines, load shedding,
degradation, and replica failover over the deterministic core (ISSUE 9).

:class:`SearchFrontend` is the thread-based production front end the
ROADMAP's "concurrent production front end" item calls for — the paper's
fixed-interval query pipelines (§IV) and FPScreen's pipeline-parallel
serving shape, layered *on top of* the synchronous
:class:`~repro.serve.service.SearchService` so every parity suite keeps
holding. Correctness anchor: with one replica, shedding disabled and
generous deadlines, results are **bit-identical** to the direct service
path (pinned by ``tests/test_frontend.py``).

Pieces (docs/ARCHITECTURE.md §Serving tier):

* **Bounded admission + deadlines** — :meth:`submit` is non-blocking and
  sheds with a typed :class:`Overloaded` once ``high_water`` requests are
  in flight (bounded memory under open-loop overload — the queue never
  grows without bound). Every request carries a deadline; expired requests
  are dropped *pre-dispatch* — never scored — and counted
  (``frontend_deadline_expired_total``).
* **Flush-interval micro-batching** — a dispatcher thread wakes every
  ``flush_interval_ms`` (or immediately when idle), drops expired
  requests, and hands the tick's batch to the least-loaded replica, which
  runs it through the service's pow2-bucketed micro-batcher.
* **Graceful degradation** — a declared :class:`DegradeLevel` ladder steps
  down under sustained overload (shedding observed, or in-flight depth
  above ``degrade_high`` for ``degrade_ticks`` consecutive ticks) and back
  up on recovery: smaller ``k``-rescore window (``k_scale`` — for
  BitBound, ``k`` *is* the Eq.2 window driver), smaller HNSW beam /
  ``ef_search``. The active level is exported as the
  ``frontend_degradation_level`` gauge.
* **Read replicas + failover** — N :class:`~repro.serve.replica.Replica`
  workers hydrated from one snapshot state, queries load-balanced by queue
  depth, inserts fanned to every live replica through the WAL under one
  insert lock (same order everywhere — states never diverge). A replica
  that raises, diverges on assigned gids, or wedges past
  ``health_timeout_s`` is marked dead, drained (query batches re-dispatch
  to a survivor), and re-hydrated from the latest published snapshot plus
  the WAL tail (``replica.failover`` span), with the replay window pinned
  against WAL GC.
* **Background maintenance** — snapshots (every ``snapshot_every_inserts``)
  and delta compaction (past ``compact_delta`` rows) run behind the
  dispatcher's scheduler, never on the insert/ack path; replica services
  are built with auto-compaction disabled so the deterministic core never
  compacts inside an ack.

Durability: the front end owns the WAL and snapshot directory itself
(replica services run in-memory) — the on-disk layout is exactly the
single-service one, so ``SearchService.open`` can always recover a front
end directory and vice versa.
"""
from __future__ import annotations

import math
import threading
import time
from copy import deepcopy
from dataclasses import dataclass, replace
from functools import partial
from pathlib import Path

import numpy as np

from ..checkpoint import manager as ckpt
from ..checkpoint.fs import DEFAULT_FS, Fs
from ..obs.metrics import MetricsRegistry, NULL_METRICS
from ..obs.trace import TRACER as _TR
from . import snapshot as snap
from . import wal as wal_mod
from .replica import DEAD, LIVE, Future, Replica, ReplicaDead
from .service import SearchService, ServiceConfig

#: replica services never auto-compact inside an insert ack — compaction is
#: scheduled off the hot path by the front end (FrontendConfig.compact_delta)
_NO_AUTO_COMPACT = 2 ** 31 - 1


class Overloaded(RuntimeError):
    """Typed admission rejection: ``high_water`` requests already in flight.
    Callers back off / retry; the queue never grows unboundedly."""


class DeadlineExceeded(RuntimeError):
    """The request's deadline expired before it was scored (dropped
    pre-dispatch) — the work was shed, not half-done."""


class Unavailable(RuntimeError):
    """No live replica can take the work right now."""


@dataclass(frozen=True)
class DegradeLevel:
    """One rung of the graceful-degradation ladder. Scales are applied to
    the *configured* (level-0) values, so stepping back up restores exact
    baseline quality; level 0 must be the identity."""
    name: str
    k_scale: float = 1.0       # per-request k (BitBound rescore-window driver)
    ef_scale: float = 1.0      # HNSW ef_search
    beam_scale: float = 1.0    # HNSW traversal beam

    @property
    def is_identity(self) -> bool:
        return self.k_scale == self.ef_scale == self.beam_scale == 1.0


DEFAULT_LADDER = (
    DegradeLevel("full"),
    DegradeLevel("beam-half", ef_scale=0.5, beam_scale=0.5),
    DegradeLevel("k-half", k_scale=0.5, ef_scale=0.25, beam_scale=0.25),
)


@dataclass
class FrontendConfig:
    """Concurrency knobs of the serving tier (engine knobs stay in
    :class:`~repro.serve.service.ServiceConfig`)."""
    replicas: int = 1
    high_water: int = 256            # admitted-but-uncompleted request bound
    default_deadline_ms: float | None = 1000.0   # None = no deadline
    flush_interval_ms: float = 2.0   # dispatcher micro-batch tick
    insert_timeout_s: float = 30.0   # per-replica apply ack before failover
    health_timeout_s: float = 10.0   # busy-this-long in one task == wedged
    rehydrate: bool = True           # auto-failover dead replicas
    ladder: tuple = DEFAULT_LADDER   # level 0 must be the identity
    degrade_high: float = 0.75       # depth fraction that arms step-down
    degrade_low: float = 0.25        # depth fraction that arms step-up
    degrade_ticks: int = 3           # consecutive armed ticks before a step
    snapshot_every_inserts: int = 0  # 0 = only explicit snapshot() calls
    compact_delta: int | None = None  # delta rows before scheduled
    #   compaction (None = the ServiceConfig.compact_threshold value)
    metrics: bool = True

    def __post_init__(self):
        if not self.ladder or not self.ladder[0].is_identity:
            raise ValueError("ladder[0] must be the identity (full-quality) "
                             "level")
        if self.replicas < 1:
            raise ValueError("need at least one replica")


@dataclass
class _FrontReq:
    rid: int
    queries: np.ndarray
    k: int
    engine: str
    deadline: float | None           # absolute clock() time; None = never
    t_submit: float
    future: Future = None            # type: ignore[assignment]


class SearchFrontend:
    """Admission -> micro-batch -> replica fan-out serving tier."""

    def __init__(self, db, engines=("bitbound-folding",),
                 config: ServiceConfig | None = None,
                 frontend: FrontendConfig | None = None,
                 fs: Fs | None = None, clock=time.perf_counter,
                 _services: list[SearchService] | None = None,
                 _wal_records=None, **overrides):
        cfg = config or ServiceConfig(**overrides)
        if overrides and config is not None:
            raise ValueError("pass either config= or keyword overrides")
        self.fcfg = frontend or FrontendConfig()
        self.clock = clock
        self._fs = fs or DEFAULT_FS
        # the front end owns durability; replica services run in-memory with
        # auto-compaction disabled (scheduled off the hot path instead)
        self._durable_dir = cfg.durable_dir
        self._compact_delta = (self.fcfg.compact_delta
                               if self.fcfg.compact_delta is not None
                               else cfg.compact_threshold)
        self.config = replace(cfg, durable_dir=None,
                              compact_threshold=_NO_AUTO_COMPACT)
        self.engines = tuple(engines)

        if _services is None:
            svc0 = SearchService(db, engines=self.engines,
                                 config=replace(self.config))
            services = [svc0]
            if self.fcfg.replicas > 1:
                arrays, meta = snap.service_state(svc0)
                meta = dict(meta, words=svc0.words)
                for _ in range(self.fcfg.replicas - 1):
                    services.append(SearchService.from_state(
                        {k: v.copy() for k, v in arrays.items()},
                        deepcopy(meta)))
        else:
            services = _services
        self.words = services[0].words
        self._n_total = int(services[0].n_total)

        self._init_metrics()
        self.replicas: list[Replica] = [
            self._make_replica(i, svc, generation=0)
            for i, svc in enumerate(services)]

        # request plumbing (before durability — the initial snapshot below
        # already goes through the locked snapshot path)
        self._admit_lock = threading.Lock()
        self._admit_q: list[_FrontReq] = []
        self._inflight = 0
        self._next_rid = 0
        self._next_batch = 0
        self._insert_lock = threading.Lock()
        self._snap_lock = threading.Lock()
        self._rehydrating: set[int] = set()
        self._compact_futs: list = []
        # degradation controller state
        self._level = 0
        self._hot_ticks = 0
        self._cool_ticks = 0
        self._shed_seen = 0.0
        self.max_level_engaged = 0
        self._last_maintenance_error: BaseException | None = None
        self._closed = False
        self._wake = threading.Event()
        self._stop = threading.Event()

        # durability (frontend-owned; same on-disk layout as SearchService)
        self._wal = None
        self._snap_id = -1
        self._inserts_since_snap = 0
        if self._durable_dir is not None:
            base = Path(self._durable_dir)
            self._snap_dir = base / "snapshots"
            self._wal_dir = base / "wal"
            if _services is None and (
                    ckpt.snapshot_steps(self._snap_dir)
                    or wal_mod.segment_seqs(self._wal_dir)):
                raise ValueError(
                    f"{base} already holds durable state; use "
                    f"SearchFrontend.open() to warm-restart from it")
            self._wal = wal_mod.WriteAheadLog(
                self._wal_dir, self.words, fs=self._fs,
                fsync_every=self.config.wal_fsync_every)
            if _services is None:
                self.snapshot()        # base DB recoverable before any insert

        self._dispatcher = threading.Thread(target=self._dispatch_loop,
                                            daemon=True,
                                            name="frontend-dispatch")
        self._dispatcher.start()

    # -- construction helpers ------------------------------------------------
    def _make_replica(self, index: int, svc: SearchService,
                      generation: int) -> Replica:
        # remember the level-0 engine knobs so degradation scales from (and
        # recovery restores) the configured baseline
        svc._fe_level = 0
        svc._fe_base = {}
        for name, eng in svc.engines.items():
            if name == "hnsw":
                svc._fe_base[name] = (int(eng.ef_search), int(eng.beam))
        rep = Replica(index, svc, generation=generation, clock=self.clock)
        self._m_replica_live.set(1, replica=index)
        self._m_depth.touch(replica=index)
        return rep

    @classmethod
    def open(cls, directory, *, engines=None,
             frontend: FrontendConfig | None = None, fs: Fs | None = None,
             clock=time.perf_counter, **overrides) -> "SearchFrontend":
        """Warm-restart a front end from a durable directory: latest intact
        snapshot, one WAL-tail replay (torn tail truncated), every replica
        hydrated bit-identically, fresh WAL segment opened."""
        fs = fs or DEFAULT_FS
        base = Path(directory)
        step, arrays, meta = ckpt.load_latest_intact(base / "snapshots")
        if step is None:
            raise FileNotFoundError(f"no intact snapshot under {base}")
        fcfg = frontend or FrontendConfig()
        records, _ = wal_mod.replay(base / "wal",
                                    from_seq=int(meta["wal_from_seq"]),
                                    words=int(meta["words"]), truncate=True,
                                    fs=fs)
        services = []
        for _ in range(fcfg.replicas):
            svc = SearchService.from_state(
                {k: v.copy() for k, v in arrays.items()}, deepcopy(meta),
                **overrides)
            svc.apply_wal_records(records)
            services.append(svc)
        cfg = replace(services[0].config, durable_dir=str(base))
        fe = cls(None, engines=tuple(meta["engines"]), config=cfg,
                 frontend=fcfg, fs=fs, clock=clock, _services=services)
        fe._snap_id = step
        if fcfg.compact_delta is None and "frontend_compact_delta" in meta:
            # the user-facing scheduled-compaction cadence survives the
            # replica configs' disabled auto-compaction threshold
            fe._compact_delta = int(meta["frontend_compact_delta"])
        return fe

    # -- metrics -------------------------------------------------------------
    def _init_metrics(self) -> None:
        self.metrics = (MetricsRegistry() if self.fcfg.metrics
                        else NULL_METRICS)
        m = self.metrics
        self._m_admitted = m.counter(
            "frontend_admitted_total", "requests admitted",
            labels=("engine",))
        self._m_shed = m.counter(
            "frontend_shed_total",
            "requests rejected at admission", labels=("reason",))
        self._m_expired = m.counter(
            "frontend_deadline_expired_total",
            "admitted requests dropped un-scored at deadline",
            labels=("stage",))
        self._m_inserts = m.counter(
            "frontend_inserts_total", "fingerprint rows acked")
        self._m_depth = m.gauge(
            "frontend_queue_depth", "replica worker queue depth",
            labels=("replica",))
        self._m_inflight = m.gauge(
            "frontend_inflight", "admitted-but-uncompleted requests")
        self._m_level = m.gauge(
            "frontend_degradation_level", "active degradation-ladder level")
        self._m_shifts = m.counter(
            "frontend_degradation_shifts_total", "ladder steps taken",
            labels=("direction",))
        self._m_replica_live = m.gauge(
            "frontend_replica_live", "1 = replica live, 0 = dead/rehydrating",
            labels=("replica",))
        self._m_failovers = m.counter(
            "frontend_failovers_total", "replicas declared dead")
        self._m_lat = m.histogram(
            "frontend_request_latency_ms", "submit -> completion",
            labels=("engine",))
        # pre-seed the known label sets so every family exports (and the
        # CI required-family floor holds) even on runs where an event —
        # a shed, an expiry, a failover — never fires
        for reason in ("overload", "unavailable"):
            self._m_shed.touch(reason=reason)
        for stage in ("dispatch", "worker"):
            self._m_expired.touch(stage=stage)
        for direction in ("down", "up"):
            self._m_shifts.touch(direction=direction)
        for engine in self.engines:
            self._m_admitted.touch(engine=engine)
            self._m_lat.touch(engine=engine)
        self._m_inserts.inc(0)
        self._m_failovers.inc(0)
        self._m_inflight.set(0)
        self._m_level.set(0)

    # -- read path -----------------------------------------------------------
    def submit(self, queries, k: int | None = None, engine: str | None = None,
               deadline_ms: float | None = -1.0) -> Future:
        """Admit a search request; returns a :class:`Future` redeemed by
        ``.result(timeout)``. Non-blocking: raises :class:`Overloaded`
        instead of queueing past ``high_water``. ``deadline_ms`` overrides
        the configured default (``None`` = no deadline for this request)."""
        if self._closed:
            raise RuntimeError("frontend is closed")
        engine = engine or self.engines[0]
        if engine not in self.engines:
            raise ValueError(f"engine {engine!r} not served "
                             f"(have {self.engines})")
        queries = np.atleast_2d(np.asarray(queries, dtype=np.uint32))
        if deadline_ms is not None and deadline_ms < 0:
            deadline_ms = self.fcfg.default_deadline_ms
        now = self.clock()
        with _TR.span("frontend.admit", engine=engine) as span:
            with self._admit_lock:
                if self._inflight >= self.fcfg.high_water:
                    self._m_shed.inc(reason="overload")
                    raise Overloaded(
                        f"{self._inflight} requests in flight "
                        f"(high_water {self.fcfg.high_water})")
                req = _FrontReq(
                    rid=self._next_rid, queries=queries,
                    k=int(k or self.config.k), engine=engine,
                    deadline=(now + deadline_ms / 1e3
                              if deadline_ms is not None else None),
                    t_submit=now, future=Future())
                self._next_rid += 1
                self._admit_q.append(req)
                self._inflight += 1
            span.set(rid=req.rid)
            self._m_admitted.inc(engine=engine)
        self._wake.set()
        return req.future

    def search(self, queries, k: int | None = None,
               engine: str | None = None, deadline_ms: float | None = -1.0,
               timeout: float | None = 60.0):
        """Blocking convenience path: submit + wait. With one replica, no
        shedding and no deadline pressure this is bit-identical to
        ``SearchService.search`` (the deterministic-core parity anchor)."""
        return self.submit(queries, k, engine,
                           deadline_ms=deadline_ms).result(timeout)

    def _complete(self, req: _FrontReq, result=None,
                  exc: BaseException | None = None) -> None:
        first = (req.future.set_exception(exc) if exc is not None
                 else req.future.set_result(result))
        if first:
            with self._admit_lock:
                self._inflight -= 1
            if exc is None:
                self._m_lat.observe((self.clock() - req.t_submit) * 1e3,
                                    engine=req.engine)

    # -- dispatcher ----------------------------------------------------------
    def _dispatch_loop(self) -> None:
        interval = self.fcfg.flush_interval_ms / 1e3
        while not self._stop.is_set():
            self._wake.wait(timeout=interval)
            self._wake.clear()
            self._tick()

    def _tick(self) -> None:
        with _TR.span("frontend.tick"):
            with self._admit_lock:
                reqs, self._admit_q = self._admit_q, []
            try:
                if reqs:
                    self._dispatch(reqs)
            except Exception as e:         # noqa: BLE001 — fail, don't lose
                self._abandon_batch(reqs, e)
            # maintenance faults must never kill the dispatcher (the
            # serving loop); they surface through metrics/state on the next
            # pass
            with _TR.span("frontend.maintenance"):
                for step in (self._monitor_health, self._degradation_tick,
                             self._schedule_maintenance):
                    try:
                        step()
                    except Exception as e:  # noqa: BLE001 — keep serving
                        self._last_maintenance_error = e

    def _dispatch(self, reqs: list[_FrontReq]) -> None:
        now = self.clock()
        ready = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                # never scored: shed pre-dispatch
                self._m_expired.inc(stage="dispatch")
                self._complete(r, exc=DeadlineExceeded(
                    f"request {r.rid} expired "
                    f"{(now - r.deadline) * 1e3:.1f}ms before dispatch"))
            else:
                ready.append(r)
        if not ready:
            return
        live = [rep for rep in self.replicas if rep.state == LIVE]
        if not live:
            for r in ready:
                self._m_shed.inc(reason="unavailable")
                self._complete(r, exc=Unavailable("no live replica"))
            return
        target = min(live, key=lambda rep: rep.queue_depth())
        level = self._level
        self._next_batch += 1
        # the batch's ids, on its dispatch and on the replica's run of it
        ids = (dict(batch=self._next_batch,
                    rid=" ".join(str(r.rid) for r in ready))
               if _TR.enabled else {})
        with _TR.span("frontend.dispatch", replica=target.index,
                      n_requests=len(ready), level=level, **ids):
            target.call(partial(self._run_batch, reqs=ready, level=level),
                        label="batch",
                        abandon=partial(self._abandon_batch, ready), **ids)
        for rep in self.replicas:
            self._m_depth.set(rep.queue_depth(), replica=rep.index)
        self._m_inflight.set(self._inflight)

    def _abandon_batch(self, reqs: list[_FrontReq],
                       exc: BaseException) -> None:
        for r in reqs:
            self._complete(r, exc=exc)

    def _run_batch(self, svc: SearchService, reqs: list[_FrontReq],
                   level: int):
        """Worker-side batch execution (re-bindable to any replica)."""
        self._apply_level(svc, level)
        lvl = self.fcfg.ladder[level]
        now = self.clock()
        rids = []
        for r in reqs:
            if r.future.done():            # completed elsewhere (re-dispatch)
                continue
            if r.deadline is not None and now > r.deadline:
                self._m_expired.inc(stage="worker")
                self._complete(r, exc=DeadlineExceeded(
                    f"request {r.rid} expired in queue"))
                continue
            k_eff = max(1, int(math.floor(r.k * lvl.k_scale)))
            rids.append((r, svc.submit(r.queries, k_eff, r.engine)))
        if not rids:
            return 0
        done = svc.flush()
        for r, rid in rids:
            self._complete(r, result=done[rid])
        return len(rids)

    def _apply_level(self, svc: SearchService, level: int) -> None:
        """Set the ladder level's engine knobs on a worker-owned service
        (level 0 restores the exact configured baseline)."""
        if svc._fe_level == level:
            return
        lvl = self.fcfg.ladder[level]
        for name, (ef0, beam0) in svc._fe_base.items():
            eng = svc.engines[name]
            eng.ef_search = max(1, int(math.floor(ef0 * lvl.ef_scale)))
            eng.beam = max(1, int(math.floor(beam0 * lvl.beam_scale)))
        svc._fe_level = level

    # -- write path ----------------------------------------------------------
    def insert(self, fps) -> np.ndarray:
        """Append fingerprints: WAL-fsync first (durable front end), then
        fan to every live replica's queue in one locked step — identical
        apply order everywhere. Acked once durable and applied by at least
        one live replica; a replica that misses ``insert_timeout_s`` is
        marked wedged and failed over, not waited on forever."""
        if self._closed:
            raise RuntimeError("frontend is closed")
        fps = np.atleast_2d(np.asarray(fps, dtype=np.uint32))
        if fps.shape[1] != self.words:
            raise ValueError(f"row width {fps.shape[1]} != {self.words}")
        with _TR.span("frontend.insert", rows=int(fps.shape[0])):
            with self._insert_lock:
                live = [r for r in self.replicas if r.state == LIVE]
                if not live:
                    raise Unavailable("no live replica to apply the insert")
                first_gid = self._n_total
                if self._wal is not None and fps.shape[0]:
                    self._wal.append(first_gid, fps)
                futs = [(rep, rep.call(
                    partial(self._replica_insert, rows=fps,
                            expect_gid=first_gid), label="insert"))
                    for rep in live]
                self._n_total += int(fps.shape[0])
            gids = np.arange(first_gid, first_gid + fps.shape[0],
                             dtype=np.int64)
            applied = 0
            for rep, fut in futs:
                try:
                    got = fut.result(timeout=self.fcfg.insert_timeout_s)
                    if not np.array_equal(np.asarray(got), gids):
                        raise RuntimeError(
                            f"replica {rep.index} assigned {got}, "
                            f"expected {gids}")
                    applied += 1
                except ReplicaDead:
                    continue               # already failed over
                except TimeoutError:
                    self._fail_replica(rep, RuntimeError(
                        f"insert ack missed {self.fcfg.insert_timeout_s}s"))
                except Exception as e:     # noqa: BLE001 — divergence
                    self._fail_replica(rep, e)
            if applied == 0 and self._wal is None:
                raise Unavailable("insert applied by no replica and the "
                                  "front end is not durable")
        self._inserts_since_snap += int(fps.shape[0])
        self._m_inserts.inc(fps.shape[0])
        return gids

    @staticmethod
    def _replica_insert(svc: SearchService, rows: np.ndarray,
                        expect_gid: int) -> np.ndarray:
        """Idempotent worker-side apply (safe under re-dispatch)."""
        n = svc.n_total
        if expect_gid + rows.shape[0] <= n:
            return np.arange(expect_gid, expect_gid + rows.shape[0],
                             dtype=np.int64)
        if expect_gid != n:
            raise RuntimeError(f"replica at {n} rows cannot apply insert "
                               f"at gid {expect_gid} (gap)")
        return svc.insert(rows)

    # -- health + failover ---------------------------------------------------
    def _monitor_health(self) -> None:
        now = self.clock()
        for rep in list(self.replicas):
            if (rep.state == LIVE
                    and rep.busy_for(now) > self.fcfg.health_timeout_s):
                self._fail_replica(rep, RuntimeError(
                    f"wedged for {rep.busy_for(now):.1f}s"))
            elif rep.state == DEAD:
                self._note_dead(rep)
                if (self.fcfg.rehydrate
                        and rep.index not in self._rehydrating):
                    self._rehydrating.add(rep.index)
                    threading.Thread(
                        target=self._rehydrate_slot, args=(rep.index,),
                        daemon=True,
                        name=f"rehydrate-{rep.index}").start()

    def kill_replica(self, index: int) -> None:
        """Operational / test hook: declare replica ``index`` failed now."""
        self._fail_replica(self.replicas[index],
                           RuntimeError("killed by operator"))

    def _fail_replica(self, rep: Replica, error: BaseException) -> None:
        if rep.state != LIVE:
            return
        rep.mark_dead(error)
        self._note_dead(rep)

    def _note_dead(self, rep: Replica) -> None:
        if getattr(rep, "_fe_noted", False):
            return
        rep._fe_noted = True
        self._m_failovers.inc()
        self._m_replica_live.set(0, replica=rep.index)
        survivors = [r for r in self.replicas
                     if r is not rep and r.state == LIVE]
        for task in rep.drain():
            if task.label == "batch" and survivors:
                min(survivors, key=lambda r: r.queue_depth()).put(task)
            else:
                # inserts already fan to every replica; extraction /
                # compaction are retried by their schedulers
                task.fail(ReplicaDead(
                    f"replica {rep.index} died ({rep.error})"))
        self._wake.set()

    def _rehydrate_slot(self, index: int) -> None:
        """Failover: rebuild a dead slot from the latest published snapshot
        + WAL tail (durable) or a survivor's extracted state, then atomically
        attach it under the insert lock so it has missed nothing."""
        try:
            with _TR.span("replica.failover", replica=index):
                old = self.replicas[index]
                generation = old.generation + 1
                if self._wal is not None:
                    pin = self._wal.pin(0)     # freeze GC during catch-up
                    try:
                        step, arrays, meta = ckpt.load_latest_intact(
                            self._snap_dir)
                        if step is None:
                            raise IOError("no intact snapshot to rehydrate "
                                          "from")
                        svc = SearchService.from_state(arrays, deepcopy(meta))
                        from_seq = int(meta["wal_from_seq"])
                        # bulk catch-up without blocking writers, then a
                        # short locked pass for the final tail
                        self._wal.flush()
                        records, _ = wal_mod.replay(
                            self._wal_dir, from_seq=from_seq,
                            words=self.words, truncate=False)
                        svc.apply_wal_records(records)
                        with self._insert_lock:
                            self._wal.flush()
                            records, _ = wal_mod.replay(
                                self._wal_dir, from_seq=from_seq,
                                words=self.words, truncate=False)
                            svc.apply_wal_records(records)
                            self.replicas[index] = self._make_replica(
                                index, svc, generation)
                    finally:
                        self._wal.unpin(pin)
                else:
                    with self._insert_lock:
                        donor = next((r for r in self.replicas
                                      if r.state == LIVE), None)
                        if donor is None:
                            raise Unavailable("no donor replica")
                        arrays, meta = donor.call(
                            snap.service_state,
                            label="extract").result(timeout=60.0)
                        meta = dict(meta, words=self.words)
                        svc = SearchService.from_state(
                            {k: v.copy() for k, v in arrays.items()},
                            deepcopy(meta))
                        self.replicas[index] = self._make_replica(
                            index, svc, generation)
        finally:
            self._rehydrating.discard(index)
            self._wake.set()

    # -- degradation controller ----------------------------------------------
    def _degradation_tick(self) -> None:
        fcfg = self.fcfg
        if len(fcfg.ladder) < 2:
            return
        with self._admit_lock:
            depth_frac = self._inflight / max(fcfg.high_water, 1)
        shed_now = self._m_shed.total()
        shed_delta = shed_now - self._shed_seen
        self._shed_seen = shed_now
        if shed_delta > 0 or depth_frac >= fcfg.degrade_high:
            self._hot_ticks += 1
            self._cool_ticks = 0
        elif depth_frac <= fcfg.degrade_low:
            self._cool_ticks += 1
            self._hot_ticks = 0
        else:
            self._hot_ticks = 0
            self._cool_ticks = 0
        if (self._hot_ticks >= fcfg.degrade_ticks
                and self._level < len(fcfg.ladder) - 1):
            self._level += 1
            self._hot_ticks = 0
            self.max_level_engaged = max(self.max_level_engaged, self._level)
            self._m_shifts.inc(direction="down")
        elif self._cool_ticks >= fcfg.degrade_ticks and self._level > 0:
            self._level -= 1
            self._cool_ticks = 0
            self._m_shifts.inc(direction="up")
        self._m_level.set(self._level)

    @property
    def degradation_level(self) -> int:
        return self._level

    # -- maintenance scheduler ----------------------------------------------
    def _schedule_maintenance(self) -> None:
        # background snapshot cadence
        if (self._wal is not None and self.fcfg.snapshot_every_inserts
                and self._inserts_since_snap
                >= self.fcfg.snapshot_every_inserts
                and self._snap_lock.acquire(blocking=False)):
            self._inserts_since_snap = 0
            threading.Thread(target=self._snapshot_locked, daemon=True,
                             name="frontend-snapshot").start()
        # scheduled compaction, off the insert/ack path
        if self._compact_futs:
            if any(not f.done() for f in self._compact_futs):
                return
            self._compact_futs = []
        rep0 = next((r for r in self.replicas if r.state == LIVE), None)
        if rep0 is None:
            return
        delta = max((eng.store.n_delta
                     for eng in rep0.svc.engines.values()
                     if hasattr(eng, "store")), default=0)
        if delta >= max(self._compact_delta, 1):
            # enqueued under the insert lock so compaction lands at the same
            # queue position (relative to inserts) on every replica — states
            # stay byte-aligned, not just logically equal
            with self._insert_lock:
                self._compact_futs = [
                    rep.call(lambda svc: svc.compact_all(), label="compact")
                    for rep in self.replicas if rep.state == LIVE]

    def _snapshot_locked(self) -> None:
        try:
            self._snapshot_once()
        finally:
            self._snap_lock.release()

    def snapshot(self) -> int:
        """Write one snapshot generation now (synchronous; the scheduler
        path runs the same body on a background thread)."""
        with self._snap_lock:
            return self._snapshot_once()

    def _snapshot_once(self) -> int:
        if self._wal is None:
            raise RuntimeError("snapshot() requires a durable front end")
        floors = self._published_floors()
        with self._insert_lock:
            donor = next((r for r in self.replicas if r.state == LIVE), None)
            if donor is None:
                raise Unavailable("no live replica to extract from")
            # pin the *recovery* floor (oldest published snapshot), not the
            # mid-write rotate point: crash-before-publish recovery replays
            # from there and a concurrent GC must not outrun it
            pin = self._wal.pin(min(floors) if floors else 0)
            from_seq = self._wal.rotate()
            fut = donor.call(snap.service_state, label="extract")
        try:
            arrays, meta = fut.result(timeout=600.0)
            meta = dict(meta, wal_from_seq=int(from_seq),
                        words=int(self.words),
                        frontend_compact_delta=int(self._compact_delta))
            sid = self._snap_id + 1
            with _TR.span("snapshot.write", sid=sid):
                ckpt.save_array_snapshot(self._snap_dir, sid, arrays, meta,
                                         fs=self._fs, durable=True)
            self._snap_id = sid
            steps = ckpt.snapshot_steps(self._snap_dir)
            keep = max(self.config.snapshot_keep, 1)
            for s in steps[:-keep]:
                self._fs.rmtree(self._snap_dir / f"snap_{s:08d}")
            floors = self._published_floors()
            if floors:
                self._wal.gc_below(min(floors))   # pin-clamped
            return sid
        finally:
            self._wal.unpin(pin)

    def _published_floors(self) -> list[int]:
        floors = []
        for s in ckpt.snapshot_steps(self._snap_dir):
            try:
                floors.append(int(ckpt.read_snapshot_meta(
                    self._snap_dir, s)["wal_from_seq"]))
            except (IOError, KeyError, ValueError):
                continue
        return floors

    # -- introspection -------------------------------------------------------
    @property
    def n_total(self) -> int:
        return self._n_total

    @property
    def shed_count(self) -> int:
        return int(self.metrics.family("frontend_shed_total").total()
                   if self.metrics.enabled else 0)

    @property
    def expired_count(self) -> int:
        fam = self.metrics.family("frontend_deadline_expired_total")
        return int(fam.total()) if fam is not None else 0

    def live_replicas(self) -> int:
        return sum(1 for r in self.replicas if r.state == LIVE)

    def drain(self, timeout: float = 60.0) -> None:
        """Wait until every admitted request has completed (test/benchmark
        barrier; does not block new submissions)."""
        t0 = self.clock()
        while True:
            with self._admit_lock:
                if self._inflight == 0:
                    return
            if self.clock() - t0 > timeout:
                raise TimeoutError(f"{self._inflight} requests still in "
                                   f"flight after {timeout}s")
            self._wake.set()
            time.sleep(0.001)

    def replica_state(self, index: int, *, compact: bool = True,
                      timeout: float = 120.0):
        """Extract one replica's full service state through its worker (the
        byte-parity probe). ``compact=True`` folds the delta first so two
        replicas with different *maintenance* schedules but the same
        logical database extract identical bytes."""
        rep = self.replicas[index]

        def _extract(svc):
            if compact:
                svc.compact_all()
            return snap.service_state(svc)

        return rep.call(_extract, label="extract").result(timeout=timeout)

    def summary(self) -> dict:
        fam = self.metrics.family("frontend_request_latency_ms")
        p50 = fam.quantile(0.5) if fam is not None else None
        p99 = fam.quantile(0.99) if fam is not None else None
        n_done = fam.count() if fam is not None else 0
        return {
            "replicas": len(self.replicas),
            "replicas_live": self.live_replicas(),
            "n_completed": int(n_done),
            "n_total_rows": int(self._n_total),
            "shed": self.shed_count,
            "expired": self.expired_count,
            "failovers": int(self.metrics.family(
                "frontend_failovers_total").total()
                if self.metrics.enabled else 0),
            "degradation_level": self._level,
            "max_degradation_level": self.max_level_engaged,
            "p50_ms": round(float(p50), 3) if p50 is not None else None,
            "p99_ms": round(float(p99), 3) if p99 is not None else None,
        }

    def export_metrics(self, path, ts: float | None = None) -> int:
        """One JSONL export covering the front-end registry plus every
        replica's service registry (rows labeled ``replica=<i>``), with a
        Prometheus text twin at ``<path>.prom``."""
        import json
        rows = self.metrics.collect()
        for rep in self.replicas:
            for row in rep.svc.metrics.collect():
                row["labels"]["replica"] = str(rep.index)
                rows.append(row)
        if ts is not None:
            for r in rows:
                r["ts"] = ts
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        with open(path, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
        with open(str(path) + ".prom", "w") as f:
            f.write(self.metrics.render_prometheus())
            for rep in self.replicas:
                f.write(rep.svc.metrics.render_prometheus())
        return len(rows)

    # -- lifecycle -----------------------------------------------------------
    def close(self) -> None:
        """Stop the dispatcher, drain workers, close the WAL. Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        self._wake.set()
        self._dispatcher.join(timeout=10.0)
        with self._admit_lock:
            reqs, self._admit_q = self._admit_q, []
        for r in reqs:
            self._complete(r, exc=Unavailable("frontend closed"))
        for rep in self.replicas:
            rep.stop()
        with self._snap_lock:
            pass                           # wait out an in-flight snapshot
        if self._wal is not None:
            self._wal.close()
            self._wal = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
