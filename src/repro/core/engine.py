"""Unified similarity-search engines (paper §IV).

Three engines, one per paper design point, on one shared base:

* :class:`BruteForceEngine` — exhaustive linear scan with the fused
  scan+top-k path (on-the-fly engine; the Pallas kernel on the ``tpu``
  backend, a streaming jnp scan on ``jnp``).
* :class:`BitBoundFoldingEngine` — exhaustive with Eq.2 popcount pruning and
  2-stage modulo-OR folding; host-side numpy reference plus a fully
  device-resident ``search_tpu`` path.
* :class:`HNSWEngine` — approximate graph search over the device-resident
  batched traversal engine (``core/hnsw.py``).

The ``backend=`` contract (shared, :class:`SearchEngine`)
---------------------------------------------------------
Every engine exposes ``search(queries, k) -> (ids, sims)`` numpy arrays and a
``backend`` selector naming the execution path:

* ``"numpy"`` — host-side reference loop with true variable-length data
  structures. Exact semantics; the parity oracle for the device paths.
  (Engines whose reference *is* the device path don't offer it.)
* ``"jnp"``   — fully device-resident fixed-shape path built from plain jnp
  ops (works on any JAX backend, no Pallas required).
* ``"tpu"``   — same device-resident path with its hot stage swapped for the
  Pallas kernel: compiled through Mosaic on a TPU, run in the Pallas
  interpreter when CPU was asked for (``JAX_PLATFORMS=cpu``, how the tests
  run), refused otherwise (``kernels/ops.py::interpret_on``). A kernel
  that fails to import or lower raises; nothing falls back.

Invalid names raise ``ValueError`` listing the engine's supported backends.
The legacy ``use_kernel=True`` flag maps onto ``backend="tpu"`` when
``backend`` is unset.

Online inserts (the serving write path)
---------------------------------------
``insert(fps) -> global ids`` appends fingerprints while the engine keeps
serving. Brute/BitBound engines are backed by a
:class:`repro.serve.store.MutableFingerprintStore` (immutable popcount-sorted
main segment + append-only delta, LSM-style compaction): searches scan
main + delta and rank-merge the two result runs with
``core/topk.merge_sorted``; the delta is padded to power-of-two buckets so
compiled pipelines are reused as it grows. :class:`HNSWEngine` routes inserts
through :func:`repro.core.hnsw.insert_hnsw` (batched incremental graph
construction). The contract — pinned by ``tests/test_insert_parity.py`` — is
that after any interleaving of inserts and searches (including across a
compaction) results are bit-identical to a from-scratch engine built on the
concatenated database.

Work accounting: ``scanned(n_queries)`` is the number of candidate
fingerprints the engine scores for ``n_queries`` queries, extrapolated from
the *most recent* ``search`` batch: ``last_batch_total * n_queries /
last_batch_n_queries``. Before any search it is 0 for data-dependent
engines; engines whose per-query work is input-independent compute it in
closed form. Per-batch traversal telemetry beyond that single number lives
in the engine's ``stats`` dict (see :attr:`HNSWEngine.stats`).

Spans (``obs/trace.py``): the exhaustive engines split a device-path
batch into ``engine.plan`` (host work before the launch), ``engine.dispatch``
(the launch of the jitted pipeline, which returns before the device is
done) and ``engine.fetch`` (every device->host read of the batch: ids,
scores and the ``scanned`` count, in one ``jax.device_get``);
``engine.sync`` is the store->device refresh after inserts or a
compaction. No span waits on the device.

The engine x backend matrix is summarised in README.md; data
layouts and the request path are documented in docs/ARCHITECTURE.md.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from . import bitbound as bb
from . import folding as fl
from . import hnsw as hn
from ..obs.trace import TRACER as _TR
from .distributed import merge_shard_topk, shard_devices
from .fingerprints import (Metric, TANIMOTO, batched_metric_scores,
                           metric_from_counts, metric_from_counts_np,
                           metric_scores, popcount, resolve_metric)
from .topk import merge_sorted, streaming_topk


def _store_mod():
    # Lazy: core must stay importable without triggering the serve package
    # at module-import time (serve imports core back).
    from ..serve import store
    return store


@functools.partial(jax.jit, static_argnames=("metric",))
def _gather_score_frontier(q, db_lines, ids, metric: Metric = TANIMOTO):
    """Jitted single-query gather-distance launch for the insert-frontier
    scorer. Module-level so the compile cache is keyed purely on shapes
    (plus the static metric): with the capacity-stable cached device lines,
    repeated frontier widths across insert batches (and engines) replay
    compiled launches instead of re-tracing the Pallas call per frontier."""
    from ..kernels import ops as kops
    return kops.gather_tanimoto(q[None], db_lines, ids[None],
                                metric=metric)[0]


@jax.jit
def _merge_main_delta(s_a, i_a, s_b, i_b, n_main):
    """Rank-merge the main-segment and delta (scores, ids) runs, keeping the
    best ``k = s_a.shape[1]`` per row. Ties keep run A (the main segment)
    ahead — the same order a single stable scan over main⊕delta produces.

    Main-run entries pointing at capacity-pad rows (``id >= n_main``) are
    masked out first: their sim-0 entries would otherwise win cross-run ties
    against real sim-0 delta rows (within the main run they always lose
    index ties, but ``merge_sorted`` puts run A first on ties)."""
    pad = i_a >= n_main
    s_a = jnp.where(pad, -jnp.inf, s_a)
    i_a = jnp.where(pad, -1, i_a)
    return jax.vmap(merge_sorted)(s_a, i_a, s_b, i_b)


class SearchEngine:
    """Shared engine plumbing: backend selection, compiled-function caching
    and the ``scanned`` work-counter contract (module docstring).

    Subclasses declare ``BACKENDS`` / ``DEFAULT_BACKEND`` and call
    :meth:`_init_engine` from ``__post_init__``; per-batch work is recorded
    with :meth:`_record_batch` and jitted pipelines are memoised per static
    key with :meth:`_cached`. Online writes go through :meth:`insert`; each
    engine implements :meth:`_apply_insert`.
    """

    BACKENDS: tuple = ("jnp", "tpu")
    DEFAULT_BACKEND: str = "jnp"
    #: residency modes the engine supports; the exhaustive engines extend
    #: this with "tiered" (full-resolution rows stay in host memory and are
    #: streamed into a double-buffered HBM staging window — ISSUE 7)
    RESIDENCIES: tuple = ("device",)

    def _init_engine(self) -> None:
        if self.backend is None:
            self.backend = ("tpu" if getattr(self, "use_kernel", False)
                            else self.DEFAULT_BACKEND)
        if self.backend not in self.BACKENDS:
            raise ValueError(
                f"{type(self).__name__} backend must be one of "
                f"{'/'.join(repr(b) for b in self.BACKENDS)}, "
                f"got {self.backend!r}")
        self._last_scanned = 0
        self._last_n_queries = 0
        self._last_tiles = (0, 0)
        self._jit_cache: dict = {}
        self.stats: dict = {}

    def _resolve_metric_width(self, words: int) -> None:
        """Resolve the ``metric`` spec (None / name / Metric) and pin the
        engine's fingerprint width. ``fp_bits=None`` infers the width from
        the data; an explicit value must match the packed word count —
        metric and width are per-engine trace-time constants, so every
        compiled pipeline downstream is keyed by construction."""
        self.metric = resolve_metric(self.metric)
        words = int(words)
        if self.fp_bits is None:
            self.fp_bits = words * 32
        elif int(self.fp_bits) != words * 32:
            raise ValueError(
                f"fp_bits={self.fp_bits} does not match the database width "
                f"({words} words = {words * 32} bits)")

    def _resolve_residency(self) -> None:
        """Resolve the ``residency`` field after the store exists: ``None``
        inherits the store's policy (a :class:`TieredFingerprintStore`
        defaults the engine to "tiered"), then validate."""
        if getattr(self, "residency", None) is None:
            self.residency = getattr(self.store, "residency", "device")
        if self.residency not in self.RESIDENCIES:
            raise ValueError(
                f"{type(self).__name__} residency must be one of "
                f"{'/'.join(repr(r) for r in self.RESIDENCIES)}, "
                f"got {self.residency!r}")

    def _cached(self, key, builder):
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = builder()
            self._jit_cache[key] = fn
        return fn

    def _record_batch(self, scanned, n_queries) -> None:
        self._last_scanned = int(scanned)
        self._last_n_queries = int(n_queries)

    def scanned(self, n_queries: int) -> int:
        """Candidates scored for ``n_queries`` queries, extrapolated from the
        most recent search batch (0 before any search)."""
        if self._last_n_queries == 0:
            return 0
        return round(self._last_scanned * n_queries / self._last_n_queries)

    def stage1_tiles(self) -> tuple[int, int]:
        """Row-window kernel tiles of the most recent device batch:
        ``(window, streamed)``, the sum of the per-query window tiles and
        that of the query-block union tiles the kernel's grid scans.
        ``(0, 0)`` for engines and backends without the kernel."""
        return self._last_tiles

    @property
    def n_total(self) -> int:
        """Fingerprints currently searchable (base + online inserts)."""
        raise NotImplementedError

    def search(self, queries, k: int):
        raise NotImplementedError

    def insert(self, fps) -> np.ndarray:
        """Append fingerprints online; returns their global ids (monotone,
        stable across compactions). Results after an insert are identical to
        a from-scratch engine on the concatenated database. Mis-dtyped rows
        (floats, signed ints) raise ``ValueError`` up front instead of being
        silently reinterpreted as uint32."""
        fps = _store_mod().validate_rows(fps)
        if fps.shape[0] == 0:
            return np.empty((0,), dtype=np.int64)
        return self._apply_insert(fps)

    def _apply_insert(self, fps: np.ndarray) -> np.ndarray:
        raise NotImplementedError(
            f"{type(self).__name__} does not support online insert()")


def _brute_topk(queries: jax.Array, db: jax.Array, db_cnt: jax.Array, k: int,
                use_kernel: bool, tile: int = 2048,
                metric: Metric = TANIMOTO):
    if use_kernel:
        from ..kernels import ops as kops
        return kops.tanimoto_topk(queries, db, k=k, db_popcount=db_cnt,
                                  metric=metric)

    def one(q):
        return streaming_topk(metric_scores(q, db, metric, db_cnt), k,
                              tile=tile)

    vals, idxs = jax.vmap(one)(queries)
    return idxs, vals


@dataclass
class BruteForceEngine(SearchEngine):
    """Exhaustive scan. ``backend``: ``"tpu"`` = fused Pallas kernel
    (the Pallas interpreter on CPU), ``"jnp"`` = streaming jnp path.

    Online inserts append to the store's delta segment; a search scans the
    (capacity-padded, global-id-ordered) main segment with the compiled
    pipeline, scans the power-of-two-padded delta with a bucketed jnp
    pipeline, and rank-merges the two top-k runs (main capacity-pad entries
    masked to -1 first — see :func:`_merge_main_delta`), so results match a
    from-scratch scan exactly for ``k <= n_total``.

    ``residency`` (ISSUE 7): ``"device"`` keeps the whole main segment in
    HBM (the default); ``"tiered"`` keeps it in host memory and streams
    ``tier_chunk_rows``-row chunks through a double-buffered HBM staging
    window — each chunk is scanned with the same fused top-k primitive and
    rank-merged into the running result via :func:`core.topk.merge_sorted`
    (ties keep the earlier chunk, reproducing the full scan's
    ascending-index tie order bit-for-bit). ``None`` inherits the store's
    policy. Double-buffer telemetry (chunks, bytes streamed, stall seconds /
    fraction) lands in :attr:`stats` after each search.
    """
    db: jax.Array
    use_kernel: bool = False
    backend: str | None = None
    compact_threshold: int = 4096
    #: prebuilt store (durability warm restart) — skips the store build;
    #: ``db`` is ignored when set
    store: object = None
    residency: str | None = None
    #: rows per streamed chunk in tiered mode (rounded to a power of two so
    #: chunks tile the power-of-two capacity exactly)
    tier_chunk_rows: int = 65536
    #: similarity metric (None / name / spec string / Metric descriptor);
    #: trace-time constant — each (metric, shape) pair compiles once
    metric: Metric | str | None = None
    #: fingerprint width in bits; None infers from the data, an explicit
    #: value is validated against the packed word count
    fp_bits: int | None = None

    BACKENDS = ("jnp", "tpu")
    DEFAULT_BACKEND = "jnp"
    RESIDENCIES = ("device", "tiered")

    def __post_init__(self):
        self._init_engine()
        self.use_kernel = self.backend == "tpu"
        if self.store is None:
            self.store = _store_mod().MutableFingerprintStore(
                np.asarray(self.db), sorted_main=False, fold_m=1,
                compact_threshold=self.compact_threshold)
        else:
            if self.store.sorted_main or self.store.fold_m != 1:
                raise ValueError("restored store layout does not match "
                                 "a brute-force engine")
            self.compact_threshold = self.store.compact_threshold
        self._resolve_metric_width(self.store.words)
        self._resolve_residency()
        self._sync_gen = None
        self._sync_delta = None
        self._delta_dev = None
        self._sync()

    @property
    def n_total(self) -> int:
        return self.store.n_total

    def _sync(self) -> None:
        st = self.store
        if (self._sync_gen == st.generation
                and self._sync_delta == st.delta_version):
            return
        with _TR.span("engine.sync", n_delta=int(st.n_delta)):
            self._refresh(st)

    def _refresh(self, st) -> None:
        if self._sync_gen != st.generation:
            self._sync_gen = st.generation
            if self.residency == "tiered":
                # full-resolution rows stay on the host; searches stream
                # them chunk-wise through _tiered_main_topk
                self.db = None
                self.db_cnt = None
                self._db_np = st.main.db
            else:
                self.db = jnp.asarray(st.main.db)      # (capacity, W)
                self.db_cnt = popcount(self.db)        # pad rows -> 0
        if self._sync_delta != st.delta_version:
            self._sync_delta = st.delta_version
            if st.n_delta == 0:
                self._delta_dev = None
            else:
                bucket = _store_mod().next_pow2(st.n_delta)
                d = np.zeros((bucket, st.words), dtype=np.uint32)
                d[:st.n_delta] = st.delta_db
                d = jnp.asarray(d)
                self._delta_dev = (d, popcount(d), bucket)

    def _main_builder(self, k: int):
        use_kernel = self.use_kernel
        metric = self.metric

        def build():
            return jax.jit(
                lambda q, db, db_cnt: _brute_topk(q, db, db_cnt, k,
                                                  use_kernel, metric=metric))
        return build

    def _delta_builder(self, k: int, bucket: int):
        metric = self.metric

        def build():
            dk = min(k, bucket)

            def run(q, ddb, dcnt, n_delta):
                s = batched_metric_scores(q, ddb, metric, dcnt)
                slot = jnp.arange(bucket)[None, :]
                s = jnp.where(slot < n_delta, s, -jnp.inf)
                vals, slots = jax.lax.top_k(s, dk)
                ids = jnp.where(jnp.isfinite(vals), slots, -1)
                if dk < k:
                    pad = ((0, 0), (0, k - dk))
                    vals = jnp.pad(vals, pad, constant_values=-jnp.inf)
                    ids = jnp.pad(ids, pad, constant_values=-1)
                return ids, vals
            return jax.jit(run)
        return build

    def _tier_scan_builder(self, k: int, rows_n: int):
        """Per-chunk fused scan: popcount + top-k over one streamed chunk.
        Same primitive as the device-resident path, so per-row scores are
        bit-identical."""
        use_kernel = self.use_kernel
        metric = self.metric

        def build():
            dk = min(k, rows_n)

            def run(q, rows):
                return _brute_topk(q, rows, popcount(rows), dk, use_kernel,
                                   metric=metric)
            return jax.jit(run)
        return build

    def _tier_merge_builder(self, k: int, rows_n: int):
        """Fold one chunk's (ids, vals) into the running top-k. The running
        run always holds earlier (lower-id) chunks, and ``merge_sorted``
        keeps run A ahead on ties — together with the in-chunk top-k's
        lowest-index tie rule this reproduces the full scan's global
        ascending-index tie order exactly."""
        def build():
            dk = min(k, rows_n)

            def run(run_vals, run_ids, vals_c, ids_c, base):
                gids = jnp.where(ids_c >= 0, ids_c.astype(jnp.int32) + base,
                                 -1)
                if dk < k:
                    pad = ((0, 0), (0, k - dk))
                    vals_c = jnp.pad(vals_c, pad, constant_values=-jnp.inf)
                    gids = jnp.pad(gids, pad, constant_values=-1)
                return jax.vmap(merge_sorted)(run_vals, run_ids, vals_c, gids)
            return jax.jit(run)
        return build

    def _tiered_main_topk(self, q, k: int):
        """Stream the host-resident main segment through a double-buffered
        HBM staging window: ``jax.device_put`` of chunk i+1 is dispatched
        before the scan of chunk i, so under JAX async dispatch the host→HBM
        transfer overlaps the previous chunk's compute. The stall time —
        waiting on a transfer that compute overtook — is measured per chunk
        and reported in :attr:`stats`."""
        sm = _store_mod()
        cap = self.store.main.capacity
        r = min(sm.next_pow2(max(self.tier_chunk_rows, 1)), cap)
        n_chunks = cap // r
        db_np = self._db_np
        sfn = self._cached(("tier_scan", int(k), r),
                           self._tier_scan_builder(k, r))
        mfn = self._cached(("tier_merge", int(k), r),
                           self._tier_merge_builder(k, r))
        nq = q.shape[0]
        run_vals = jnp.full((nq, k), -jnp.inf, jnp.float32)
        run_ids = jnp.full((nq, k), -1, jnp.int32)
        # spans: each host->HBM transfer is a "tier.device_put" span from
        # its dispatch to the moment the scan needs it; chunk i+1's opens
        # before chunk i's scan is launched. The scan's device time is in
        # the device trace; its span times the launch.
        t0 = time.perf_counter()
        stall = 0.0
        put_h = _TR.begin("tier.device_put", chunk=0)
        staged = jax.device_put(db_np[:r])
        for c in range(n_chunks):
            cur, cur_h = staged, put_h
            if c + 1 < n_chunks:
                put_h = _TR.begin("tier.device_put", chunk=c + 1)
                staged = jax.device_put(db_np[(c + 1) * r:(c + 2) * r])
            with _TR.span("tier.stall", chunk=c):
                ts = time.perf_counter()
                jax.block_until_ready(cur)
                te = time.perf_counter()
            stall += te - ts
            cur_h.end()
            with _TR.span("tier.scan_chunk", chunk=c, rows=r):
                ids_c, vals_c = sfn(q, cur)
                run_vals, run_ids = mfn(run_vals, run_ids, vals_c, ids_c,
                                        jnp.int32(c * r))
        jax.block_until_ready(run_vals)
        total = time.perf_counter() - t0
        self.stats.update(
            residency="tiered", tiered_chunks=n_chunks, tiered_chunk_rows=r,
            tiered_streamed_bytes=int(n_chunks) * r * db_np.shape[1] * 4,
            tiered_stall_s=stall, tiered_scan_s=total,
            tiered_stall_fraction=(stall / total) if total > 0 else 0.0)
        return run_ids, run_vals

    def device_pipeline(self, queries, k: int):
        """The jitted main-segment scan (device residency) for this batch
        and its arguments: ``fn(*args) -> (ids, vals)`` device arrays."""
        self._sync()
        fn = self._cached(("main", int(k), self.db.shape[0]),
                          self._main_builder(k))
        return fn, (jnp.asarray(queries), self.db, self.db_cnt)

    def search(self, queries, k: int):
        with _TR.span("engine.plan"):
            self._sync()
            q = jnp.asarray(queries)
        dd = self._delta_dev
        with _TR.span("engine.dispatch", delta_bucket=dd[2] if dd else 0):
            if self.residency == "tiered":
                ids, vals = self._tiered_main_topk(q, k)
            else:
                fn, args = self.device_pipeline(q, k)
                ids, vals = fn(*args)
            if dd is not None:
                ddb, dcnt, bucket = dd
                dfn = self._cached(("delta", int(k), bucket),
                                   self._delta_builder(k, bucket))
                dids, dvals = dfn(q, ddb, dcnt,
                                  jnp.int32(self.store.n_delta))
                gids = jnp.where(dids >= 0,
                                 dids + jnp.int32(self.store.n_main), -1)
                vals, ids = _merge_main_delta(vals, ids.astype(jnp.int32),
                                              dvals, gids.astype(jnp.int32),
                                              jnp.int32(self.store.n_main))
        with _TR.span("engine.fetch"):
            return jax.device_get((ids, vals))

    def _apply_insert(self, fps):
        return self.store.insert(fps)   # compaction handled by the store

    def scanned(self, n_queries: int) -> int:
        # per-query work is the whole DB regardless of the query batch
        return n_queries * self.store.n_total


@dataclass
class BitBoundFoldingEngine(SearchEngine):
    """BitBound (Eq. 2) + 2-stage folding (paper §III-B, §IV-A).

    Stage 1 scans only the popcount-bounded range of the *folded* DB and keeps
    ``k_r1 = k*m*log2(2m)`` candidates; stage 2 rescores them at full
    resolution. ``cutoff`` is the similarity cutoff Sc; ``m=1`` disables
    folding (pure BitBound).

    The fingerprints live in a :class:`~repro.serve.store.MutableFingerprintStore`
    (popcount-sorted capacity-padded main segment + append-only delta).
    Searches scan the main segment through the Eq.2 window machinery and the
    delta through a popcount mask computed with the *same* float64 bounds;
    candidates from both segments are merged **in the global popcount-sorted
    order a from-scratch rebuild would produce** (stable ties: ascending
    (popcount, global id)), so results are bit-identical to a rebuilt engine
    at every interleaving of inserts and searches.

    Two execution paths share the store:

    * ``search_numpy`` — host-side reference with true variable-length Eq.2
      ranges (one python loop per query). Exact semantics, used as the parity
      oracle and for algorithmic speedup measurements.
    * ``search_tpu`` — device-resident fixed-shape path: stage 1 runs the
      scalar-prefetched row-window Pallas kernel over each query's Eq.2 tile
      window of the folded main segment (``kernels.ops.window_topk``) plus a
      masked jnp scan of the folded delta; the merged stage-1 candidate set
      (rank = virtual position in the merged sorted array, via two
      searchsorteds) is rescored at full resolution with a fused top-k — one
      jitted function per ``(window bucket, k, delta bucket, capacity)``, no
      host round-trips, returning ``(ids, sims, scanned)`` device arrays.
      With ``backend="jnp"`` stage 1 is a masked jnp scan with identical
      results.

    ``backend`` selects what :meth:`search` runs: ``"numpy"`` (default,
    reference), ``"tpu"`` (Pallas device path) or ``"jnp"`` (device path
    without Pallas).

    ``residency`` (ISSUE 7) selects where the *full-resolution* main segment
    lives for the device paths:

    * ``"device"`` — everything in HBM (the default).
    * ``"tiered"`` — only the folded stage-1 arrays plus the 4 B/row count
      and order vectors stay in HBM (``(4*W/m + 8)`` bytes/row instead of
      ``4*W*(1 + 1/m) + 8``); the full-resolution rows stay on the host
      (optionally memmapped — :class:`~repro.serve.store.TieredFingerprintStore`).
      Stage 1 and the rebuilt-order candidate merge run on device exactly as
      before, but instead of gathering rescore rows from an HBM-resident
      array, the candidate metadata returns to the host, which gathers the
      BitBound-bounded candidate rows and streams them in
      ``tier_chunk``-candidate chunks through a double-buffered HBM staging
      window: ``jax.device_put`` of chunk i+1 overlaps the fused
      rescore+top-k of chunk i, and partial top-k runs are rank-merged with
      :func:`core.topk.merge_sorted`. Chunks ascend in stage-1 candidate
      rank and ``merge_sorted`` keeps the earlier run on ties, so results
      are **bit-identical** to ``residency="device"``
      (``tests/test_tiered.py``). Double-buffer telemetry (chunks, bytes
      streamed, stall fraction) lands in :attr:`stats`. ``None`` inherits
      the store's policy. The numpy backend is host-resident by definition
      and ignores the knob.
    """
    db: np.ndarray
    cutoff: float = 0.8
    m: int = 4
    scheme: int = 1
    use_kernel: bool = False
    backend: str | None = None
    compact_threshold: int = 4096
    #: prebuilt store (durability warm restart) — skips the store build;
    #: ``db`` is ignored when set
    store: object = None
    residency: str | None = None
    #: stage-2 candidate columns per streamed chunk in tiered mode
    tier_chunk: int = 256
    #: similarity metric (None / name / spec string / Metric descriptor).
    #: Metrics with a popcount bound get a per-metric Eq.2-style window
    #: (``Metric.bound_ratios``); unbounded ones (tversky alpha/beta = 0)
    #: fall back to a full scan and ``scanned`` reflects it.
    metric: Metric | str | None = None
    fp_bits: int | None = None

    BACKENDS = ("numpy", "jnp", "tpu")
    DEFAULT_BACKEND = "numpy"
    RESIDENCIES = ("device", "tiered")

    def __post_init__(self):
        self._init_engine()
        if self.store is None:
            self.store = _store_mod().MutableFingerprintStore(
                np.asarray(self.db), sorted_main=True, fold_m=self.m,
                fold_scheme=self.scheme,
                compact_threshold=self.compact_threshold)
        else:
            if (not self.store.sorted_main or self.store.fold_m != self.m
                    or self.store.fold_scheme != self.scheme):
                raise ValueError("restored store layout does not match "
                                 "engine fold config")
            self.compact_threshold = self.store.compact_threshold
        self._resolve_metric_width(self.store.words)
        self._resolve_residency()
        self._stage1_cache = self._jit_cache
        self._sync_gen = None
        self._sync_delta = None
        self._delta_dev = None
        self._device_state: dict | None = None
        self._sync()

    @property
    def n_total(self) -> int:
        return self.store.n_total

    def _sync(self) -> None:
        st = self.store
        if (self._sync_gen == st.generation
                and self._sync_delta == st.delta_version):
            return
        with _TR.span("engine.sync", n_delta=int(st.n_delta)):
            self._refresh(st)

    def _refresh(self, st) -> None:
        if self._sync_gen != st.generation:
            self._sync_gen = st.generation
            if self.residency == "tiered":
                # full-resolution rows stay host-side; counts/order (4 and
                # 8 B/row) still ship — the rebuilt-order merge needs them
                self.full = None
                self._full_np = st.main.db
            else:
                self.full = jnp.asarray(st.main.db)
                self._full_np = None
            self.full_cnt = jnp.asarray(st.main.counts.astype(np.int32))
            self.folded = jnp.asarray(st.main.folded)
            self.folded_cnt = jnp.asarray(
                st.main.folded_counts.astype(np.int32))
            self.order = jnp.asarray(st.main.order.astype(np.int32))
            self._counts_np = st.main.counts           # pads = PAD_COUNT
        if self._sync_delta != st.delta_version:
            self._sync_delta = st.delta_version
            nd = st.n_delta
            if nd == 0:
                self._delta_dev = None
            else:
                sm = _store_mod()
                bucket = sm.next_pow2(nd)
                pad = bucket - nd
                wf = st.delta_folded.shape[1]
                d_full = np.concatenate(
                    [st.delta_db, np.zeros((pad, st.words), np.uint32)])
                d_folded = np.concatenate(
                    [st.delta_folded, np.zeros((pad, wf), np.uint32)])
                d_cnt = np.concatenate(
                    [st.delta_counts,
                     np.full((pad,), sm.PAD_COUNT, np.int64)])
                d_fcnt = np.concatenate(
                    [st.delta_folded_counts, np.zeros((pad,), np.int64)])
                self._delta_dev = {
                    "bucket": bucket,
                    "full": jnp.asarray(d_full),
                    "folded": jnp.asarray(d_folded),
                    "cnt": jnp.asarray(d_cnt.astype(np.int32)),
                    "folded_cnt": jnp.asarray(d_fcnt.astype(np.int32)),
                }

    # -- dispatch -----------------------------------------------------------
    def search(self, queries, k: int):
        """Top-k per query via the configured backend -> (ids, sims) numpy."""
        if self.backend in ("jnp", "tpu"):
            ids, sims, scanned = self.search_tpu(queries, k)
            with _TR.span("engine.fetch"):
                ids, sims, scanned = jax.device_get((ids, sims, scanned))
            self._record_batch(scanned, len(ids))
            return ids, sims
        return self.search_numpy(queries, k)

    def _apply_insert(self, fps):
        return self.store.insert(fps)   # compaction handled by the store

    # -- host-side (variable-shape) reference path --------------------------
    def _np_scores(self, q: np.ndarray, db: np.ndarray, db_cnt: np.ndarray):
        # tanimoto keeps the historical f64 scorer verbatim (its orderings
        # are the pre-metric baseline); other metrics score in f32 via the
        # shared oracle so host orderings match the device's f32 sort
        inter = np.bitwise_count(q[None, :] & db).sum(-1).astype(np.int64)
        if self.metric.name == "tanimoto":
            union = (int(np.bitwise_count(q).sum())
                     + db_cnt.astype(np.int64) - inter)
            return np.where(union > 0, inter / np.maximum(union, 1), 0.0)
        return metric_from_counts_np(self.metric, inter,
                                     int(np.bitwise_count(q).sum()),
                                     db_cnt.astype(np.int64))

    def search_numpy(self, queries, k: int):
        """Reference engine (numpy): true variable-range pruning, used for
        wall-clock algorithmic speedup measurements and as the parity oracle
        for the fixed-shape device path (`search_tpu`).

        The per-query candidate window is the main segment's Eq.2 range plus
        the delta rows whose popcount falls inside the same bounds, stably
        re-sorted by popcount (main first on ties) — exactly the window a
        from-scratch rebuild on the concatenated database would scan.
        """
        self._sync()
        st = self.store
        queries = np.asarray(queries)
        n_main_v = st.n_main
        full = st.main.db
        full_cnt = st.main.counts
        folded = st.main.folded
        folded_cnt = st.main.folded_counts
        order = st.main.order
        kr1 = fl.kr1_for(k, self.m)
        ids_out = np.full((len(queries), k), -1, dtype=np.int64)
        sims_out = np.zeros((len(queries), k), dtype=np.float32)
        # one shared Eq.2 implementation with the device path — the m=1
        # bit-for-bit parity contract depends on identical windows
        a_all = np.bitwise_count(queries).sum(-1)
        los, his = bb.bound_range_np(self._counts_np, a_all, self.cutoff,
                                     metric=self.metric)
        # delta mask from the SAME float64 bounds as the main window
        lo_cnt, hi_cnt = bb.bound_counts_np(a_all, self.cutoff,
                                            metric=self.metric)
        d_cnt = st.delta_counts
        scanned = 0
        for qi, q in enumerate(queries):
            lo, hi = los[qi], his[qi]
            d_idx = np.where((d_cnt >= lo_cnt[qi]) & (d_cnt <= hi_cnt[qi]))[0]
            n_win = (hi - lo) + len(d_idx)
            if n_win <= 0:
                continue
            scanned += n_win
            # merged window in the rebuilt sorted order: stable popcount
            # sort with the (already sorted) main run first, so equal
            # popcounts stay in ascending global-id order
            cnt_w = np.concatenate([full_cnt[lo:hi], d_cnt[d_idx]])
            mo = np.argsort(cnt_w, kind="stable")
            fold_w = np.concatenate(
                [folded[lo:hi], st.delta_folded[d_idx]])[mo]
            fcnt_w = np.concatenate(
                [folded_cnt[lo:hi], st.delta_folded_counts[d_idx]])[mo]
            full_w = np.concatenate([full[lo:hi], st.delta_db[d_idx]])[mo]
            cnt_w = cnt_w[mo]
            gids_w = np.concatenate([order[lo:hi], n_main_v + d_idx])[mo]
            qf = fl.fold(q[None], self.m, self.scheme)[0]
            s1 = self._np_scores(qf, fold_w, fcnt_w)
            kr1_eff = min(kr1, n_win)
            # stable sort, ties by ascending merged-window index — the same
            # deterministic order the device path's top_k produces
            cand = np.argsort(-s1, kind="stable")[:kr1_eff]
            s2 = self._np_scores(q, full_w[cand], cnt_w[cand])
            k_eff = min(k, len(cand))
            best = np.argsort(-s2, kind="stable")[:k_eff]
            ids_out[qi, :k_eff] = gids_w[cand[best]]
            sims_out[qi, :k_eff] = s2[best]
        self._record_batch(scanned, len(queries))
        return ids_out, sims_out

    # -- device-resident fixed-shape path -----------------------------------
    def _device_meta(self) -> dict:
        cap = self.store.main.capacity
        if self._device_state is not None and \
                self._device_state["capacity"] == cap:
            return self._device_state
        from ..kernels import ops as kops
        tile = kops._pick_tile(cap, None)
        self._device_state = {"kops": kops if self.backend == "tpu" else None,
                              "tile": tile,
                              "total_tiles": (cap + tile - 1) // tile,
                              "capacity": cap}
        return self._device_state

    def _make_stage1(self, bucket: int, k1m: int):
        """Shared stage-1 closure (windowed folded scan -> per-query top-k1m
        candidate window rows). Used by both `_build_device_search` and
        `_build_tiered_candidates` — one implementation, so the two
        residencies stay bit-identical by construction.

        The kernel scans the batch in the plan's popcount order ``perm``,
        so neighbouring queries share one tile stream; results go back to
        the callers' order right after it. Every row is its own window's
        exact top-k, so the order changes no result."""
        state = self._device_meta()
        kops, tile, capacity = state["kops"], state["tile"], state["capacity"]

        metric = self.metric

        def stage1_main(qf, folded, folded_cnt, lo_row, hi_row, perm):
            if kops is not None:
                cand, s1 = kops.window_topk(
                    qf[perm], folded, folded_cnt, lo_row[perm], hi_row[perm],
                    k=k1m, max_tiles=bucket, tile_n=tile, metric=metric)
                inv = jnp.zeros_like(perm).at[perm].set(
                    jnp.arange(perm.shape[0], dtype=perm.dtype))
                cand, s1 = cand[inv], s1[inv]
            else:
                s = batched_metric_scores(qf, folded, metric, folded_cnt)
                idx = jnp.arange(capacity)[None, :]
                in_window = jnp.logical_and(idx >= lo_row[:, None],
                                            idx < hi_row[:, None])
                s = jnp.where(in_window, s, -jnp.inf)
                s1, cand = jax.lax.top_k(s, k1m)
                cand = jnp.where(jnp.isfinite(s1), cand, -1)
            return cand, s1

        return stage1_main

    def _make_delta_select(self, k1m: int, k1c: int, delta_bucket: int):
        """Shared main+delta candidate merge: stage-1 scores for both
        segments, merged in the *rebuilt* global popcount-sorted order and
        truncated to ``k1c`` candidates. Returns per-candidate metadata
        (scores, validity, delta routing, window rows, global ids) — the
        device pipeline gathers rescore rows from HBM right after this;
        the tiered pipeline returns it to the host instead."""
        capacity = self._device_meta()["capacity"]
        metric = self.metric
        BIG = jnp.int32(2**30)

        def select(qf, cand, s1, full_cnt, order, d_folded, d_cnt,
                   d_folded_cnt, d_ok, n_main):
            # delta stage-1: masked folded scan (same arithmetic as the
            # kernel: int popcounts routed through metric_from_counts)
            qf_cnt = popcount(qf)
            d_inter = jnp.sum(jax.lax.population_count(
                qf[:, None, :] & d_folded).astype(jnp.int32), axis=-1)
            s1d = metric_from_counts(metric, d_inter, qf_cnt[:, None],
                                     d_folded_cnt[None, :])
            s1d = jnp.where(d_ok, s1d, -jnp.inf)
            # virtual position of every candidate in the merged popcount-
            # sorted array (= the rebuilt sorted row): main row r keeps rank
            # r + |delta with cnt < cnt[r]|; delta row d gets its stable
            # (cnt, insertion-order) rank + |main with cnt <= cnt[d]|.
            # Delta global-ids always exceed main ids, which makes these two
            # searchsorted sides reproduce the rebuilt stable sort exactly.
            d_sorted = jnp.sort(d_cnt)                   # pads: PAD_COUNT
            d_rank = jnp.argsort(jnp.argsort(d_cnt, stable=True))
            pos_d = (d_rank + jnp.searchsorted(full_cnt, d_cnt, side="right")
                     ).astype(jnp.int32)
            safe_c = jnp.clip(cand, 0, capacity - 1)
            pos_m = cand + jnp.searchsorted(
                d_sorted, full_cnt[safe_c], side="left").astype(jnp.int32)
            pos_m = jnp.where(cand >= 0, pos_m, BIG)
            s_all = jnp.concatenate([s1, s1d], axis=1)   # (Q, k1m + D)
            pos_all = jnp.concatenate(
                [pos_m, jnp.broadcast_to(pos_d[None, :], s1d.shape)], axis=1)
            # stage-1 truncation in rebuilt order: score desc, position asc
            sel = jnp.lexsort((pos_all, -s_all), axis=-1)[:, :k1c]
            sel_s = jnp.take_along_axis(s_all, sel, axis=1)
            valid = jnp.isfinite(sel_s)
            is_d = sel >= k1m
            cand_sel = jnp.take_along_axis(cand, jnp.clip(sel, 0, k1m - 1),
                                           axis=1)
            d_slot = jnp.clip(sel - k1m, 0, delta_bucket - 1)
            safe_m = jnp.clip(cand_sel, 0, capacity - 1)
            gids = jnp.where(is_d, n_main + d_slot, order[safe_m])
            gids = jnp.where(valid, gids, -1)
            return sel_s, valid, is_d, d_slot, safe_m, gids

        return select

    def _build_device_search(self, bucket: int, k: int, delta_bucket: int):
        """One jitted two-stage pipeline for <= ``bucket``-tile main windows
        and a ``delta_bucket``-row delta segment (0 = no delta). All segment
        arrays are runtime arguments, so the compiled pipeline survives
        compactions that keep the capacity (and so the shapes) unchanged."""
        state = self._device_meta()
        capacity = state["capacity"]
        m, scheme = self.m, self.scheme
        kr1 = max(fl.kr1_for(k, m), k)
        k1m = min(kr1, capacity)
        stage1_main = self._make_stage1(bucket, k1m)

        metric = self.metric

        def rescore(queries, rows, cnts, valid):
            q_cnt = popcount(queries)
            inter = jnp.sum(jax.lax.population_count(
                queries[:, None, :] & rows).astype(jnp.int32), axis=-1)
            s2 = metric_from_counts(metric, inter, q_cnt[:, None], cnts)
            return jnp.where(valid, s2, -jnp.inf)

        def finish(vals, gids, ok, lo_row, hi_row, extra_scanned):
            k_out = vals.shape[1]
            ids = jnp.where(ok, gids, -1)
            sims = jnp.where(ok, vals, 0.0).astype(jnp.float32)
            if k_out < k:                               # k > N degenerate pad
                pad = ((0, 0), (0, k - k_out))
                ids = jnp.pad(ids, pad, constant_values=-1)
                sims = jnp.pad(sims, pad)
            scanned = jnp.sum(jnp.maximum(hi_row - lo_row, 0)) + extra_scanned
            return ids, sims, scanned

        if delta_bucket == 0:
            k_out = min(k, k1m)

            def run(queries, lo_row, hi_row, perm, folded, folded_cnt, full,
                    full_cnt, order):
                qf = fl.fold_jax(queries, m, scheme)
                cand, s1 = stage1_main(qf, folded, folded_cnt, lo_row, hi_row,
                                       perm)
                valid = cand >= 0
                safe = jnp.clip(cand, 0, capacity - 1)
                if m == 1:
                    # folded == full: stage-1 scores are already exact
                    vals, ok = s1[:, :k_out], valid[:, :k_out]
                    gids = order[safe[:, :k_out]]
                else:
                    s2 = rescore(queries, full[safe], full_cnt[safe], valid)
                    vals, pos = jax.lax.top_k(s2, k_out)  # fused top-k
                    top = jnp.take_along_axis(safe, pos, axis=1)
                    ok = jnp.isfinite(vals)
                    gids = order[top]
                return finish(vals, gids, ok, lo_row, hi_row, jnp.int32(0))

            return jax.jit(run)

        # -- main + delta: merge stage-1 candidates in the *rebuilt* global
        # popcount-sorted order before the kr1 truncation ------------------
        k1c = min(kr1, k1m + delta_bucket)
        k_out = min(k, k1c)
        select = self._make_delta_select(k1m, k1c, delta_bucket)

        def run(queries, lo_row, hi_row, perm, folded, folded_cnt, full,
                full_cnt, order, d_full, d_folded, d_cnt, d_folded_cnt, d_ok,
                n_main):
            qf = fl.fold_jax(queries, m, scheme)
            cand, s1 = stage1_main(qf, folded, folded_cnt, lo_row, hi_row,
                                   perm)
            sel_s, valid, is_d, d_slot, safe_m, gids = select(
                qf, cand, s1, full_cnt, order, d_folded, d_cnt,
                d_folded_cnt, d_ok, n_main)
            if m == 1:
                vals, ok = sel_s[:, :k_out], valid[:, :k_out]
                top_g = gids[:, :k_out]
            else:
                rows = jnp.where(is_d[..., None], d_full[d_slot],
                                 full[safe_m])
                cnts = jnp.where(is_d, d_cnt[d_slot], full_cnt[safe_m])
                s2 = rescore(queries, rows, cnts, valid)
                vals, p = jax.lax.top_k(s2, k_out)
                top_g = jnp.take_along_axis(gids, p, axis=1)
                ok = jnp.isfinite(vals)
            extra = jnp.sum(d_ok.astype(jnp.int32))
            return finish(vals, top_g, ok, lo_row, hi_row, extra)

        return jax.jit(run)

    # -- tiered residency: host-resident full rows, streamed rescore --------
    def _build_tiered_candidates(self, bucket: int, k: int,
                                 delta_bucket: int):
        """Candidate half of the pipeline for ``residency="tiered"``
        (m > 1): the same jitted stage-1 folded scan (+ rebuilt-order delta
        merge) as `_build_device_search`, stopped at the point where the
        device pipeline would gather full-resolution rows from HBM. The
        candidate metadata returns to the host, which gathers the rows from
        the host-resident main segment and streams them through
        `_tiered_rescore`."""
        state = self._device_meta()
        capacity = state["capacity"]
        m, scheme = self.m, self.scheme
        kr1 = max(fl.kr1_for(k, m), k)
        k1m = min(kr1, capacity)
        stage1_main = self._make_stage1(bucket, k1m)

        if delta_bucket == 0:
            def run(queries, lo_row, hi_row, perm, folded, folded_cnt,
                    order):
                qf = fl.fold_jax(queries, m, scheme)
                cand, s1 = stage1_main(qf, folded, folded_cnt, lo_row,
                                       hi_row, perm)
                valid = cand >= 0
                safe = jnp.clip(cand, 0, capacity - 1)
                gids = jnp.where(valid, order[safe], -1)
                return safe, gids, valid

            return jax.jit(run)

        k1c = min(kr1, k1m + delta_bucket)
        select = self._make_delta_select(k1m, k1c, delta_bucket)

        def run(queries, lo_row, hi_row, perm, folded, folded_cnt, full_cnt,
                order, d_folded, d_cnt, d_folded_cnt, d_ok, n_main):
            qf = fl.fold_jax(queries, m, scheme)
            cand, s1 = stage1_main(qf, folded, folded_cnt, lo_row, hi_row,
                                   perm)
            sel_s, valid, is_d, d_slot, safe_m, gids = select(
                qf, cand, s1, full_cnt, order, d_folded, d_cnt,
                d_folded_cnt, d_ok, n_main)
            return safe_m, gids, valid, is_d, d_slot

        return jax.jit(run)

    def _tier_rescore_builder(self, k: int, chunk: int):
        """Fused rescore + top-k + rank-merge over one streamed candidate
        chunk. Candidate popcounts are recomputed on device from the
        streamed rows (identical integers to the stored counts for every
        valid candidate; invalid ones are masked to -inf on both
        residencies). Per-chunk `lax.top_k` breaks score ties by the lowest
        in-chunk index and `merge_sorted` keeps the running (earlier-chunk)
        run ahead on ties; chunks ascend in stage-1 candidate rank, so the
        merged run reproduces the device path's single global top-k bit for
        bit."""
        dk = min(k, chunk)
        metric = self.metric

        def run(queries, rows, valid_c, gids_c, run_vals, run_ids):
            cnts = jnp.sum(jax.lax.population_count(rows).astype(jnp.int32),
                           axis=-1)
            q_cnt = popcount(queries)
            inter = jnp.sum(jax.lax.population_count(
                queries[:, None, :] & rows).astype(jnp.int32), axis=-1)
            s2 = metric_from_counts(metric, inter, q_cnt[:, None], cnts)
            s2 = jnp.where(valid_c, s2, -jnp.inf)
            vals, pos = jax.lax.top_k(s2, dk)
            g = jnp.take_along_axis(gids_c, pos, axis=1)
            g = jnp.where(jnp.isfinite(vals), g, -1)
            if dk < k:
                pad = ((0, 0), (0, k - dk))
                vals = jnp.pad(vals, pad, constant_values=-jnp.inf)
                g = jnp.pad(g, pad, constant_values=-1)
            return jax.vmap(merge_sorted)(run_vals, run_ids, vals, g)

        return jax.jit(run)

    def _tiered_rescore(self, queries, k: int, safe_m, gids, valid,
                        is_d, d_slot):
        """Stream the BitBound-selected candidate rows host -> HBM in
        double-buffered chunks and rank-merge the per-chunk top-k runs.
        ``jax.device_put`` of chunk i+1 is dispatched before the fused
        rescore kernel on chunk i, so the host link overlaps compute; the
        wait on the staged buffer is timed as the stall telemetry."""
        sm = _store_mod()
        nq = queries.shape[0]
        with _TR.span("engine.fetch", what="candidates"):
            safe_np, gids_np, valid_np, is_d_np, d_slot_np = jax.device_get(
                (safe_m, gids, valid, is_d, d_slot))
        n_cand = safe_np.shape[1]
        C = max(1, min(sm.next_pow2(max(self.tier_chunk, 1)),
                       sm.next_pow2(n_cand)))
        n_chunks = (n_cand + C - 1) // C
        rfn = self._cached(("tierrescore", int(k), C),
                           lambda: self._tier_rescore_builder(k, C))
        if is_d is not None:
            d_db = self.store.delta_db
            nd = max(d_db.shape[0], 1)

        def host_chunk(c):
            lo_c, hi_c = c * C, min((c + 1) * C, n_cand)
            rows = self._full_np[safe_np[:, lo_c:hi_c]]
            v = valid_np[:, lo_c:hi_c]
            g = gids_np[:, lo_c:hi_c]
            if is_d is not None:
                dm = is_d_np[:, lo_c:hi_c]
                if dm.any():
                    ds = np.minimum(d_slot_np[:, lo_c:hi_c], nd - 1)
                    rows = np.where(dm[:, :, None], d_db[ds], rows)
            if hi_c - lo_c < C:     # last chunk: pad to the compiled shape
                pad = C - (hi_c - lo_c)
                rows = np.concatenate(
                    [rows, np.zeros((nq, pad, rows.shape[2]), np.uint32)],
                    axis=1)
                v = np.concatenate([v, np.zeros((nq, pad), bool)], axis=1)
                g = np.concatenate(
                    [g, np.full((nq, pad), -1, g.dtype)], axis=1)
            return np.ascontiguousarray(rows), v, np.ascontiguousarray(g)

        run_vals = jnp.full((nq, k), -jnp.inf, jnp.float32)
        run_ids = jnp.full((nq, k), -1, jnp.int32)
        # spans: the brute tiered scan's scheme, with a "tier.host_gather"
        # span per chunk gathered on the host
        stall = 0.0
        t_all = time.perf_counter()
        put_h = _TR.begin("tier.device_put", chunk=0)
        with _TR.span("tier.host_gather", chunk=0):
            first = host_chunk(0)
        staged = jax.device_put(first)
        for c in range(n_chunks):
            cur, cur_h = staged, put_h
            if c + 1 < n_chunks:
                put_h = _TR.begin("tier.device_put", chunk=c + 1)
                with _TR.span("tier.host_gather", chunk=c + 1):
                    nxt = host_chunk(c + 1)
                staged = jax.device_put(nxt)
            with _TR.span("tier.stall", chunk=c):
                ts = time.perf_counter()
                jax.block_until_ready(cur)
                te = time.perf_counter()
            stall += te - ts
            cur_h.end()
            rows_c, v_c, g_c = cur
            with _TR.span("tier.rescore", chunk=c, cols=C):
                run_vals, run_ids = rfn(queries, rows_c, v_c, g_c,
                                        run_vals, run_ids)
        jax.block_until_ready(run_vals)
        total = time.perf_counter() - t_all
        words = self._full_np.shape[1]
        self.stats.update(
            residency="tiered", tiered_chunks=n_chunks, tiered_chunk_cols=C,
            tiered_streamed_bytes=int(n_chunks) * int(C) * int(nq)
            * (4 * words + 5),
            tiered_stall_s=stall, tiered_scan_s=total,
            tiered_stall_fraction=(stall / total) if total > 0 else 0.0)
        with _TR.span("engine.fetch", what="results"):
            vals_np, ids_np = jax.device_get((run_vals, run_ids))
        ok = np.isfinite(vals_np)
        ids = np.where(ok, ids_np, -1)
        sims = np.where(ok, vals_np, 0.0).astype(np.float32)
        return ids, sims

    def _batch_plan(self, q_np: np.ndarray):
        """Host-side metadata of one batch: per-query Eq.2 row windows
        ``[lo, hi)``, the stable popcount order ``perm`` the row-window
        kernel scans the batch in, its power-of-two grid bucket, the delta
        bucket and the delta rows' popcount-window mask (None without a
        delta).

        In that order each block of ``kops.window_block`` queries streams
        the union of its windows once; the bucket covers the widest union.
        The per-query window tiles and the block union tiles are recorded
        for :meth:`stage1_tiles`."""
        self._sync()
        state = self._device_meta()
        tile, total_tiles = state["tile"], state["total_tiles"]
        a = np.bitwise_count(q_np).sum(-1)
        lo, hi = bb.bound_range_np(self._counts_np, a, self.cutoff,
                                   metric=self.metric)
        if state["kops"] is None:
            bucket = total_tiles  # the jnp stage scans full rows, one variant
            perm = np.arange(len(a), dtype=np.int32)      # unused
        else:
            kops = state["kops"]
            perm = np.argsort(a, kind="stable").astype(np.int32)
            window = kops.window_blocks(lo, hi, 1, tile)[1]
            streamed = kops.window_blocks(lo[perm], hi[perm],
                                          kops.window_block(len(a)), tile)[1]
            bucket = bb.bucket_tiles(int(streamed.max(initial=0)),
                                     total_tiles)
            self._last_tiles = (int(window.sum()), int(streamed.sum()))
        dd = self._delta_dev
        delta_bucket = dd["bucket"] if dd is not None else 0
        ok_np = None
        if dd is not None:
            lo_cnt, hi_cnt = bb.bound_counts_np(a, self.cutoff,
                                                metric=self.metric)
            d_cnt_np = self.store.delta_counts
            ok_np = np.zeros((q_np.shape[0], delta_bucket), dtype=bool)
            ok_np[:, :d_cnt_np.shape[0]] = (
                (d_cnt_np[None, :] >= lo_cnt[:, None]) &
                (d_cnt_np[None, :] <= hi_cnt[:, None]))
        return lo, hi, perm, bucket, delta_bucket, ok_np

    def device_pipeline(self, queries, k: int):
        """The jitted device-resident pipeline for this batch and its
        arguments: ``fn(*args) -> (ids, sims, scanned)`` device arrays."""
        plan = self._batch_plan(np.asarray(queries))
        return self._resident_call(jnp.asarray(queries), k, *plan)

    def _resident_call(self, queries, k, lo, hi, perm, bucket, delta_bucket,
                       ok_np):
        fn = self._cached(
            (bucket, int(k), delta_bucket, self._device_meta()["capacity"]),
            lambda: self._build_device_search(bucket, k, delta_bucket))
        args = (queries, jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32),
                jnp.asarray(perm), self.folded, self.folded_cnt, self.full,
                self.full_cnt, self.order)
        dd = self._delta_dev
        if dd is not None:
            args += (dd["full"], dd["folded"], dd["cnt"], dd["folded_cnt"],
                     jnp.asarray(ok_np), jnp.int32(self.store.n_main))
        return fn, args

    def search_tpu(self, queries, k: int):
        """Fixed-shape device path -> ``(ids, sims, scanned)``: jax arrays
        on the device-resident path (nothing read back; :meth:`search`
        fetches them), host values on the tiered one.

        Host work is only window metadata (two searchsorteds + the delta
        popcount mask per batch and the power-of-two grid bucket); the folded
        scans, merge, gather, rescore and top-k all run inside one jitted
        function per ``(bucket, k, delta bucket, capacity)``. ``queries``
        are read on the host for the plan, so pass them as numpy.
        """
        q_np = np.asarray(queries)
        with _TR.span("engine.plan"):
            lo, hi, perm, bucket, delta_bucket, ok_np = self._batch_plan(
                q_np)
        # m == 1 never gathers full-resolution rows (folded == full, stage-1
        # scores are already exact), so the device pipeline serves tiered
        # mode as-is with `self.full is None` — the traced function simply
        # never touches that argument.
        if self.residency == "tiered" and self.m > 1:
            dd = self._delta_dev
            cfn = self._cached(
                ("tiercand", bucket, int(k), delta_bucket,
                 self._device_meta()["capacity"]),
                lambda: self._build_tiered_candidates(bucket, k,
                                                      delta_bucket))
            with _TR.span("engine.dispatch", bucket=int(bucket),
                          delta_bucket=int(delta_bucket), tiered=True):
                queries = jnp.asarray(q_np)
                lo_j = jnp.asarray(lo, jnp.int32)
                hi_j = jnp.asarray(hi, jnp.int32)
                perm_j = jnp.asarray(perm)
                if dd is None:
                    safe_m, gids, valid = cfn(queries, lo_j, hi_j, perm_j,
                                              self.folded, self.folded_cnt,
                                              self.order)
                    is_d = d_slot = None
                    extra = 0
                else:
                    safe_m, gids, valid, is_d, d_slot = cfn(
                        queries, lo_j, hi_j, perm_j, self.folded,
                        self.folded_cnt, self.full_cnt, self.order,
                        dd["folded"], dd["cnt"], dd["folded_cnt"],
                        jnp.asarray(ok_np),
                        jnp.int32(self.store.n_main))
                    extra = int(ok_np.sum())
            ids, sims = self._tiered_rescore(queries, k, safe_m, gids,
                                             valid, is_d, d_slot)
            scanned = int(np.maximum(hi - lo, 0).sum()) + extra
            return ids, sims, scanned
        with _TR.span("engine.dispatch", bucket=int(bucket),
                      delta_bucket=int(delta_bucket)):
            fn, args = self._resident_call(jnp.asarray(q_np), k, lo, hi,
                                           perm, bucket, delta_bucket,
                                           ok_np)
            return fn(*args)


@dataclass
class HNSWEngine(SearchEngine):
    """Approximate graph search (paper §III-C / §IV-B).

    ``backend`` (module-docstring contract):

    * ``"numpy"`` — host reference traversal, true variable-length queues
      (:func:`repro.core.hnsw.search_hnsw_numpy`).
    * ``"jnp"``   — batched device-resident traversal with the jnp
      expansion stage (:func:`repro.core.hnsw.expand_scores_jnp`).
    * ``"tpu"``   — same traversal with the Pallas kernels: the fused
      gather/score/sort expand kernel (``kernels/expand.py``) and the
      ``gather_tanimoto`` row kernel for the entry points.

    The expansion stage streams a neighbour-blocked copy of the base layer
    (``nbr_fps (N, 2M, W)``): one contiguous block per popped node, ``beam``
    DMA streams per query-iteration instead of ``beam*2M`` row fetches, at
    the HBM cost of one extra ``2M*W``-word copy of the base layer.

    ``beam`` is the number of candidates expanded per traversal iteration
    (``beam * 2M`` neighbours scored per kernel launch); ``beam=None`` (the
    default) auto-tunes it from ``ef_search`` (:func:`repro.core.hnsw.auto_beam`,
    the ROADMAP telemetry rule — equal recall, ~B× fewer lock-step
    iterations). ``max_iters`` caps the lock-step loop (default
    ``4*ef + 16``).

    ``shards`` (ISSUE 5) splits the database round-robin into N independent
    per-shard graphs, one per mesh device (FPScreen-style partition-then-
    merge — the paper's replicated traversal pipelines): a search fans out
    one lock-step traversal per shard — per-shard entry points, visited
    bitsets and PQ queues, each on its own device so launches overlap under
    async dispatch — and rank-merges the per-shard result runs into one
    global top-k (``core/distributed.merge_shard_topk`` over
    ``core/topk.merge_sorted_many``). ``shards=1`` is bit-identical to the
    unsharded path (same build seed, identity merge); ``shards=None`` (the
    default) skips the fan-out machinery entirely. Inserts route rows to
    their shard (``gid % N``) and only touched shards refresh their device
    graphs (a full per-shard rebuild at the padded capacity — compiled
    traversals are reused; the unsharded path's finer dirty-row scatter is
    not yet wired through the fan-out). Each shard packs only its own
    nodes' ``nbr_fps``, so the extra ``2M*W``-word HBM copy is split N
    ways.

    The graph is built on the device in rounds of at most ``build_round``
    rows (:func:`repro.core.hnsw.build_hnsw`); the built adjacency becomes
    the device graph as it stands, with no second upload, and
    :attr:`build_stats` holds the build's rows, seconds and rounds. Online
    inserts go through :func:`repro.core.hnsw.insert_hnsw` (one node at a
    time on the host, rng-continuation levels), so an engine that inserted
    online is graph-identical to one built by the same bulk build over the
    same first rows followed by the same inserts: where the bulk build
    stopped is part of what defines the graph. The device graph is padded
    to a power-of-two node capacity: inserts below the capacity reuse
    every compiled traversal, and
    the post-insert refresh is **incremental** — only new fingerprint rows
    and the base-adjacency / neighbour-block rows the batch touched
    (``index.dirty_log``) are scattered into the device arrays; a full
    re-upload happens only when the capacity doubles. The ``tpu`` backend's
    insert-frontier scorer likewise keeps a capacity-padded device copy of
    the database and appends new rows in place instead of re-uploading the
    full database every batch.

    After each ``search``, :attr:`stats` holds the batch's traversal
    telemetry: ``iters`` / ``expansions`` / ``neighbour_evals`` totals and,
    on device backends, per-query arrays plus termination-reason counts
    (``converged`` vs ``max_iters_hit``).
    """
    db: np.ndarray
    m: int = 16
    ef_construction: int = 100
    ef_search: int = 64
    seed: int = 0
    index: hn.HNSWIndex = None
    _graph: hn.HNSWDeviceGraph = None
    backend: str | None = None
    beam: int | None = None
    max_iters: int | None = None
    shards: int | None = None
    #: prebuilt per-shard indexes (durability warm restart) — skips the
    #: sharded build; requires ``shards`` and ignores ``db``
    shard_indexes: list | None = None
    #: similarity metric (None / name / spec string / Metric descriptor);
    #: the graph is built under it and searches must match — a restored
    #: index built under a different metric raises up front
    metric: Metric | str | None = None
    fp_bits: int | None = None
    #: the bulk build's largest round (rows inserted together); part of
    #: what defines the graph, as ``seed`` is
    build_round: int = hn.DEFAULT_BUILD_ROUND

    BACKENDS = ("numpy", "jnp", "tpu")
    DEFAULT_BACKEND = "jnp"

    def _check_index_metric(self, index) -> None:
        built = getattr(index, "metric", TANIMOTO) or TANIMOTO
        if built != self.metric:
            raise ValueError(
                f"restored HNSW index was built under metric "
                f"{built.spec!r}; engine requests {self.metric.spec!r} — "
                f"graph neighbourhoods are metric-specific, rebuild instead")

    def __post_init__(self):
        self._init_engine()
        self.build_stats: dict = {}
        if self.metric is None and self.index is not None:
            self.metric = getattr(self.index, "metric", TANIMOTO)
        if self.metric is None and self.shard_indexes:
            self.metric = getattr(self.shard_indexes[0], "metric", TANIMOTO)
        self.metric = resolve_metric(self.metric)
        if self.beam is None:
            self.beam = hn.auto_beam(self.ef_search)
        if self.shard_indexes is not None and self.shards is None:
            raise ValueError("shard_indexes= requires shards=")
        if self.shards is not None:
            if self.index is not None:
                raise ValueError("pass either index= or shards=, not both")
            self.shards = int(self.shards)
            if self.shard_indexes is not None:
                if len(self.shard_indexes) != self.shards:
                    raise ValueError(
                        f"{len(self.shard_indexes)} restored shard indexes "
                        f"for shards={self.shards}")
                for ix in self.shard_indexes:
                    self._check_index_metric(ix)
                self._shard_indexes = list(self.shard_indexes)
            else:
                t0 = time.perf_counter()
                self._shard_indexes = hn.build_hnsw_sharded(
                    np.asarray(self.db), self.shards, m=self.m,
                    ef_construction=self.ef_construction, seed=self.seed,
                    metric=self.metric, build_round=self.build_round)
                self._built(self._shard_indexes, time.perf_counter() - t0)
            self._resolve_metric_width(self._shard_indexes[0].db.shape[1])
            # the numpy backend never touches a device — don't init jax
            self._shard_devices = (None if self.backend == "numpy"
                                   else shard_devices(self.shards))
            self._shard_graphs = [None] * self.shards
            self._shard_dirty = [True] * self.shards
            self._graph = None
            self._refresh_shard_graphs()
            return
        if self.index is None:
            t0 = time.perf_counter()
            self.index = hn.build_hnsw(np.asarray(self.db), m=self.m,
                                       ef_construction=self.ef_construction,
                                       seed=self.seed, metric=self.metric,
                                       build_round=self.build_round)
            self._built([self.index], time.perf_counter() - t0)
        else:
            self._check_index_metric(self.index)
        self._resolve_metric_width(self.index.db.shape[1])
        self._graph_dirty = False
        self._graph_n = 0          # index.n the device graph was built for
        self._dirty_pos = 0        # consumed prefix of index.dirty_log
        self._dirty_epoch = 0      # dirty_log epoch the prefix belongs to
        self._upper_version = 0    # index.upper_version the graph carries
        self._insert_db_cache = None  # (device db lines, rows filled)
        self._refresh_graph()

    def _built(self, indexes: list, seconds: float) -> None:
        self.build_stats = {
            "rows": sum(ix.bulk_rows for ix in indexes),
            "seconds": float(seconds),
            "rounds": sum(len(hn.build_schedule(ix.bulk_rows, ix.build_round))
                          for ix in indexes)}

    @property
    def n_total(self) -> int:
        if self.shards is not None:
            return sum(ix.n for ix in self._shard_indexes)
        return self.index.n

    def _refresh_shard_graphs(self) -> None:
        """(Re)build the device graphs of shards whose index grew or whose
        padded capacity doubled; untouched shards keep their device copy."""
        if self.backend == "numpy":
            self._shard_dirty = [False] * self.shards
            for ix in self._shard_indexes:
                ix.device_build = None
            return
        np2 = _store_mod().next_pow2
        for s, idx in enumerate(self._shard_indexes):
            g = self._shard_graphs[s]
            cap = np2(idx.n)
            if g is not None and not self._shard_dirty[s] \
                    and g.db.shape[0] == cap:
                continue
            self._shard_graphs[s] = hn.place_graph(
                hn.to_device_graph(idx, capacity=cap,
                                   kernel=self.backend == "tpu"),
                self._shard_devices[s])
            idx.device_build = None
            self._shard_dirty[s] = False

    def _refresh_graph(self) -> None:
        # the numpy backend never touches the device — don't ship the graph
        if self.backend == "numpy":
            self._graph = None
            self.index.device_build = None
            return
        idx = self.index
        cap = _store_mod().next_pow2(idx.n)
        g = self._graph
        log = idx.dirty_log
        if (g is None or g.db.shape[0] != cap or log is None
                or self._graph_n > idx.n
                or self._dirty_epoch != idx.dirty_epoch):
            self._graph = hn.to_device_graph(idx, capacity=cap,
                                             kernel=self.backend == "tpu")
            idx.device_build = None    # the graph holds what it needs
        else:
            # incremental refresh: scatter only the rows the insert batches
            # touched (new fingerprints + dirty base-adjacency rows and
            # their neighbour blocks) into the device
            # arrays — the full re-upload only ever happens on a capacity
            # doubling. tests/test_hnsw_backends.py pins this equal to a
            # fresh to_device_graph.
            rows = sorted(set(log[self._dirty_pos:])
                          | set(range(self._graph_n, idx.n)))
            new = dict(db=g.db, db_popcount=g.db_popcount,
                       base_adj=g.base_adj, nbr_fps=g.nbr_fps,
                       nbr_cnt=g.nbr_cnt, db_lines=g.db_lines,
                       nbr_slabs=g.nbr_slabs)
            if rows:
                from ..kernels import ops as kops
                rows = np.asarray(rows, dtype=np.int64)
                adj_rows = idx.base_adj[rows]

                def put(field, at, value):
                    new[field] = new[field].at[at].set(jnp.asarray(value))
                put("db", rows, idx.db[rows])
                put("db_popcount", rows,
                    idx.db_popcount[rows].astype(np.int32))
                put("base_adj", rows, adj_rows)
                if g.db_lines is not None:
                    lids = kops.line_ids(idx.db.shape[1], rows)
                    put("db_lines", lids, kops.db_lines_at(idx.db, lids))
                fps_np, cnt_np = hn._blocked_rows(
                    idx.db, idx.db_popcount, adj_rows)
                if g.nbr_slabs is not None:
                    put("nbr_slabs", rows, kops.nbr_slabs(fps_np))
                else:
                    put("nbr_fps", rows, fps_np)
                    put("nbr_cnt", rows, cnt_np)
            # upper layers only change when a batch inserted a level>0 node
            # (index.upper_version) — level-0-only batches skip the O(cap)
            # densify+upload entirely
            upper_dev = g.upper_adj
            if self._upper_version != idx.upper_version:
                upper_dev = jnp.asarray(hn._dense_upper(idx, cap))
            self._graph = hn.HNSWDeviceGraph(
                upper_adj=upper_dev, entry_point=jnp.int32(idx.entry_point),
                max_level=max(idx.max_level, 0), **new)
        self._graph_n = idx.n
        self._dirty_pos = len(idx.dirty_log) if idx.dirty_log is not None \
            else 0
        self._dirty_epoch = idx.dirty_epoch
        self._upper_version = idx.upper_version
        self._graph_dirty = False

    def _insert_scorer_factory(self, db: np.ndarray, db_cnt: np.ndarray):
        """Insert-frontier scorer routing neighbour batches through the
        Pallas ``gather_tanimoto`` kernel (ROADMAP "device-side
        construction": the graph walk stays host-side, the distance stage
        runs on device). The device database is cached capacity-padded, in
        the kernel's line layout, across insert batches — the lines holding
        new rows are rewritten in place (one scatter per batch) and a full
        upload only happens when the capacity doubles, so no frontier launch
        relays the database out. Existing rows are immutable, so the cache
        is just (lines, rows filled).
        ``db_cnt`` is part of the scorer-factory protocol but unused here —
        the kernel recomputes row popcounts in-register."""
        del db_cnt
        from ..kernels import ops as kops
        n, w = db.shape
        cached = self._insert_db_cache
        if cached is not None and kops.line_ids(w, n - 1) < len(cached[0]):
            dev, filled = cached
            if n > filled:
                lids = kops.line_ids(w, np.arange(filled, n))
                dev = dev.at[lids].set(jnp.asarray(kops.db_lines_at(db, lids)))
        else:
            cap = _store_mod().next_pow2(n)
            dev = jnp.asarray(kops.db_lines_at(
                db, kops.line_ids(w, np.arange(cap))))
        self._insert_db_cache = (dev, n)

        metric = self.metric

        def scorer(q: np.ndarray, ids: np.ndarray) -> np.ndarray:
            s = _gather_score_frontier(jnp.asarray(q), dev,
                                       jnp.asarray(ids, dtype=jnp.int32),
                                       metric=metric)
            return np.asarray(s)
        return scorer

    def _apply_insert(self, fps):
        if self.shards is not None:
            # per-shard incremental construction in global-id order; only
            # the shards the batch landed on refresh at the next search.
            # (The device frontier scorer caches one db — per-shard dbs
            # differ, so sharded inserts keep the value-identical host
            # scorer.)
            gids, touched = hn.insert_hnsw_sharded(self._shard_indexes, fps)
            for s in touched:
                self._shard_dirty[s] = True
            return gids
        factory = None
        if self.backend == "tpu":
            factory = self._insert_scorer_factory
        gids = hn.insert_hnsw(self.index, fps, scorer_factory=factory)
        # lazy device refresh: N consecutive insert batches cost one
        # (incremental) graph update at the next search, not N
        self._graph_dirty = True
        return gids

    def _device_search(self, k: int, ef: int, beam: int, max_level: int):
        use_kernel = self.backend == "tpu"
        max_iters = self.max_iters
        metric = self.metric
        key = (k, ef, beam, max_level, use_kernel, metric)

        def build():
            # the traversal's program is ``jit_hnsw_traverse`` in a trace
            def hnsw_traverse(q, arrays):
                g = arrays._replace(max_level=max_level)
                score_fn = expand_fn = None     # search_hnsw's jnp stages
                if use_kernel:
                    from ..kernels import ops as kops

                    def score_fn(qs, qc, ids):
                        return kops.gather_tanimoto(qs, g.db_lines, ids,
                                                    q_cnt=qc, metric=metric)

                    def expand_fn(qs, qc, pop, flat, worst, kk):
                        return kops.expand_tanimoto_sorted(
                            qs, g.nbr_slabs, pop, flat, worst, kk,
                            q_cnt=qc, metric=metric)
                return hn.search_hnsw(g, q, k, ef, max_iters=max_iters,
                                      beam=beam, score_fn=score_fn,
                                      expand_fn=expand_fn, metric=metric)
            return jax.jit(hnsw_traverse)
        return self._cached(key, build)

    def _search_sharded(self, queries, k: int, ef: int, beam: int):
        """Fan-out: one traversal per shard, rank-merge into global top-k."""
        n_shards = self.shards
        queries = np.asarray(queries)
        q_n = queries.shape[0]
        m2 = self._shard_indexes[0].base_adj.shape[1]
        if self.backend == "numpy":
            runs_i = np.full((n_shards, q_n, k), -1, dtype=np.int64)
            runs_s = np.zeros((n_shards, q_n, k), dtype=np.float32)
            evals = iters = 0
            for s, idx in enumerate(self._shard_indexes):
                ids, sims, ctr = hn.search_hnsw_numpy(idx, queries, k, ef)
                runs_i[s] = hn.sharded_global_ids(ids, s, n_shards)
                runs_s[s] = sims
                evals += ctr["evals"]
                iters += ctr["iters"]
            # host merge, same semantics as the device tree: pads lose to
            # every real entry, ties keep shard order (stable sort over the
            # shard-major concatenation)
            alli = runs_i.transpose(1, 0, 2).reshape(q_n, n_shards * k)
            alls = runs_s.transpose(1, 0, 2).reshape(q_n, n_shards * k)
            alls = np.where(alli >= 0, alls, -np.inf)
            order = np.argsort(-alls, axis=1, kind="stable")[:, :k]
            ids = np.take_along_axis(alli, order, axis=1)
            sims = np.take_along_axis(alls, order, axis=1)
            sims = np.where(ids >= 0, sims, 0.0).astype(np.float32)
            self._record_batch(evals, q_n)
            self.stats = {"backend": "numpy", "shards": n_shards,
                          "iters": iters, "expansions": iters,
                          "neighbour_evals": evals}
            return ids, sims
        if any(self._shard_dirty):
            self._refresh_shard_graphs()
        runs_s, runs_i, shard_stats = [], [], []
        dev0 = self._shard_devices[0]
        q_dev = jnp.asarray(queries)
        for s, g in enumerate(self._shard_graphs):
            fn = self._device_search(k, ef, beam, g.max_level)
            q_s = jax.device_put(q_dev, self._shard_devices[s])
            ids, sims, tstats = fn(q_s, g._replace(max_level=None))
            runs_i.append(jax.device_put(ids, dev0))
            runs_s.append(jax.device_put(sims, dev0))
            shard_stats.append(tstats)
        gids = hn.globalize_shard_ids(jnp.stack(runs_i))
        ids, sims = merge_shard_topk(jnp.stack(runs_s), gids, k)
        iters = np.stack([np.asarray(st.iters) for st in shard_stats])
        expans = np.stack([np.asarray(st.expansions) for st in shard_stats])
        reason = np.stack([np.asarray(st.reason) for st in shard_stats])
        self._record_batch(int(expans.sum()) * m2, q_n)
        self.stats = {
            "backend": self.backend,
            "shards": n_shards,
            **hn.stats_summary(iters, expans, reason, m2),
            "iters_per_query": iters.sum(axis=0),
            "expansions_per_query": expans.sum(axis=0),
            "per_shard": [{"iters": int(i.sum()), "expansions": int(e.sum())}
                          for i, e in zip(iters, expans)],
        }
        return np.asarray(ids), np.asarray(sims)

    def device_pipeline(self, queries, k: int, ef: int | None = None,
                        beam: int | None = None):
        """The jitted lock-step traversal (unsharded, device backend) for
        this batch and its arguments: ``fn(*args) -> (ids, sims, stats)``
        device arrays."""
        if self._graph_dirty:
            self._refresh_graph()
        g = self._graph
        fn = self._device_search(k, ef or self.ef_search, beam or self.beam,
                                 g.max_level)
        return fn, (jnp.asarray(queries), g._replace(max_level=None))

    def search(self, queries, k: int, ef: int | None = None,
               beam: int | None = None):
        ef = ef or self.ef_search
        beam = beam or self.beam
        # the span folds the traversal's TraversalStats totals into its args
        # via span.set() before closing (no-op when tracing is disabled)
        with _TR.span("hnsw.search", backend=self.backend, ef=int(ef),
                      shards=self.shards or 1) as sp:
            if self.shards is not None:
                ids, sims = self._search_sharded(queries, k, ef, beam)
                sp.set(**{kk: self.stats[kk]
                          for kk in ("iters", "expansions", "neighbour_evals")
                          if kk in self.stats})
                return ids, sims
            m2 = self.index.base_adj.shape[1]
            if self.backend == "numpy":
                ids, sims, ctr = hn.search_hnsw_numpy(
                    self.index, np.asarray(queries), k, ef)
                self._record_batch(ctr["evals"], len(queries))
                self.stats = {"backend": "numpy", "iters": ctr["iters"],
                              "expansions": ctr["iters"],
                              "neighbour_evals": ctr["evals"]}
                sp.set(iters=ctr["iters"], neighbour_evals=ctr["evals"])
                return ids, sims
            fn, args = self.device_pipeline(queries, k, ef, beam)
            ids, sims, tstats = fn(*args)
            iters = np.asarray(tstats.iters)
            expans = np.asarray(tstats.expansions)
            # each expanded candidate gathers and scores <= 2M neighbour
            # slots
            self._record_batch(int(expans.sum()) * m2, iters.shape[0])
            self.stats = {
                "backend": self.backend,
                **hn.stats_summary(iters, expans, tstats.reason, m2),
                "iters_per_query": iters,
                "expansions_per_query": expans,
            }
            sp.set(iters=self.stats["iters"],
                   expansions=self.stats["expansions"],
                   neighbour_evals=self.stats["neighbour_evals"],
                   converged=self.stats["converged"],
                   max_iters_hit=self.stats["max_iters_hit"])
            return ids, sims


def recall_at_k(pred_ids: np.ndarray, true_ids: np.ndarray) -> float:
    """Top-K matching rate vs brute force (paper's accuracy definition)."""
    hits = 0
    for p, t in zip(pred_ids, true_ids):
        hits += len(set(int(x) for x in p if x >= 0) & set(int(x) for x in t))
    return hits / true_ids.size
