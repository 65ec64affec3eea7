"""Similarity-search serving: the paper's engine as a first-class service.

Two serving shapes:

* ``--engine sharded-brute|bitbound-folding|hnsw`` — frozen-database
  benchmark loops (batched KNN over a mesh-sharded or single-chip engine),
  the paper's offline-deployment measurement.
* ``--engine service`` — the online deployment: a
  :class:`repro.serve.service.SearchService` driven with a mixed
  insert+query workload (``--write-ratio``), dynamic micro-batching into
  power-of-two buckets, LSM-compacting mutable store underneath, and
  per-request latency / QPS / compaction telemetry. This is the paper's
  FPGA host loop (stream queries, append compounds without stalling the
  scan) mapped onto the TPU engines.

``--trace-out DIR`` runs either shape under a ``jax.profiler`` session:
the program's spans and the device's operations on one timeline, as
``DIR/plugins/profile/<run>/*.xplane.pb`` plus a ``perfetto_trace.json.gz``
that opens in https://ui.perfetto.dev.
"""
from __future__ import annotations

import argparse
import time
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import CHEMBL_LIKE
from ..core import BruteForceEngine, BitBoundFoldingEngine, HNSWEngine, recall_at_k
from ..core.distributed import make_sharded_search, shard_database
from ..data.molecules import SyntheticConfig, synthetic_fingerprints, queries_from_db
from .compile_cache import use_compile_cache
from .mesh import make_local_mesh


def serve(engine: str = "sharded-brute", n_db: int = 100_000, k: int = 20,
          n_queries: int = 256, batches: int = 4, use_kernel: bool = False,
          backend: str | None = None, hnsw_layout: str = "rows",
          hnsw_shards: int | None = None, residency: str = "device",
          metric: str | None = None, fp_bits: int | None = None,
          log=print):
    """``backend`` selects the engine execution path (shared contract, see
    ``core/engine.py``): "numpy" (host reference), "tpu" (device-resident
    Pallas pipeline; Mosaic on TPU, the Pallas interpreter when CPU was
    asked for, refused otherwise — ``kernels/ops.py::interpret_on``) or
    "jnp" (device path without
    Pallas). Applies to the ``bitbound-folding`` (two-stage scan) and
    ``hnsw`` (batched graph traversal) engines. ``hnsw_layout`` picks the
    traversal's fine-grained distance layout ("rows" row-gather /
    "blocked" neighbour-blocked streaming, bit-exact results);
    ``hnsw_shards`` fans the HNSW engine out over N per-device database
    shards with a rank-merged global top-k (EXPERIMENTS.md §Sharded
    HNSW). ``residency="tiered"`` keeps the full-resolution DB host-side
    and streams rescore candidates through a double-buffered HBM window
    (bitbound-folding engine; EXPERIMENTS.md §Tiered residency).
    ``metric`` / ``fp_bits`` pick the similarity and fingerprint width the
    engines are traced at (EXPERIMENTS.md §Metric sweep)."""
    from ..core.fingerprints import resolve_metric
    met = resolve_metric(metric)
    length = int(fp_bits) if fp_bits else 1024
    db = synthetic_fingerprints(SyntheticConfig(n=n_db, length=length))
    queries = queries_from_db(db, n_queries * batches)

    if engine == "sharded-brute":
        if met.name != "tanimoto":
            raise ValueError(
                "--metric is not supported by the sharded-brute mesh loop; "
                "use --engine bitbound-folding / hnsw / service")
        # only this branch needs the device mesh — the single-chip engines
        # must stay servable even where mesh construction is unsupported
        with make_local_mesh() as mesh:
            db_s, cnt_s, n_valid = shard_database(mesh, db)
            search, _, _ = make_sharded_search(mesh, db_s.shape[0], k,
                                               use_kernel=use_kernel,
                                               n_valid=n_valid)
            # warmup/compile
            q0 = jnp.asarray(queries[:n_queries])
            search(q0, db_s, cnt_s)
            t0 = time.time()
            for b in range(batches):
                q = jnp.asarray(queries[b * n_queries:(b + 1) * n_queries])
                vals, ids = search(q, db_s, cnt_s)
                jax.block_until_ready(vals)
            dt = time.time() - t0
    elif engine == "bitbound-folding":
        eng = BitBoundFoldingEngine(db, cutoff=CHEMBL_LIKE.cutoff,
                                    m=CHEMBL_LIKE.folding_m, backend=backend,
                                    residency=residency, metric=met)
        if eng.backend in ("jnp", "tpu"):
            # warm every batch once: different batches can hit different
            # (window-bucket, k) pipelines, and compiling inside the timed
            # loop would pollute the QPS measurement
            for b in range(batches):
                eng.search(queries[b * n_queries:(b + 1) * n_queries], k)
        t0 = time.time()
        for b in range(batches):
            eng.search(queries[b * n_queries:(b + 1) * n_queries], k)
        dt = time.time() - t0
    elif engine == "hnsw":
        eng = HNSWEngine(db[:min(n_db, 20_000)], m=CHEMBL_LIKE.hnsw_m,
                         ef_construction=CHEMBL_LIKE.hnsw_ef_construction,
                         ef_search=CHEMBL_LIKE.hnsw_ef_search,
                         backend=backend, layout=hnsw_layout,
                         shards=hnsw_shards, metric=met)
        eng.search(queries[:n_queries], k)  # compile
        t0 = time.time()
        for b in range(batches):
            eng.search(queries[b * n_queries:(b + 1) * n_queries], k)
        dt = time.time() - t0
        log(f"[search-serve] hnsw traversal stats: "
            f"{eng.stats.get('iters', 0)} iters, "
            f"{eng.stats.get('neighbour_evals', 0)} neighbour evals, "
            f"{eng.stats.get('max_iters_hit', 0)} budget-terminated "
            f"(last batch, {eng.stats.get('shards') or 1} shard(s))")
    else:
        raise ValueError(engine)

    qps = n_queries * batches / dt
    log(f"[search-serve] engine={engine} backend={backend or 'default'} "
        f"metric={met.spec} fp_bits={length} db={n_db} k={k}: "
        f"{qps:.0f} QPS ({dt:.2f}s for {n_queries * batches} queries)")
    return qps


def make_workload(n_ops: int, write_ratio: float,
                  pool: np.ndarray, queries: np.ndarray, insert_batch: int = 1,
                  seed: int = 2):
    """Deterministic mixed op schedule: ``("query", fp)`` / ``("insert",
    rows)`` tuples with an expected ``write_ratio`` fraction of inserts
    (cycling through the insert pool / query set)."""
    rng = np.random.default_rng(seed)
    is_write = rng.random(n_ops) < write_ratio
    ops = []
    qi = wi = 0
    for w in is_write:
        if w and len(pool):
            rows = pool[wi % len(pool):wi % len(pool) + insert_batch]
            ops.append(("insert", rows))
            wi += len(rows)
        else:
            ops.append(("query", queries[qi % len(queries)]))
            qi += 1
    return ops


def serve_frontend(engines=("brute", "bitbound-folding"), n_db: int = 20_000,
                   k: int = 10, n_ops: int = 256, write_ratio: float = 0.01,
                   backend: str | None = None, compact_threshold: int = 2048,
                   replicas: int = 2, durable_dir: str | None = None,
                   snapshot_every: int = 0, resume: bool = False,
                   metric: str | None = None, fp_bits: int | None = None,
                   metrics_out: str | None = None, log=print):
    """Drive the concurrent serving tier (ISSUE 9): the same mixed
    insert+query workload as :func:`serve_service`, but through a
    :class:`repro.serve.frontend.SearchFrontend` — bounded admission,
    deadlines, degradation ladder and ``replicas`` read replicas fed by
    one WAL-ordered insert fan-out. ``durable_dir`` makes the *front end*
    durable (the on-disk layout matches the single service, so either can
    recover the other's directory); ``resume`` warm-restarts every replica
    from the latest snapshot + WAL tail. Returns the frontend summary."""
    from ..serve.frontend import FrontendConfig, SearchFrontend

    length = int(fp_bits) if fp_bits else 1024
    db = synthetic_fingerprints(SyntheticConfig(n=n_db, length=length))
    pool = synthetic_fingerprints(
        SyntheticConfig(n=max(n_ops, 64), length=length, seed=7))
    queries = queries_from_db(db, min(n_db, 512))
    fcfg = FrontendConfig(replicas=replicas, default_deadline_ms=None,
                          flush_interval_ms=1.0,
                          snapshot_every_inserts=snapshot_every)
    if resume:
        if durable_dir is None:
            raise ValueError("--resume requires --durable-dir")
        fe = SearchFrontend.open(
            durable_dir, frontend=fcfg,
            **({"backend": backend} if backend else {}),
            **({"metric": metric} if metric else {}))
        log(f"[search-serve] frontend resumed from {durable_dir}: "
            f"{fe.n_total} rows x {replicas} replicas")
        if fe.words * 32 != length:
            # the snapshot decides the width on resume — regenerate the
            # driver's insert pool + query set at the restored width
            length = fe.words * 32
            db = synthetic_fingerprints(SyntheticConfig(n=n_db,
                                                        length=length))
            pool = synthetic_fingerprints(
                SyntheticConfig(n=max(n_ops, 64), length=length, seed=7))
            queries = queries_from_db(db, min(n_db, 512))
    else:
        fe = SearchFrontend(db, engines=engines, backend=backend, k=k,
                            cutoff=CHEMBL_LIKE.cutoff,
                            fold_m=CHEMBL_LIKE.folding_m,
                            compact_threshold=compact_threshold,
                            durable_dir=durable_dir, frontend=fcfg,
                            metric=metric or "tanimoto", fp_bits=fp_bits)
    ops = make_workload(n_ops, write_ratio, pool, queries)
    enames = list(fe.engines)
    futs = []
    for i, (op, payload) in enumerate(ops):
        if op == "insert":
            fe.insert(payload)
        else:
            futs.append(fe.submit(payload, k=k,
                                  engine=enames[i % len(enames)]))
    for f in futs:
        f.result(timeout=120.0)
    fe.drain(timeout=120.0)
    s = fe.summary()
    log(f"[search-serve] frontend engines={','.join(fe.engines)} "
        f"backend={fe.config.backend or 'default'} db={n_db} k={k} "
        f"replicas={s['replicas_live']}/{s['replicas']}: "
        f"p50={s.get('p50_ms')}ms p99={s.get('p99_ms')}ms "
        f"{s['n_completed']} completed, shed={s['shed']} "
        f"expired={s['expired']} failovers={s['failovers']} "
        f"degradation<= {s['max_degradation_level']}")
    if durable_dir is not None:
        log(f"[search-serve] durable front end: WAL + snapshots under "
            f"{durable_dir} (resume with --engine service --replicas "
            f"{replicas} --resume --durable-dir {durable_dir})")
    if metrics_out:
        fe.export_metrics(metrics_out, ts=time.time())
        log(f"[search-serve] metrics -> {metrics_out} "
            f"(+ {metrics_out}.prom)")
    fe.close()
    return s


def serve_service(engines=("brute", "bitbound-folding"), n_db: int = 20_000,
                  k: int = 10, n_ops: int = 256, write_ratio: float = 0.01,
                  backend: str | None = None, compact_threshold: int = 2048,
                  flush_every: int = 8, hnsw_layout: str = "rows",
                  hnsw_shards: int | None = None,
                  durable_dir: str | None = None, snapshot_every: int = 0,
                  resume: bool = False, residency: str = "device",
                  tier_chunk_rows: int | None = None,
                  tier_chunk: int | None = None,
                  metric: str | None = None, fp_bits: int | None = None,
                  metrics_out: str | None = None,
                  log=print):
    """Drive a :class:`SearchService` with a mixed insert+query workload and
    report the serving telemetry. Returns the service summary dict.

    ``durable_dir`` turns on the durability layer (WAL + snapshots under
    that directory; every insert is fsync'd before it is acked);
    ``snapshot_every`` writes a full-state snapshot every N inserts;
    ``resume`` warm-restarts from an existing durable directory via
    :meth:`SearchService.open` instead of building the engines from the
    synthetic database (EXPERIMENTS.md §Durability runbook).

    ``metrics_out`` exports the service metrics registry as JSONL (plus a
    Prometheus text twin at ``<path>.prom``). ``tier_chunk_rows`` /
    ``tier_chunk`` shrink the tiered streaming chunks to force multi-chunk
    captures (EXPERIMENTS.md §Observability runbook)."""
    from ..serve.service import SearchService

    length = int(fp_bits) if fp_bits else 1024
    db = synthetic_fingerprints(SyntheticConfig(n=n_db, length=length))
    pool = synthetic_fingerprints(
        SyntheticConfig(n=max(n_ops, 64), length=length, seed=7))
    queries = queries_from_db(db, min(n_db, 512))
    if resume:
        if durable_dir is None:
            raise ValueError("--resume requires --durable-dir")
        # only patch the persisted config when a backend was requested —
        # an absent --backend must keep the backend the snapshot was
        # served with, not reset it to the default
        svc = SearchService.open(
            durable_dir, **({"backend": backend} if backend else {}),
            **({"metric": metric} if metric else {}))
        log(f"[search-serve] resumed from {durable_dir}: "
            f"{next(iter(svc.engines.values())).n_total} rows, "
            f"engines={','.join(svc.engines)}")
        if svc.words * 32 != length:
            # the snapshot decides the width on resume — regenerate the
            # driver's insert pool + query set at the restored width
            length = svc.words * 32
            db = synthetic_fingerprints(SyntheticConfig(n=n_db,
                                                        length=length))
            pool = synthetic_fingerprints(
                SyntheticConfig(n=max(n_ops, 64), length=length, seed=7))
            queries = queries_from_db(db, min(n_db, 512))
    else:
        svc = SearchService(db, engines=engines, backend=backend, k=k,
                            cutoff=CHEMBL_LIKE.cutoff,
                            fold_m=CHEMBL_LIKE.folding_m,
                            compact_threshold=compact_threshold,
                            hnsw_layout=hnsw_layout, hnsw_shards=hnsw_shards,
                            durable_dir=durable_dir, residency=residency,
                            tier_chunk_rows=tier_chunk_rows,
                            tier_chunk=tier_chunk,
                            metric=metric or "tanimoto", fp_bits=fp_bits)
    ops = make_workload(n_ops, write_ratio, pool, queries)
    enames = list(svc.engines)
    since_flush = 0
    inserts_since_snap = 0
    for i, (op, payload) in enumerate(ops):
        if op == "insert":
            svc.insert(payload)            # broadcast to every engine
            inserts_since_snap += 1
            if (durable_dir is not None and snapshot_every
                    and inserts_since_snap >= snapshot_every):
                svc.snapshot()
                inserts_since_snap = 0
        else:
            # router: spread query traffic round-robin over the engines
            svc.submit(payload, k=k, engine=enames[i % len(enames)])
            since_flush += 1
            if since_flush >= flush_every:
                svc.flush()
                since_flush = 0
    svc.flush()
    s = svc.summary()
    log(f"[search-serve] service engines={','.join(svc.engines)} "
        f"backend={svc.config.backend or 'default'} db={n_db} k={k} "
        f"write_ratio={write_ratio}: p50={s.get('p50_ms', 0)}ms "
        f"p99={s.get('p99_ms', 0)}ms {s['qps']} QPS, "
        f"{s['n_inserts']} inserts, {s['compactions']} compactions, "
        f"buckets={s['batch_buckets']}")
    if durable_dir is not None:
        log(f"[search-serve] durable: WAL + snapshots under {durable_dir} "
            f"(resume with --engine service --resume --durable-dir "
            f"{durable_dir})")
    svc.close()
    if metrics_out:
        svc.metrics.export_jsonl(metrics_out, ts=time.time())
        with open(str(metrics_out) + ".prom", "w") as f:
            f.write(svc.metrics.render_prometheus())
        log(f"[search-serve] metrics -> {metrics_out} "
            f"(+ {metrics_out}.prom)")
    return s


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", default="sharded-brute",
                    choices=["sharded-brute", "bitbound-folding", "hnsw",
                             "service"])
    ap.add_argument("--n-db", type=int, default=100_000)
    ap.add_argument("--k", type=int, default=20)
    ap.add_argument("--n-queries", type=int, default=256)
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--backend", default=None,
                    choices=["numpy", "jnp", "tpu"],
                    help="engine execution path for bitbound-folding "
                         "(default numpy), hnsw (default jnp) and service")
    ap.add_argument("--hnsw-layout", default="rows",
                    choices=["rows", "blocked"],
                    help="HNSW fine-grained distance layout: per-row gather "
                         "or neighbour-blocked streaming (bit-exact results)")
    ap.add_argument("--shards", type=int, default=None,
                    help="fan the HNSW engine out over N per-device database "
                         "shards (rank-merged global top-k; 1 = bit-identical "
                         "to unsharded)")
    ap.add_argument("--ops", type=int, default=256,
                    help="service mode: number of workload operations")
    ap.add_argument("--write-ratio", type=float, default=0.01,
                    help="service mode: fraction of ops that are inserts")
    ap.add_argument("--compact-threshold", type=int, default=2048,
                    help="service mode: delta rows triggering compaction")
    ap.add_argument("--service-engines", default="brute,bitbound-folding",
                    help="service mode: comma-separated engine list")
    ap.add_argument("--durable-dir", default=None,
                    help="service mode: directory for the WAL + snapshots "
                         "(inserts are fsync'd before they are acked)")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="service mode: full-state snapshot every N inserts "
                         "(0 = only the initial one; requires --durable-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="service mode: warm-restart from --durable-dir "
                         "(latest intact snapshot + WAL replay) instead of "
                         "building fresh engines")
    ap.add_argument("--residency", default="device",
                    choices=["device", "tiered"],
                    help="full-resolution DB placement for the exhaustive "
                         "engines: HBM-resident, or host-resident with "
                         "double-buffered streaming rescore (breaks the "
                         "single-device HBM capacity ceiling)")
    ap.add_argument("--tier-chunk-rows", type=int, default=None,
                    help="service mode, tiered residency: rows per streamed "
                         "chunk for the brute engine (smaller forces more "
                         "chunks through the double buffer)")
    ap.add_argument("--tier-chunk", type=int, default=None,
                    help="service mode, tiered residency: candidate columns "
                         "per streamed rescore chunk (bitbound engine)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="service mode: serve through the concurrent front "
                         "end (SearchFrontend) with N read replicas instead "
                         "of the bare synchronous service")
    ap.add_argument("--metric", default=None,
                    help="similarity metric: tanimoto (default), dice, "
                         "cosine, or tversky(a,b) — engines score, prune "
                         "and build graphs under it; on --resume it must "
                         "match the snapshot's metric")
    ap.add_argument("--fp-bits", type=int, default=None,
                    help="fingerprint width in bits (multiple of 32; "
                         "default 1024) for the synthetic DB and engines")
    ap.add_argument("--metrics-out", default=None,
                    help="service mode: export the metrics registry as JSONL "
                         "here (a Prometheus text twin lands at <path>.prom)")
    ap.add_argument("--trace-out", default=None,
                    help="run under a jax.profiler session writing to this "
                         "directory: program spans and device operations, "
                         "with a Perfetto file (ui.perfetto.dev)")
    args = ap.parse_args()
    use_compile_cache()
    from ..obs.trace import session
    with (session(args.trace_out, perfetto=True) if args.trace_out
          else nullcontext()):
        _run(args)
    if args.trace_out:
        print(f"[search-serve] trace -> {args.trace_out} (open "
              f"perfetto_trace.json.gz in https://ui.perfetto.dev)")


def _run(args) -> None:
    if args.engine == "service" and args.replicas > 1:
        serve_frontend(engines=tuple(args.service_engines.split(",")),
                       n_db=args.n_db, k=args.k, n_ops=args.ops,
                       write_ratio=args.write_ratio, backend=args.backend,
                       compact_threshold=args.compact_threshold,
                       replicas=args.replicas,
                       durable_dir=args.durable_dir,
                       snapshot_every=args.snapshot_every,
                       resume=args.resume,
                       metric=args.metric, fp_bits=args.fp_bits,
                       metrics_out=args.metrics_out)
    elif args.engine == "service":
        serve_service(engines=tuple(args.service_engines.split(",")),
                      n_db=args.n_db, k=args.k, n_ops=args.ops,
                      write_ratio=args.write_ratio, backend=args.backend,
                      compact_threshold=args.compact_threshold,
                      hnsw_layout=args.hnsw_layout, hnsw_shards=args.shards,
                      durable_dir=args.durable_dir,
                      snapshot_every=args.snapshot_every,
                      resume=args.resume, residency=args.residency,
                      tier_chunk_rows=args.tier_chunk_rows,
                      tier_chunk=args.tier_chunk,
                      metric=args.metric, fp_bits=args.fp_bits,
                      metrics_out=args.metrics_out)
    else:
        serve(args.engine, n_db=args.n_db, k=args.k,
              n_queries=args.n_queries, use_kernel=args.use_kernel,
              backend=args.backend, hnsw_layout=args.hnsw_layout,
              hnsw_shards=args.shards, residency=args.residency,
              metric=args.metric, fp_bits=args.fp_bits)


if __name__ == "__main__":
    main()
