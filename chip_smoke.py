#!/usr/bin/env python3
"""Bring-up smoke test of the served similarity-search path on TPU.

Drives the system through the entry points a user calls, at the paper's
deployment size (``CHEMBL_LIKE``: 1,941,405 x 1024-bit Tanimoto, k=20,
Sc=0.8, fold m=4), and checks every answer against an exact numpy oracle.

One chip (``python chip_smoke.py``):

1. the default JAX device must be a TPU — anything else exits non-zero;
2. the library is generated from a fixed seed;
3. ``SearchFrontend`` serves it with 2 replicas on the chip, engines
   ``brute`` and ``bitbound-folding`` on the Pallas (``tpu``) backend: 64
   queries in micro-batches, interleaved with 8 single-row inserts;
4. brute equals the oracle (modulo equal-score ties), BitBound recall@20 is
   at least the numpy backend's minus 0.01, every inserted row finds
   itself at similarity 1.0;
5. the ``jnp`` backend replays the same traffic bit-identically;
6. HNSW (rows and blocked layouts, 20,000 rows) recalls within 0.01 of the
   numpy backend and at least 0.9 against the oracle;
7. every engine resolved backend ``tpu``, its outputs live on the TPU and
   its compiled pipeline holds a Mosaic kernel (``tpu_custom_call``).

Four chips (``python chip_smoke.py --chips 4``) runs only the multi-chip
paths and what they are compared with: ``HNSWEngine(shards=4)`` against
``shards=1`` on the 20,000-row HNSW library (each shard's graph on its own
chip), and the
sharded exhaustive mesh search over all 1,941,405 rows against
single-chip brute.

The per-phase times printed are smoke timings (wall clock, and XLA compile
time from ``jax.monitoring``), not benchmark numbers. The last line of
stdout is one JSON object: ``{"ok": ..., "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from dataclasses import dataclass
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402

from repro.configs.paper import CHEMBL_LIKE  # noqa: E402
from repro.core import (BitBoundFoldingEngine, BruteForceEngine,  # noqa: E402
                        HNSWEngine)
from repro.core.distributed import (make_sharded_search,  # noqa: E402
                                    shard_database)
from repro.core.engine import recall_at_k  # noqa: E402
from repro.core.fingerprints import TANIMOTO, metric_from_counts_np  # noqa: E402
from repro.data.molecules import (SyntheticConfig, queries_from_db,  # noqa: E402
                                  synthetic_fingerprints)
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.serve import FrontendConfig, SearchFrontend, ServiceConfig  # noqa: E402

ENGINES = ("brute", "bitbound-folding")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
WAIT_S = 3600.0
ORACLE_BLOCK = 1 << 16


class SmokeFailure(AssertionError):
    """A result the smoke test refuses."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclass
class Sizes:
    """The deployment under test; tests shrink it, the chip run does not."""
    n_db: int = CHEMBL_LIKE.n_molecules
    n_queries: int = 64
    n_inserts: int = 8
    request_rows: int = 4                  # queries per submitted request
    replicas: int = 2
    k: int = CHEMBL_LIKE.k
    cutoff: float = CHEMBL_LIKE.cutoff
    fold_m: int = CHEMBL_LIKE.folding_m
    hnsw_n: int = 20_000                   # graph build is a host loop
    hnsw_m: int = CHEMBL_LIKE.hnsw_m
    hnsw_ef_construction: int = 40
    hnsw_ef_search: int = CHEMBL_LIKE.hnsw_ef_search
    seed: int = 0


class Report:
    """Per-phase smoke timings: wall clock and XLA backend-compile seconds."""

    def __init__(self):
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compile_s += secs

    def run(self, name: str, fn, *args, **kwargs):
        print(f"[smoke] {name}: start", flush=True)
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn(*args, **kwargs)
        print(f"[smoke] {name}: pass  wall_s={time.perf_counter() - t0:.1f}"
              f"  compile_s={self.compile_s - c0:.1f}"
              f"  (smoke timing, not a benchmark number)", flush=True)
        return out


# -- phase 1 / 2: device and library -----------------------------------------

def require_tpu() -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"[smoke] device: {dev}", flush=True)
    check(dev["platform"] == "tpu",
          f"the default JAX device is {dev['platform']!r}, not a TPU")
    return dev


def make_library(s: Sizes):
    db = synthetic_fingerprints(SyntheticConfig(n=s.n_db, seed=s.seed))
    queries = queries_from_db(db, s.n_queries, seed=s.seed + 1)
    inserts = synthetic_fingerprints(
        SyntheticConfig(n=s.n_inserts, seed=s.seed + 2))
    return db, queries, inserts


def rounds(s: Sizes) -> list[np.ndarray]:
    """Query rows of each round; insert ``r`` follows round ``r``."""
    return np.array_split(np.arange(s.n_queries), s.n_inserts)


# -- the exact oracle ----------------------------------------------------------

def exact_scores(rows: np.ndarray, q: np.ndarray) -> np.ndarray:
    """f32 Tanimoto of ``q`` against every row, as the engines round it.
    Rows go in blocks so no temporary is larger than a few MB."""
    if len(rows) == 0:
        return np.zeros(0, np.float32)
    blocks = range(0, len(rows), ORACLE_BLOCK)
    inter = np.concatenate([np.bitwise_count(rows[i:i + ORACLE_BLOCK] & q)
                            .sum(-1) for i in blocks])
    cnt = np.concatenate([np.bitwise_count(rows[i:i + ORACLE_BLOCK]).sum(-1)
                          for i in blocks])
    return metric_from_counts_np(TANIMOTO, inter, np.bitwise_count(q).sum(),
                                 cnt)


def exact_topk(scores: np.ndarray, k: int) -> np.ndarray:
    """Top-k ids by (score descending, id ascending)."""
    kth = np.partition(scores, -k)[-k]
    cand = np.flatnonzero(scores >= kth)
    return cand[np.argsort(-scores[cand], kind="stable")][:k]


def check_exact(label: str, ids, sims, scores: np.ndarray, k: int) -> None:
    """Engine top-k against the full score vector: the value run equals the
    oracle's bit for bit and each id holds its reported score, so ids may
    differ only inside a group of equal scores."""
    want = np.sort(np.partition(scores, -k)[-k:])[::-1]
    check(np.array_equal(np.asarray(sims, np.float32), want),
          f"{label}: scores {sims} != oracle {want}")
    check(len(set(ids.tolist())) == k and ids.min() >= 0,
          f"{label}: ids {ids} are not {k} distinct rows")
    check(np.array_equal(scores[ids], want),
          f"{label}: ids {ids} do not hold the scores reported for them")


def library_scores(db, inserts, n_visible: int, q) -> np.ndarray:
    return np.concatenate([exact_scores(db, q),
                           exact_scores(inserts[:n_visible], q)])


# -- phase 3: serve ------------------------------------------------------------

def replay(search, insert, queries, inserts, s: Sizes):
    """Run the smoke traffic against ``search(rows) -> (ids, sims)``:
    every round's queries, then that round's single-row insert."""
    ids = np.zeros((s.n_queries, s.k), np.int64)
    sims = np.zeros((s.n_queries, s.k), np.float32)
    for r, rows in enumerate(rounds(s)):
        i, v = search(rows)
        ids[rows], sims[rows] = np.asarray(i), np.asarray(v)
        insert(inserts[r:r + 1])
    return ids, sims


def serve(db, queries, inserts, s: Sizes, backend: str = "tpu"):
    """Serve the traffic through ``SearchFrontend``. Returns per-engine
    results, the inserted rows' global ids, each inserted row's own
    read-back, and the replicas' engines."""
    cfg = ServiceConfig(backend=backend, k=s.k, cutoff=s.cutoff,
                        fold_m=s.fold_m)
    fcfg = FrontendConfig(replicas=s.replicas, default_deadline_ms=None,
                          health_timeout_s=WAIT_S, insert_timeout_s=WAIT_S)
    fe = SearchFrontend(db, engines=ENGINES, config=cfg, frontend=fcfg)
    gids: list[int] = []
    try:
        def search(rows):
            futs = {e: [fe.submit(queries[rows[i:i + s.request_rows]],
                                  engine=e)
                        for i in range(0, len(rows), s.request_rows)]
                    for e in ENGINES}
            return {e: [f.result(WAIT_S) for f in fs]
                    for e, fs in futs.items()}

        out = {e: (np.zeros((s.n_queries, s.k), np.int64),
                   np.zeros((s.n_queries, s.k), np.float32)) for e in ENGINES}
        for r, rows in enumerate(rounds(s)):
            got = search(rows)
            for e in ENGINES:
                out[e][0][rows] = np.concatenate([i for i, _ in got[e]])
                out[e][1][rows] = np.concatenate([v for _, v in got[e]])
            gids.extend(int(g) for g in fe.insert(inserts[r:r + 1]))
        own = {e: fe.search(inserts, engine=e, timeout=WAIT_S)
               for e in ENGINES}
        live = fe.live_replicas()
        check(live == s.replicas, f"{live} of {s.replicas} replicas alive")
    finally:
        fe.close()
    engines = [rep.svc.engines for rep in fe.replicas]
    return out, gids, own, engines


# -- phase 4 / 5: checks -------------------------------------------------------

def check_served(out, gids, own, db, queries, inserts, s: Sizes) -> dict:
    """Brute against the oracle, BitBound recall against the numpy
    backend's on the same traffic, inserted rows find themselves."""
    check(gids == list(range(s.n_db, s.n_db + s.n_inserts)),
          f"inserted rows got ids {gids}")
    oracle = np.zeros((s.n_queries, s.k), np.int64)
    b_ids, b_sims = out["brute"]
    for r, rows in enumerate(rounds(s)):
        for qi in rows:
            scores = library_scores(db, inserts, r, queries[qi])
            check_exact(f"brute q{qi}", b_ids[qi], b_sims[qi], scores, s.k)
            oracle[qi] = exact_topk(scores, s.k)
    ref = BitBoundFoldingEngine(db, cutoff=s.cutoff, m=s.fold_m,
                                backend="numpy")
    np_ids, _ = replay(lambda rows: ref.search(queries[rows], s.k),
                       ref.insert, queries, inserts, s)
    recall_np = recall_at_k(np_ids, oracle)
    recall = recall_at_k(out["bitbound-folding"][0], oracle)
    print(f"[smoke] bitbound recall@{s.k}: tpu {recall:.4f}  "
          f"numpy {recall_np:.4f}", flush=True)
    check(recall >= recall_np - 0.01,
          f"bitbound tpu recall {recall} < numpy {recall_np} - 0.01")
    for e in ENGINES:
        ids, sims = own[e]
        for j, gid in enumerate(gids):
            hit = np.flatnonzero(ids[j] == gid)
            check(hit.size == 1 and sims[j][hit[0]] == 1.0,
                  f"{e}: inserted row {gid} does not find itself at 1.0 "
                  f"(ids {ids[j]}, sims {sims[j]})")
    return {"bitbound_recall": recall, "bitbound_recall_numpy": recall_np}


def check_jnp_parity(out, db, queries, inserts, s: Sizes) -> None:
    """The repo's backend parity contract, between Mosaic-compiled and XLA
    code: the jnp engines replay the served traffic bit-identically."""
    twins = {"brute": BruteForceEngine(db, backend="jnp"),
             "bitbound-folding": BitBoundFoldingEngine(
                 db, cutoff=s.cutoff, m=s.fold_m, backend="jnp")}
    for e, eng in twins.items():
        ids, sims = replay(lambda rows: eng.search(queries[rows], s.k),
                           eng.insert, queries, inserts, s)
        check(np.array_equal(ids, out[e][0]) and
              np.array_equal(sims, out[e][1]),
              f"{e}: jnp and tpu disagree on "
              f"{int((ids != out[e][0]).any(1).sum())} queries' ids, "
              f"{int((sims != out[e][1]).any(1).sum())} queries' scores")


# -- phase 6: HNSW -------------------------------------------------------------

def hnsw_library(s: Sizes):
    """The graph engines' library, queries and exact top-k: a ChEMBL-like
    library of ``hnsw_n`` rows in its own right (graph construction is a
    host loop, ROADMAP R3). A prefix of the full library would not do: its
    rows come from as many distinct scaffolds as rows, so near neighbours
    are barely closer than random rows and every backend's recall against
    the exact top-k sits near 0.4."""
    small = synthetic_fingerprints(SyntheticConfig(n=s.hnsw_n,
                                                   seed=s.seed + 3))
    queries = queries_from_db(small, s.n_queries, seed=s.seed + 4)
    oracle = np.stack([exact_topk(exact_scores(small, q), s.k)
                       for q in queries])
    return small, queries, oracle


def check_hnsw(s: Sizes) -> dict:
    """Both device layouts against the numpy traversal and the oracle."""
    small, queries, oracle = hnsw_library(s)
    knobs = dict(m=s.hnsw_m, ef_construction=s.hnsw_ef_construction,
                 ef_search=s.hnsw_ef_search)
    ref = HNSWEngine(small, backend="numpy", **knobs)
    recall_np = recall_at_k(ref.search(queries, s.k)[0], oracle)
    engines = {}
    for layout in ("rows", "blocked"):
        eng = HNSWEngine(small, index=ref.index, backend="tpu",
                         layout=layout, **knobs)
        recall = recall_at_k(eng.search(queries, s.k)[0], oracle)
        print(f"[smoke] hnsw {layout} recall@{s.k}: tpu {recall:.4f}  "
              f"numpy {recall_np:.4f}", flush=True)
        check(abs(recall - recall_np) <= 0.01 and recall >= 0.9,
              f"hnsw {layout}: tpu recall {recall}, numpy {recall_np}")
        engines[f"hnsw-{layout}"] = eng
    return engines


# -- phase 7: the device did the work ------------------------------------------

def check_device(engines: dict, queries, k: int,
                 platform: str = "tpu") -> None:
    """Each engine resolved the Pallas backend, its pipeline's outputs live
    on the device, and the compiled program holds a Mosaic kernel."""
    for label, eng in engines.items():
        check(eng.backend == "tpu", f"{label}: backend {eng.backend!r}")
        fn, args = eng.device_pipeline(queries, k)
        places = {d.platform for x in jax.tree.leaves(fn(*args))
                  for d in x.devices()}
        check(places == {platform}, f"{label}: outputs on {places}")
        kernel = "tpu_custom_call" in fn.lower(*args).as_text()
        print(f"[smoke] {label}: pipeline HLO has tpu_custom_call: {kernel}",
              flush=True)
        if platform == "tpu":
            check(kernel, f"{label}: no Mosaic kernel in the pipeline")


def run_one_chip(report: Report, s: Sizes, platform: str = "tpu") -> None:
    db, queries, inserts = report.run("library", make_library, s)
    out, gids, own, engines = report.run("serve", serve, db, queries,
                                         inserts, s)
    report.run("check vs oracle", check_served, out, gids, own, db,
               queries, inserts, s)
    report.run("jnp parity", check_jnp_parity, out, db, queries, inserts, s)
    hnsw = report.run("hnsw", check_hnsw, s)
    served = {f"{e}[replica {i}]": eng
              for i, svc in enumerate(engines) for e, eng in svc.items()}
    report.run("device", check_device, {**served, **hnsw}, queries[:8], s.k,
               platform)


# -- four chips ------------------------------------------------------------------

def check_sharded_hnsw(s: Sizes) -> None:
    small, queries, oracle = hnsw_library(s)
    knobs = dict(m=s.hnsw_m, ef_construction=s.hnsw_ef_construction,
                 ef_search=s.hnsw_ef_search, backend="tpu")
    recall = {}
    for shards in (1, 4):
        eng = HNSWEngine(small, shards=shards, **knobs)
        recall[shards] = recall_at_k(eng.search(queries, s.k)[0], oracle)
        homes = [next(iter(g.db.devices())) for g in eng._shard_graphs]
        check(len(set(homes)) == shards,
              f"{shards} shards live on {len(set(homes))} devices")
    print(f"[smoke] sharded hnsw recall@{s.k}: 4 shards {recall[4]:.4f}  "
          f"1 shard {recall[1]:.4f}", flush=True)
    check(abs(recall[4] - recall[1]) <= 0.01,
          f"sharded hnsw recall {recall[4]} vs unsharded {recall[1]}")


def check_mesh_search(db, queries, s: Sizes) -> None:
    """The 4x1 mesh scan (per-chip fused kernel + all_gather merge) equals
    single-chip brute force at the full library size."""
    mesh = make_mesh((len(jax.devices()), 1), ("data", "model"))
    db_s, cnt_s, n_valid = shard_database(mesh, db)
    fn, _, _ = make_sharded_search(mesh, db_s.shape[0], s.k,
                                   use_kernel=True, n_valid=n_valid)
    vals, ids = fn(queries, db_s, cnt_s)
    ref_ids, ref_sims = BruteForceEngine(db, backend="tpu").search(queries,
                                                                  s.k)
    check(np.array_equal(np.asarray(ids), ref_ids) and
          np.array_equal(np.asarray(vals), ref_sims),
          "mesh search and single-chip brute disagree")


def run_four_chips(report: Report, s: Sizes) -> None:
    check(len(jax.devices()) == 4, f"{len(jax.devices())} devices, not 4")
    db, queries, _ = report.run("library", make_library, s)
    report.run("sharded hnsw", check_sharded_hnsw, s)
    report.run("mesh search", check_mesh_search, db, queries, s)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-chip paths")
    args = ap.parse_args(argv)
    device = None
    try:
        device = require_tpu()
        print(f"[smoke] compile cache: {use_compile_cache()}", flush=True)
        report = Report()
        t0 = time.perf_counter()
        (run_four_chips if args.chips == 4 else run_one_chip)(report, Sizes())
        print(f"[smoke] total: wall_s={time.perf_counter() - t0:.1f}  "
              f"compile_s={report.compile_s:.1f}  (smoke timing, not a "
              f"benchmark number)", flush=True)
    except Exception:  # noqa: BLE001 — report the failed phase, exit non-zero
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
