"""Paper Figs. 8/9/12: HNSW QPS vs recall over the (M, ef) grid.

``--backend`` sweeps the same grid through the engine's execution paths
(``numpy`` host reference / ``jnp`` device traversal / ``tpu`` device
traversal with the Pallas gather-distance kernel, interpreted on CPU),
so the paper's Fig. 12 recall-vs-QPS operating point is directly trackable
per backend. Rows land in the ``experiments/bench`` JSON schema with
``backend``/``beam`` columns plus the traversal telemetry
(iterations, expansions, budget terminations) from ``HNSWEngine.stats``.

``--shards N`` sweeps the sharded fan-out engine instead
(``HNSWEngine(shards=N)``: per-shard traversals + rank-merge, §IV Fig. 8's
parallel pipelines) and lands in ``fig8_hnsw_grid..._sharded.json``; run it
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to give the
shards distinct host devices (EXPERIMENTS.md §Sharded HNSW).
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core import HNSWEngine, recall_at_k
from repro.core import hnsw as hn
from .common import K, brute_truth, emit, get_db, get_queries, timeit


def run(n_db=8_000, n_queries=32, ms=(5, 10, 20), efs=(20, 60, 120, 200),
        backend="jnp", beam=1, ef_construction=100, layout="rows",
        shards=None, metric=None, fp_bits=None):
    from repro.core.fingerprints import resolve_metric
    met = resolve_metric(metric)
    length = int(fp_bits) if fp_bits else 1024
    db = get_db(n_db, seed=7, length=length)
    queries = get_queries(db, n_queries, seed=8)
    true_ids, _ = brute_truth(db, queries, K, metric=met)
    rows = []
    lsuf = "" if layout == "rows" else f"_{layout}"
    ssuf = "" if shards is None else f"_s{shards}"
    msuf = "" if met.name == "tanimoto" else f"_{met.name}"
    for m in ms:
        if shards is None:
            index = hn.build_hnsw(np.asarray(db), m=m,
                                  ef_construction=ef_construction, seed=0,
                                  metric=met)
            eng = HNSWEngine(db, index=index, backend=backend, beam=beam,
                             layout=layout)
        else:
            eng = HNSWEngine(db, m=m, ef_construction=ef_construction,
                             seed=0, backend=backend, beam=beam,
                             layout=layout, shards=shards, metric=met)
        for ef in efs:
            dt = timeit(lambda: eng.search(queries, K, ef=ef), repeats=2)
            ids, _ = eng.search(queries, K, ef=ef)
            rows.append({
                "name": f"hnsw_m{m}_ef{ef}_{backend}{lsuf}{ssuf}{msuf}",
                "m": m, "ef": ef,
                "backend": backend, "beam": beam, "layout": layout,
                "shards": shards,
                "metric": met.spec, "fp_bits": length,
                "n_db": n_db, "n_queries": n_queries,
                "us_per_call": round(dt / n_queries * 1e6, 1),
                "host_qps": round(n_queries / dt, 1),
                "recall": round(recall_at_k(ids, true_ids), 4),
                "avg_neighbour_evals": eng.scanned(n_queries) // n_queries,
                "avg_iters": round(eng.stats.get("iters", 0) / n_queries, 1),
                "max_iters_hit": eng.stats.get("max_iters_hit", 0),
            })
    suffix = "" if backend == "jnp" else f"_{backend}"
    shard_suffix = "" if shards is None else "_sharded"
    emit(f"fig8_hnsw_grid{suffix}{lsuf}{shard_suffix}{msuf}", rows)
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", default="jnp",
                    choices=["numpy", "jnp", "tpu"])
    ap.add_argument("--n-db", type=int, default=None,
                    help="database size (default 8000, 2000 for tpu "
                         "interpret mode)")
    ap.add_argument("--n-queries", type=int, default=None)
    ap.add_argument("--ms", type=int, nargs="+", default=None,
                    help="HNSW M values to sweep")
    ap.add_argument("--efs", type=int, nargs="+", default=None,
                    help="ef_search values to sweep")
    ap.add_argument("--beam", type=int, default=1,
                    help="candidates expanded per traversal iteration")
    ap.add_argument("--layout", default="rows", choices=["rows", "blocked"],
                    help="fine-grained distance layout (row gather vs "
                         "neighbour-blocked streaming; bit-exact results)")
    ap.add_argument("--shards", type=int, default=None,
                    help="fan-out over N per-device database shards "
                         "(emits the _sharded artifact)")
    ap.add_argument("--ef-construction", type=int, default=None)
    ap.add_argument("--metric", default=None,
                    help="similarity metric: tanimoto (default), dice, "
                         "cosine, or tversky(a,b) — the graph is built and "
                         "searched under it (emits a _<metric> artifact)")
    ap.add_argument("--fp-bits", type=int, default=None,
                    help="fingerprint width in bits (default 1024)")
    args = ap.parse_args()
    # interpret-mode Pallas (off-TPU) walks the gather grid in python:
    # default to a tiny-mode sweep there so the smoke leg stays fast
    tiny = args.backend == "tpu"
    run(n_db=args.n_db or (2_000 if tiny else 8_000),
        n_queries=args.n_queries or (8 if tiny else 32),
        ms=tuple(args.ms) if args.ms else ((8,) if tiny else (5, 10, 20)),
        efs=tuple(args.efs) if args.efs else ((20, 60) if tiny
                                              else (20, 60, 120, 200)),
        backend=args.backend, beam=args.beam, layout=args.layout,
        shards=args.shards, metric=args.metric, fp_bits=args.fp_bits,
        ef_construction=args.ef_construction or (40 if tiny else 100))


if __name__ == "__main__":
    main()
