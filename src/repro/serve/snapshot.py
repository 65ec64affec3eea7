"""Serving-state serialization: full search state <-> flat named arrays.

This is the durability layer's data model (ISSUE 6 / ROADMAP "Durability and
warm restart"). A snapshot captures everything a replica needs to hydrate
**bit-identically** — the insert==rebuild parity suites define "identical":

* per store-backed engine (brute / bitbound-folding): the main segment's
  rows *in global-id order* plus the delta rows. The sorted/padded/folded
  main arrays are **not** stored — ``MutableFingerprintStore`` rebuilds
  them through the same deterministic ``_build_main`` (stable popcount
  argsort, power-of-two capacity, eager folding) that produced the live
  segment, so the restored arrays are byte-equal and the store's write
  counters (``generation`` / ``delta_version`` / ``compactions``) are
  carried in the meta blob.
* per HNSW index (and per shard of a sharded engine): fingerprints,
  base-layer adjacency, per-level upper adjacency, entry point, level
  assignments, and the **level-draw rng state** (``np.random.Generator``
  PCG64 state dict) — continuing inserts after a restore draws exactly the
  levels the live index would have drawn. Construction-time ``upper_dicts``
  are rebuilt from the dense arrays (the existing deserialized-index path);
  capacity backing arrays reallocate lazily on the first insert with values
  identical to the live ones (both sides share the same power-of-two
  bracket).

Arrays are a flat ``{name: ndarray}`` dict (names like
``"brute/main_rows"``, ``"hnsw/shard01/db"``) written by
``repro.checkpoint.manager.save_array_snapshot``; everything non-array
rides in the manifest's JSON ``meta``. ``service_state`` is the canonical
extraction — the property-based round-trip test compares the live and
restored extractions byte-for-byte.
"""
from __future__ import annotations

import numpy as np

from ..core import hnsw as hn
from ..core.fingerprints import TANIMOTO, resolve_metric
from ..obs.trace import TRACER as _TR
from ..core.engine import (BitBoundFoldingEngine, BruteForceEngine,
                           HNSWEngine)
from .store import (MutableFingerprintStore, TieredFingerprintStore,
                    _popcounts)

FORMAT_VERSION = 1


def _cow(a: np.ndarray) -> np.ndarray:
    """Copy-on-write extraction: always materialize a private C-contiguous
    copy (``np.ascontiguousarray`` would alias an already-contiguous live
    array, racing the background snapshot writer against inserts)."""
    return np.array(a, order="C")


# -- store ------------------------------------------------------------------

def store_state(store: MutableFingerprintStore):
    """Extract a store as ``(arrays, meta)``."""
    n = store.main.n
    main_rows = np.empty((n, store.words), dtype=np.uint32)
    main_rows[store.main.order[:n]] = store.main.db[:n]
    arrays = {"main_rows": main_rows, "delta_db": store.delta_db.copy()}
    meta = {
        "sorted_main": bool(store.sorted_main),
        "fold_m": int(store.fold_m),
        "fold_scheme": int(store.fold_scheme),
        "compact_threshold": int(store.compact_threshold),
        "generation": int(store.generation),
        "delta_version": int(store.delta_version),
        "compactions": int(store.compactions),
        "residency": getattr(store, "residency", "device"),
        "words": int(store.words),
    }
    return arrays, meta


def store_from_state(arrays, meta) -> MutableFingerprintStore:
    from ..core import folding as fl
    # tiered stores restore as tiered (host-RAM main segment; an mmap
    # backing directory is a deployment knob, not snapshot state) so the
    # hydrated engine never materializes the full DB on device
    kind = (TieredFingerprintStore
            if meta.get("residency", "device") == "tiered"
            else MutableFingerprintStore)
    rows = np.asarray(arrays["main_rows"], dtype=np.uint32)
    if "words" in meta and rows.shape[1] != int(meta["words"]):
        raise ValueError(
            f"snapshot rows are {rows.shape[1]} words wide but meta "
            f"records {meta['words']} — refusing a width-mismatched restore")
    st = kind(
        rows, sorted_main=meta["sorted_main"],
        fold_m=meta["fold_m"], fold_scheme=meta["fold_scheme"],
        compact_threshold=meta["compact_threshold"])
    delta = np.asarray(arrays["delta_db"], dtype=np.uint32)
    if delta.shape[0]:
        st.delta_db = delta
        st.delta_counts = _popcounts(delta)
        st.delta_folded = (fl.fold(delta, st.fold_m, st.fold_scheme)
                           if st.fold_m > 1 else delta)
        st.delta_folded_counts = _popcounts(st.delta_folded)
    st.generation = meta["generation"]
    st.delta_version = meta["delta_version"]
    st.compactions = meta["compactions"]
    return st


# -- HNSW index -------------------------------------------------------------

def hnsw_index_state(index: hn.HNSWIndex):
    """Extract one HNSW index as ``(arrays, meta)``."""
    arrays = {
        "db": _cow(index.db),
        "base_adj": _cow(index.base_adj),
        "level_of": _cow(index.level_of),
    }
    for l in range(1, index.max_level + 1):
        arrays[f"upper{l}_nodes"] = _cow(index.level_nodes[l - 1])
        arrays[f"upper{l}_adj"] = _cow(index.level_adj[l - 1])
    rng_state = None
    if index.rng is not None:
        rng_state = index.rng.bit_generator.state  # JSON-able nested dict
    meta = {
        "m": int(index.m),
        "ef_construction": int(index.ef_construction),
        "entry_point": int(index.entry_point),
        "max_level": int(index.max_level),
        "seed": int(index.seed),
        "build_round": int(index.build_round),
        "bulk_rows": int(index.bulk_rows),
        "max_level_cap": int(index.max_level_cap),
        "dirty_epoch": int(index.dirty_epoch),
        "upper_version": int(index.upper_version),
        "rng_state": rng_state,
        "metric": getattr(index, "metric", TANIMOTO).spec,
    }
    return arrays, meta


def hnsw_index_from_state(arrays, meta) -> hn.HNSWIndex:
    db = np.ascontiguousarray(arrays["db"], dtype=np.uint32)
    level_nodes, level_adj = [], []
    for l in range(1, meta["max_level"] + 1):
        level_nodes.append(
            np.asarray(arrays[f"upper{l}_nodes"], dtype=np.int32))
        level_adj.append(np.asarray(arrays[f"upper{l}_adj"], dtype=np.int32))
    index = hn.HNSWIndex(
        db=db, db_popcount=hn._np_popcount(db), m=meta["m"],
        ef_construction=meta["ef_construction"],
        entry_point=meta["entry_point"], max_level=meta["max_level"],
        base_adj=np.ascontiguousarray(arrays["base_adj"], dtype=np.int32),
        level_nodes=level_nodes, level_adj=level_adj,
        level_of=np.ascontiguousarray(arrays["level_of"], dtype=np.int8),
        seed=meta["seed"], max_level_cap=meta["max_level_cap"],
        build_round=meta.get("build_round", 0),
        bulk_rows=meta.get("bulk_rows", 0),
        metric=resolve_metric(meta.get("metric", "tanimoto")))
    index.dirty_epoch = meta["dirty_epoch"]
    index.upper_version = meta["upper_version"]
    if meta.get("rng_state") is not None:
        index.rng = np.random.default_rng(index.seed)
        index.rng.bit_generator.state = meta["rng_state"]
    # construction dicts: rebuilt through the existing deserialized-index
    # path (values identical to the live dicts; _densify sorts keys, so
    # iteration-order differences cannot leak into future graphs)
    index.upper_dicts = hn._upper_dicts_from_dense(index)
    return index


# -- engines ----------------------------------------------------------------

_STORE_KINDS = {"brute": BruteForceEngine, "bitbound": BitBoundFoldingEngine}


def engine_state(engine):
    """Extract any of the three engine types as ``(arrays, meta)``."""
    if isinstance(engine, BruteForceEngine):
        arrays, smeta = store_state(engine.store)
        return arrays, {"kind": "brute", "store": smeta}
    if isinstance(engine, BitBoundFoldingEngine):
        arrays, smeta = store_state(engine.store)
        return arrays, {"kind": "bitbound", "store": smeta}
    if isinstance(engine, HNSWEngine):
        if engine.shards is not None:
            arrays, shard_meta = {}, []
            for s, ix in enumerate(engine._shard_indexes):
                a, m_ = hnsw_index_state(ix)
                arrays.update({f"shard{s:02d}/{k}": v for k, v in a.items()})
                shard_meta.append(m_)
            return arrays, {"kind": "hnsw", "shards": engine.shards,
                            "shard_index": shard_meta}
        arrays, imeta = hnsw_index_state(engine.index)
        return arrays, {"kind": "hnsw", "shards": None, "index": imeta}
    raise TypeError(f"cannot snapshot engine type {type(engine).__name__}")


def engine_from_state(arrays, meta, **engine_kwargs):
    """Rebuild an engine from its extracted state. ``engine_kwargs`` are the
    construction knobs (backend, cutoff, ef_search, ...) that are serving
    config rather than data — the caller passes them from ServiceConfig."""
    kind = meta["kind"]
    engine_kwargs.pop("shards", None)   # sharding is data shape: meta decides
    if kind in _STORE_KINDS:
        store = store_from_state(arrays, meta["store"])
        return _STORE_KINDS[kind](None, store=store, **engine_kwargs)
    if kind == "hnsw":
        if meta["shards"] is not None:
            shards = int(meta["shards"])
            indexes = []
            for s, imeta in enumerate(meta["shard_index"]):
                pre = f"shard{s:02d}/"
                sub = {k[len(pre):]: v for k, v in arrays.items()
                       if k.startswith(pre)}
                indexes.append(hnsw_index_from_state(sub, imeta))
            return HNSWEngine(None, shards=shards, shard_indexes=indexes,
                              **engine_kwargs)
        index = hnsw_index_from_state(arrays, meta["index"])
        return HNSWEngine(None, index=index, **engine_kwargs)
    raise ValueError(f"unknown engine kind {kind!r}")


def service_state(svc):
    """Extract a whole :class:`repro.serve.service.SearchService` as
    ``(arrays, meta)`` — the canonical state the round-trip tests compare."""
    from dataclasses import asdict
    arrays, engines_meta = {}, {}
    # the COW extraction runs on the serving thread even for background
    # snapshots — its span is the synchronous cost the request path pays
    with _TR.span("snapshot.extract", engines=" ".join(svc.engines)):
        for name, eng in svc.engines.items():
            a, m_ = engine_state(eng)
            arrays.update({f"{name}/{k}": v for k, v in a.items()})
            engines_meta[name] = m_
    cfg = asdict(svc.config)
    cfg.pop("durable_dir", None)       # bound at open(), not snapshot time
    meta = {
        "format": FORMAT_VERSION,
        "config": cfg,
        "engines": list(svc.engines.keys()),
        "default_engine": svc.default_engine,
        "engine_state": engines_meta,
        "n_total": int(next(iter(svc.engines.values())).n_total),
        "metric": resolve_metric(cfg.get("metric", "tanimoto")).spec,
        "fp_bits": int(cfg.get("fp_bits") or svc.words * 32),
    }
    return arrays, meta


def split_engine_arrays(arrays, name):
    """Select the ``name/``-prefixed subset of a service array dict."""
    pre = name + "/"
    return {k[len(pre):]: v for k, v in arrays.items() if k.startswith(pre)}
