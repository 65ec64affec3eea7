"""One run of one cell: set-up, the measured window, the check, the
result line.

Set-up draws the library from the seed on the device, builds the served
entry point (``SearchFrontend``, one replica, the degradation ladder held
at its identity level), loads the store's delta where the traffic asks for
it, and sends every batch shape the window can reach. The window then
offers the traffic with tracing off, or (``--trace 1``) with the profiler on
for a short steady sub-window. After the window the device's peak memory is
read, the front end is closed and the answers are checked against the plain
reference. The last line of standard output is the result.
"""
from __future__ import annotations

import gc
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field, fields, replace
from pathlib import Path

import numpy as np

from bench import check, load, spec, tracereduce, warm
from bench.spec import Cell

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: a traced run's sub-window opens this far into the window (steady by
#: then: the first requests are served in milliseconds)
TRACE_FROM_S = 2.0


class NoChip(RuntimeError):
    """The run was asked for chips this machine does not have."""


def process_age() -> float:
    """Seconds since this process started (the kernel's own clock)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start / os.sysconf("SC_CLK_TCK")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    log(f"device: {info}")
    if require_tpu and (info["platform"] != "tpu" or info["count"] < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX sees "
                     f"{info['count']} {info['platform']} device(s)")
    return info


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where it is
    set, else a fixed directory in the checkout. Every program is kept,
    however quickly it compiled, so a second run finds all of them."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class GcPauses:
    """Counts the interpreter's collections of its oldest generation, and
    the longest, while it is open."""

    def __init__(self):
        self.n, self.longest, self._t = 0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if info.get("generation") != 2:
            return
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.n += 1
            self.longest = max(self.longest, time.perf_counter() - self._t)

    def close(self) -> None:
        gc.callbacks.remove(self._on)


class Compiles:
    """Counts XLA backend compilations and their seconds."""

    def __init__(self):
        import jax
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.n += 1
            self.s += secs

    def close(self) -> None:
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


@dataclass
class RunView:
    """What a metric reader may read."""
    cell: Cell
    setup_s: float
    window: load.Window
    n_rows: int
    library_counts: np.ndarray
    peak: dict | None
    registry: dict = field(default_factory=dict)
    trace: tracereduce.Trace | None = None

    @property
    def cfg(self) -> dict:
        return self.cell.config

    def reg(self, name: str, what: str = "value", **labels) -> float:
        """A registry reading over the window (a counter's or gauge's
        ``value``, a histogram's ``sum`` or ``count``), summed over the
        children whose labels match ``labels``."""
        return sum(row[what] for key, row in self.registry.items()
                   if key[0] == name
                   and all(dict(key[1]).get(k) == str(v)
                           for k, v in labels.items()))

    def children(self, name: str) -> list:
        return [(dict(key[1]), row) for key, row in self.registry.items()
                if key[0] == name]


def registry(fe) -> dict:
    """Every registry row of the front end and its replicas' services,
    keyed by (family, labels)."""
    rows = list(fe.metrics.collect())
    for rep in fe.replicas:
        for row in rep.svc.metrics.collect():
            row["labels"] = dict(row["labels"], replica=str(rep.index))
            rows.append(row)
    return {(r["name"], tuple(sorted(r["labels"].items()))): r for r in rows}


def registry_delta(before: dict, after: dict) -> dict:
    out = {}
    for key, row in after.items():
        old = before.get(key, {})
        if row["type"] == "histogram":
            out[key] = {"count": row["count"] - old.get("count", 0),
                        "sum": row["sum"] - old.get("sum", 0.0)}
        elif row["type"] == "counter":
            out[key] = {"value": row["value"] - old.get("value", 0.0)}
        else:
            out[key] = {"value": row["value"]}
    return out


def service_config(cell: Cell):
    """The service's knobs: the fields the configuration and the traffic
    state, then the configuration's ``engine_params``, an object of
    ``ServiceConfig`` fields (the engine's own parameters, such as HNSW's
    M, efConstruction and efSearch). A key that is no such field is an
    error."""
    from repro.serve import ServiceConfig
    cfg, tr = cell.config, cell.traffic
    svc = ServiceConfig(
        backend="tpu", metric=cfg.get("metric", "tanimoto"),
        fp_bits=int(cfg["fp_bits"]), k=int(cfg["k"]),
        max_batch=int(tr["service"]["max_batch"]),
        compact_threshold=int(cfg["compact_threshold"]),
        cutoff=float(cfg.get("cutoff", 0.0)),
        fold_m=int(cfg.get("fold_m", 1)),
        fold_scheme=int(cfg.get("fold_scheme", 1)),
        residency=cfg["residency"])
    params = cfg.get("engine_params", {})
    unknown = sorted(set(params) - {f.name for f in fields(ServiceConfig)})
    if unknown:
        raise ValueError(f"engine_params of {cell.config_name}: {unknown} "
                         "are not ServiceConfig fields")
    return replace(svc, **params)


def build_frontend(cell: Cell, library: np.ndarray):
    from repro.serve import FrontendConfig, SearchFrontend
    from repro.serve.frontend import DegradeLevel
    cfg, tr = cell.config, cell.traffic
    svc = service_config(cell)
    f = tr["frontend"]
    fcfg = FrontendConfig(
        replicas=1, high_water=int(f["high_water"]),
        default_deadline_ms=f.get("deadline_ms"),
        flush_interval_ms=float(f["flush_interval_ms"]),
        insert_timeout_s=float(f["insert_timeout_s"]),
        health_timeout_s=float(f["health_timeout_s"]),
        ladder=(DegradeLevel("full"),))
    return SearchFrontend(library, engines=(cfg["engine"],), config=svc,
                          frontend=fcfg)


def read_metrics(view: RunView, entries: list) -> dict:
    out = {}
    for m in entries:
        value = spec.metric_reader(view.cell, m["name"])(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


@dataclass
class Prepared:
    """A cell after set-up: the served entry point, warm, with its data."""
    cell: Cell
    seed: int
    device: dict
    peak: dict | None
    compiles: Compiles
    library: np.ndarray
    counts: np.ndarray
    fresh: np.ndarray          # insert pool: the preload, then the window's
    n_pre: int
    fe: object
    setup_s: float = 0.0


def prepare(cell: Cell, seed: int, ops: list, n_inserts: int,
            require_tpu: bool = True) -> Prepared:
    """Set-up: the library from the seed, the front end over it, the
    delta preloaded, every batch shape the window can make sent once.
    ``ops`` is an open-loop window's schedule; ``n_inserts`` fresh
    molecules are drawn for its inserts."""
    phases = {}
    device = device_info(cell.chips, require_tpu)
    peak = None
    if require_tpu:
        from bench.roofline import peaks
        peak = peaks(device["kind"])
    compiles = Compiles()
    sys.path.insert(0, str(spec.ROOT / "src"))
    from bench.library import draw_library
    phases["jax_init"] = process_age()

    cfg, tr = cell.config, cell.traffic
    t = time.perf_counter()
    lib, library = draw_library(cfg, seed)
    n_pre = int(tr.get("delta_rows", 0))
    fresh = lib.molecules(n_pre + n_inserts, stream=1)
    counts = np.bitwise_count(library).sum(-1).astype(np.int64)
    phases["library"] = time.perf_counter() - t

    t = time.perf_counter()
    fe = build_frontend(cell, library)
    phases["frontend"] = time.perf_counter() - t
    p = Prepared(cell, seed, device, peak, compiles, library, counts, fresh,
                 n_pre, fe)
    try:
        t = time.perf_counter()
        if n_pre:
            fe.insert(fresh[:n_pre])
        phases["preload"] = time.perf_counter() - t
        t = time.perf_counter()
        shapes = warm.plan(tr, cfg, library, counts, ops, fresh[n_pre:], seed)
        for batch in shapes:
            fe.search(batch, int(cfg["k"]), deadline_ms=None, timeout=1200.0)
        phases["warm"] = time.perf_counter() - t
    except BaseException:
        close(p)
        raise
    phases["compile"] = compiles.s
    # what set-up built (library, compiled programs, the schedule) lives
    # for the whole run: out of the collector's reach, a collection in the
    # window scans only what the window allocates
    gc.collect()
    gc.freeze()
    import jax
    jax.config.update("jax_log_compiles", True)  # names what compiles later
    p.setup_s = process_age()
    log("setup: " + ", ".join(f"{k} {v:.3f} s" for k, v in phases.items())
        + f"; {len(shapes)} warm batches, {compiles.n} compilations; "
        f"setup_s {p.setup_s:.3f}")
    return p


def close(p: Prepared) -> None:
    import jax
    jax.config.update("jax_log_compiles", False)
    p.fe.close()
    p.compiles.close()
    gc.unfreeze()


def window(p: Prepared, seconds: float, seed: int, tracer,
           ops=None) -> tuple:
    """Offer the cell's traffic for ``seconds``; returns the window, the
    registry readings over it and the compilations inside it."""
    tr = p.cell.traffic
    before = registry(p.fe)
    n0 = p.compiles.n
    if tr["loop"] == "closed":
        win = load.run_closed(p.fe, p.library, tr, p.cell.config, seconds,
                              seed, tracer)
    else:
        win = load.run_open(p.fe, p.library, p.fresh[p.n_pre:], tr,
                            p.cell.config, seconds, seed, tracer, ops)
    return win, registry_delta(before, registry(p.fe)), p.compiles.n - n0


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, out_dir: Path | None = None) -> dict:
    """One run of ``cell``; returns the result line as a dict."""
    import jax
    tr, cfg = cell.traffic, cell.config
    ops, n_new = [], 0
    if tr["loop"] == "open":
        ops, n_new = load.open_schedule(tr, seconds, seed,
                                        int(cfg["n_molecules"]))
    p = prepare(cell, seed, ops, n_new, require_tpu)
    try:
        tracer, marks = load.Tracer(), {}
        if trace:
            out_dir = Path(out_dir or spec.BENCH_DIR / "out" / "trace")
            shutil.rmtree(out_dir, ignore_errors=True)
            tracer = load.Tracer(
                out_dir, start_s=min(TRACE_FROM_S, seconds / 4),
                length_s=min(float(tr["trace_seconds"]), seconds / 2),
                on_open=lambda: marks.update(before=registry(p.fe)),
                on_close=lambda: marks.update(after=registry(p.fe)))
        pauses = GcPauses()
        try:
            win, reg, in_window = window(p, seconds, seed, tracer, ops)
        finally:
            pauses.close()
        if "after" in marks:
            reg = registry_delta(marks["before"], marks["after"])
        stats = jax.devices()[0].memory_stats() or {}
        p.device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    finally:
        close(p)
    log(f"window: {in_window} compilations inside it; {pauses.n} full "
        f"collections, longest {pauses.longest * 1e3:.1f} ms")
    buckets = {}
    for key, row in reg.items():
        if key[0] == "service_batches_total" and row["value"]:
            b = int(dict(key[1])["bucket"])
            buckets[b] = buckets.get(b, 0) + int(row["value"])
    log(f"batch buckets: {dict(sorted(buckets.items()))}")
    if win.ops:
        late = load.lateness_ms(win)
        if late.size:
            log(f"generator lateness ms: p50 {np.median(late):.3f}, "
                f"p99 {load.quantile(late, 0.99):.3f}, max {late.max():.3f}")

    view = RunView(cell=cell, setup_s=p.setup_s, window=win,
                   n_rows=len(p.library) + p.n_pre, library_counts=p.counts,
                   peak=p.peak, registry=reg)
    extra = {}
    if trace and tracer.state == "done":
        view.trace = tracereduce.load(out_dir)
        lo, hi = view.trace.window
        p.device["busy_s"] = tracereduce.busy_s(view.trace)
        p.device["window_s"] = hi - lo
        extra["breakdown"] = tracereduce.breakdown(view.trace)
    metrics = read_metrics(view, cell.metrics(trace))

    got = check.check(spec.reference_class(cell), cfg, tr, win, p.library,
                      p.fresh, p.n_pre, seed)
    log(f"check: {got['checked']} answers against the reference in "
        f"{got['seconds']:.3f} s")
    correct, shown = check.verdict(got["numbers"], cfg["limits"])
    attempted, failed = load.counts(win)
    for name, v in shown.items():
        log(f"check {name}: {v['value']} (limit {v['limit']})")
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": p.device, **extra,
            "checks": shown}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    try:
        device_info(cell.chips, require_tpu=True)
        log(f"compile cache: {use_compile_cache(spec.ROOT)}")
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(str(e))
        return 2
    print(json.dumps(result), flush=True)
    return 0

