"""The harness on the CPU: discovery by name, the result line, the refusal
without a chip, and the arithmetic behind the end-to-end metrics and the
least bytes. Runs are tiny: 2,000-row libraries, Pallas in the
interpreter."""
import copy
import hashlib
import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import (check, harness, load, oracle, roofline, spec,  # noqa: E402
                   warm)
from bench.load import Op, Window  # noqa: E402


SERVE = "chembl27-bitbound.serve"


def serve_cell() -> spec.Cell:
    """The open-loop cell kept in files for a later PR to enter in
    BENCHMARK.json (``traffic/serve.json`` on ``chembl27-bitbound``), with
    the metrics it would report."""
    b = spec.BENCH_DIR
    e2e = [{"name": n, "unit": u} for n, u in
           (("setup_s", "s"), ("serve_p50_ms", "ms"), ("serve_p95_ms", "ms"))]
    layer = [{"name": n, "unit": u} for n, u in
             (("frontend.wait_share.serve", "%"),
              ("service.pad_share.serve", "%"),
              ("engine.batch_mean_ms.serve", "ms"),
              ("device.idle_share.serve", "%"))]
    return spec.Cell(
        name=SERVE, chips=1, config_name="chembl27-bitbound",
        traffic_name="serve",
        config=spec.load_json(b / "configs" / "chembl27-bitbound.json"),
        traffic=spec.load_json(b / "traffic" / "serve.json"),
        end_to_end=e2e, per_layer=layer)


def tiny(name: str) -> spec.Cell:
    """Cell ``name`` at a size the interpreter runs in seconds."""
    return shrink(serve_cell() if name == SERVE else spec.resolve(name))


def shrink(cell: spec.Cell) -> spec.Cell:
    """``cell`` at a size the interpreter runs in seconds: 2,000 rows,
    batches of 8, 8 answers checked."""
    cell.config = dict(cell.config, n_molecules=2000)
    tr = copy.deepcopy(cell.traffic)
    tr["check_sample"] = 8
    if tr["loop"] == "closed":
        tr["queries_per_batch"] = 8
        tr["service"]["max_batch"] = 8
    else:
        tr.update(rate_per_s=30.0, delta_rows=17, insert_share=0.1)
        tr["service"]["max_batch"] = 4
    cell.traffic = tr
    return cell


def run_tiny(name: str, trace: bool = False, tmp_path=None, seed=2 ** 33,
             seconds: float = 1.0):
    return harness.run(tiny(name), seed=seed, seconds=seconds, trace=trace,
                       require_tpu=False,
                       out_dir=tmp_path / "trace" if tmp_path else None)


def test_result_line_closed_loop():
    res = run_tiny("chembl27-bitbound.screen")
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0 and res["attempted"] % 8 == 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    names = {m["name"] for m in spec.resolve(
        "chembl27-bitbound.screen").end_to_end}
    assert {"setup_s", "screen_compounds_per_s"} <= set(res["metrics"])
    assert set(res["metrics"]) <= names
    assert res["metrics"]["screen_compounds_per_s"]["unit"] == "compounds/s"
    assert res["checks"] == {"mismatched": {"value": 0, "limit": 0},
                             "lost": {"value": 0, "limit": 0}}
    assert json.loads(json.dumps(res)) == res


def test_result_line_open_loop_traced(tmp_path):
    # the traced sub-window runs from 1 s to 3 s: long enough for requests
    # to complete in it under the interpreter
    res = run_tiny("chembl27-bitbound.serve", trace=True, tmp_path=tmp_path,
                   seconds=4.0)
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"mismatched", "lost", "ryw_violations"}
    assert set(res["metrics"]) == {m["name"] for m in serve_cell().per_layer}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "chembl27-exact.screen", "--seed", "3", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_new_cell_config_traffic_and_metric_are_found_by_name(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "out",
                                                  "__pycache__"))
    before = {p: hashlib.sha256(p.read_bytes()).hexdigest()
              for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    bench = spec.load_json(ROOT / "BENCHMARK.json")
    b = tmp_path / "bench"
    cfg = dict(spec.load_json(b / "configs" / "chembl27-exact.json"),
               name="chembl27-exact-small", n_molecules=500)
    (b / "configs" / "chembl27-exact-small.json").write_text(json.dumps(cfg))
    tr = dict(spec.load_json(b / "traffic" / "screen.json"),
              queries_per_batch=16)
    (b / "traffic" / "screen16.json").write_text(json.dumps(tr))
    (b / "metrics" / "rows_scored.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "chembl27-exact-small", "source": "x",
                             "file": "bench/configs/chembl27-exact-small.json",
                             "reduced": ["n_molecules"], "why": "x"})
    bench["workloads"].append({"name": "chembl27-exact-small.screen16",
                               "config": "chembl27-exact-small",
                               "traffic": "screen16", "chips": 1,
                               "why": "x"})
    next(m for m in bench["end_to_end"]
         if m["name"] == "screen_compounds_per_s")["workloads"].append(
        "chembl27-exact-small.screen16")
    bench["per_layer"].append({"name": "rows_scored", "unit": "rows",
                               "better": "higher", "source": "program_counter",
                               "layer": "engine", "moves":
                               "screen_compounds_per_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = spec.resolve("chembl27-exact-small.screen16", root=tmp_path)
    assert cell.config["n_molecules"] == 500
    assert cell.traffic["queries_per_batch"] == 16
    assert {"setup_s", "screen_compounds_per_s"} <= {
        m["name"] for m in cell.end_to_end}
    names = [m["name"] for m in cell.per_layer]
    assert "rows_scored" in names
    assert "window_topk_roofline" not in names   # it lists its cells
    assert spec.metric_reader(cell, "rows_scored")(None) == 42.0
    assert spec.reference_class(cell).__name__ == "Reference"
    # a metric without ``workloads`` reaches every cell of its end-to-end
    # metric, the existing ones too
    old = spec.resolve("chembl27-exact.screen", root=tmp_path)
    assert "rows_scored" in [m["name"] for m in old.per_layer]
    for p, h in before.items():
        assert hashlib.sha256(p.read_bytes()).hexdigest() == h


def test_benchmark_entries_resolve():
    bench = spec.load_json(ROOT / "BENCHMARK.json")
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.end_to_end[0]["name"] == "setup_s"
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_reader(cell, m["name"]))
        assert cell.config["limits"]


def test_latency_runs_from_the_due_time_and_failures_are_infinite():
    ops = [Op(0.1, "query", 0, t_submit=100.12, t_done=100.15),
           Op(0.2, "readback", 0, t_submit=100.2, t_done=100.5),
           Op(0.3, "insert", 0, t_submit=100.3, t_done=100.31),
           Op(0.4, "query", 1, t_submit=100.4, error="DeadlineExceeded")]
    win = Window(t0=100.0, ops=ops)
    lat = load.latencies_ms(win)
    assert np.allclose(lat[:2], [50.0, 300.0]) and math.isinf(lat[2])
    assert load.quantile(lat, 0.5) == pytest.approx(300.0)
    assert math.isinf(load.quantile(lat, 0.95))
    assert check.finite(load.quantile(lat, 0.95)) == np.finfo(float).max
    assert np.allclose(load.lateness_ms(win), [20.0, 0.0, 0.0])
    assert load.counts(win) == (4, 1)


def test_quantile_is_nearest_rank_over_all_requests():
    v = np.arange(1, 101, dtype=float)
    assert load.quantile(v, 0.95) == 95.0
    assert load.quantile(v, 0.5) == 50.0
    assert load.quantile(np.append(v, np.inf), 0.99) == 100.0


def test_screen_rate_is_whole_batches_over_elapsed_time():
    rows = np.arange(4)
    win = Window(t0=100.0, batches=[
        {"rows": rows, "t_done": 101.0, "error": None},
        {"rows": rows, "t_done": 102.5, "error": None},
        {"rows": rows, "t_done": 103.0, "error": "Overloaded"}])
    assert load.screen_rate(win, 1000) == pytest.approx(8 * 1000 / 2.5)
    assert load.counts(win) == (12, 4)


def test_open_schedule_is_one_arrival_trace_for_every_seed():
    tr = serve_cell().traffic
    a, na = load.open_schedule(tr, 10.0, 1, 1000)
    b, nb = load.open_schedule(tr, 10.0, 2 ** 40, 1000)
    assert len(a) == len(b) == round(tr["rate_per_s"] * 10) and na == nb
    assert [(o.due, o.kind) for o in a] == [(o.due, o.kind) for o in b]
    assert [o.row for o in a if o.kind == "query"] != \
        [o.row for o in b if o.kind == "query"]
    assert all(0 <= o.due < 10.0 for o in a)
    assert sum(o.kind == "readback" for o in a) == na > 0


def test_union_rows_matches_a_mask():
    rng = np.random.default_rng(0)
    for _ in range(20):
        lo = rng.integers(0, 1000, 30)
        hi = lo + rng.integers(-5, 200, 30)
        mask = np.zeros(1300, bool)
        for a, b in zip(lo, hi):
            mask[a:max(a, b)] = True
        assert roofline.union_rows(lo, hi) == mask.sum()


def test_least_bytes_at_the_real_shapes():
    exact = spec.load_json(ROOT / "bench/configs/chembl27-exact.json")
    bb = spec.load_json(ROOT / "bench/configs/chembl27-bitbound.json")
    n = exact["n_molecules"]
    assert roofline.fused_topk_bytes(n, 1024, exact) == (
        n * (128 + 4) + 1024 * 128 + 1024 * 20 * 8)
    rng = np.random.default_rng(1)
    counts = np.sort(np.clip(rng.normal(62, 22, n), 8, 256).astype(int))
    q = np.clip(rng.normal(62, 22, 1024), 8, 256).astype(int)
    got = roofline.window_topk_bytes(counts, q, bb, 240)
    ok = np.zeros(257, bool)
    for a in q:
        b = np.arange(257)
        ok |= np.minimum(a, b) / np.maximum(np.maximum(a, b), 1) >= 0.8
    rows = int(ok[counts].sum())
    assert got == rows * (32 + 4) + 1024 * 32 + 1024 * 240 * 8
    # the union is read once, where the windows one by one would read the
    # library hundreds of times
    each = sum(int(oracle.window_mask(a, 0.8, 1024)[counts].sum()) for a in q)
    assert rows <= n and each > 300 * rows
    assert roofline.hbm_share(819e9, 2.0, {"hbm_bytes_per_s": 819e9}) == 50.0


def test_warm_plan_covers_every_batch_the_window_can_make():
    from bench.library import draw_library
    cfg = dict(spec.load_json(ROOT / "bench/configs/chembl27-bitbound.json"),
               n_molecules=4000)
    lib, library = draw_library(cfg, 3)
    counts = np.bitwise_count(library).sum(-1).astype(np.int64)
    w = warm.window_rows(counts, cfg)
    pc = lambda rows: np.bitwise_count(rows).sum(-1)  # noqa: E731

    tr = serve_cell().traffic
    assert warm.longest_run(dict(tr, rate_per_s=60.0)) == 68
    assert warm.longest_run(dict(tr, rate_per_s=1.0)) == 9
    assert warm.longest_run(dict(tr, rate_per_s=1e5)) == 256
    assert warm.poisson_max(3.0) == 18 and warm.poisson_max(0.0) == 0
    ops, n_new = load.open_schedule(tr, 10.0, 5, len(library))
    fresh = lib.molecules(n_new, stream=1)
    plan = warm.plan(tr, cfg, library, counts, ops, fresh, 5)
    got = {(len(b), int(pc(b[:1])[0])) for b in plan}
    for b in plan:      # the widest first, then copies of the narrowest
        assert (w[pc(b[1:])] <= w[pc(b[:1])]).all()
    asked = pc(np.stack([library[o.row] if o.kind == "query"
                         else fresh[o.row] for o in ops
                         if o.kind != "insert"]))
    for n in range(1, warm.longest_run(tr) + 1):
        for i in range(len(asked) - n + 1):
            run = asked[i:i + n]
            assert (1 << (n - 1).bit_length(),
                    int(run[np.argmax(w[run])])) in got
    # the read-backs ask for molecules the library lacks
    assert any(o.kind == "readback" for o in ops)

    screen = spec.resolve("chembl27-bitbound.screen").traffic
    plan = warm.plan(screen, cfg, library, counts, [], fresh, 5)
    assert {len(b) for b in plan} == {1024}
    for i in range(50):
        rows = load.closed_batch(5, i, len(library), 1024)
        a = counts[rows][np.argmax(w[counts[rows]])]
        assert a in {int(pc(b[:1])[0]) for b in plan}


NAME = r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}"
UNIT = r"[A-Za-z0-9_/%.-]{1,16}"
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


def test_benchmark_json_keeps_its_shape():
    import re
    path = ROOT / "BENCHMARK.json"
    bench = spec.load_json(path)
    assert path.stat().st_size <= 64 * 1024
    assert set(bench) == {"command", "paths", "run_seconds", *KEYS}
    assert 1 <= len(bench["command"]) <= 32
    assert all(1 <= len(w) <= 200 for w in bench["command"])
    assert 1 <= len(bench["paths"]) <= 16
    assert all(re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
               and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert 1 <= bench["run_seconds"] <= 51
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for part, keys in KEYS.items():
        names = [e["name"] for e in bench[part]]
        assert len(names) == len(set(names))
        for e in bench[part]:
            extra = {"workloads"} if part in ("end_to_end", "per_layer") \
                else set()
            assert keys <= set(e) <= keys | extra, e
            assert re.fullmatch(NAME, e["name"]), e["name"]
            for k in ("why", "layer", "source"):
                if isinstance(e.get(k), str) and part != "end_to_end":
                    assert 1 <= len(e[k]) <= 200
                    assert "\n" not in e[k] and "\t" not in e[k]
            if "unit" in e:
                assert re.fullmatch(UNIT, e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            assert set(e.get("workloads", cells)) <= cells
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and len(m["layer"]) <= 200
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for c in bench["configs"]:
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert (ROOT / c["file"]).exists() and len(c["reduced"]) <= 16
        assert all(re.fullmatch(NAME, k) for k in c["reduced"])
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert re.fullmatch(NAME, w["config"]) and \
            re.fullmatch(NAME, w["traffic"])
    configs = {c["name"] for c in bench["configs"]}
    assert configs == {w["config"] for w in bench["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
