"""The committed HNSW cell, ``chembl27-hnsw.screen``: its configuration
through the harness at 3,000 rows on the CPU, its three per-layer readers,
and the least-bytes count and program-time reduction behind its roofline
share (``bench/roofline_hnsw.py``).
"""
import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import harness, roofline, roofline_hnsw, spec  # noqa: E402
from test_bench_approximate import cell_in  # noqa: E402

HNSW_LAYER = ("hnsw.evals_per_query", "hnsw_traversal_roofline",
              "hnsw.build_rows_per_s")


def test_the_hnsw_cell_runs_at_3000_rows_and_its_readers_read(tmp_path,
                                                             monkeypatch):
    """``chembl27-hnsw`` through the harness at 3,000 rows, traced: correct,
    and the new readers give numbers. A CPU run has no peak and its trace
    no ``XLA Modules`` line, so the roofline reader is read again over the
    run's own view with the v5e's peak and a stand-in for that line."""
    committed = spec.resolve("chembl27-hnsw.screen")
    cell = cell_in(tmp_path, "chembl27-hnsw", base="chembl27-hnsw",
                   n_molecules=3000)
    tr = copy.deepcopy(cell.traffic)
    tr.update(check_sample=8, queries_per_batch=8, trace_seconds=1.0)
    tr["service"]["max_batch"] = 8
    cell.traffic = tr
    cell.per_layer = [m for m in committed.per_layer
                      if m["name"] in HNSW_LAYER]
    assert [m["name"] for m in cell.per_layer] == list(HNSW_LAYER)
    seen = {}

    def modules(out_dir, window):
        seen["window"] = window
        lo, hi = window
        return {"/device:TPU:0": [("jit_hnsw_traverse(1)", lo, lo + 0.5),
                                  ("jit_other(2)", lo, hi)]}
    monkeypatch.setattr(roofline_hnsw, "load_modules", modules)
    views = []
    read_metrics = harness.read_metrics

    def keep(view, entries):
        views.append(view)
        return read_metrics(view, entries)
    monkeypatch.setattr(harness, "read_metrics", keep)
    res = harness.run(cell, seed=2 ** 33 + 5, seconds=4.0, trace=True,
                      require_tpu=False, out_dir=tmp_path / "trace")
    assert res["correct"] is True, res["checks"]
    assert res["checks"]["misscored"]["value"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == {"hnsw.evals_per_query", "hnsw.build_rows_per_s"}
    assert got["hnsw.evals_per_query"] > 0
    assert got["hnsw.build_rows_per_s"] > 0
    view, = views
    view.peak = roofline.peaks("TPU v5 lite")
    share = spec.metric_reader(cell, "hnsw_traversal_roofline")(view)
    assert "window" in seen and 0 < share < 100


def test_least_bytes_of_a_hand_counted_traversal():
    # 2 queries at M 16, k 20: 96 scored neighbours (3 expansions of 32)
    got = roofline_hnsw.traversal_bytes(evals=96, expansions=3, queries=2,
                                        m=16, k=20)
    assert got == 96 * 128 + 3 * 32 * 4 + 2 * 128 + 2 * 20 * 8
    assert roofline_hnsw.traversal_bytes(0, 0, 1, 16, 20, fp_bits=2048) \
        == 256 + 160


def test_program_time_is_the_named_program_inside_the_window():
    mods = {"/device:TPU:0": [("jit_hnsw_traverse(7)", 0.5, 1.5),
                              ("jit_hnsw_traverse(7)", 2.0, 3.0),
                              ("jit_hnsw_traverse_x(8)", 1.0, 2.0),
                              ("jit_convert(9)", 1.0, 2.0)],
            "/device:TPU:1": [("jit_hnsw_traverse(7)", 1.0, 2.0)]}
    t = roofline_hnsw.program_s(mods, (1.0, 2.5), "jit_hnsw_traverse")
    assert t == pytest.approx((0.5 + 0.5 + 1.0) / 2)
    assert roofline_hnsw.program_s({}, (0, 1), "jit_hnsw_traverse") == 0
    assert roofline_hnsw.load_modules("/nonexistent", (0, 1)) is None
