"""A configuration whose engine answers approximately: its engine's
parameters from the configuration file, and its answers held to the exact
scores and to recall@k (``check.grade``), while every other configuration
keeps the bit-for-bit comparison.

The HNSW runs are tiny (2,000 rows, batches of 8, Pallas in the
interpreter) and draw one library, whose graph is built once for the
module.
"""
import copy
import hashlib
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from bench import check, harness, oracle, spec  # noqa: E402
from bench.library import draw_library  # noqa: E402
from test_bench_harness import shrink  # noqa: E402

from repro.serve import ServiceConfig  # noqa: E402

PAPER_HNSW = {"hnsw_m": 16, "hnsw_ef_construction": 100,
              "hnsw_ef_search": 64}
HNSW_LIMITS = {"misscored": 0, "recall_shortfall": 0.1, "lost": 0,
               "ryw_violations": 0}
E2E = [{"name": "setup_s", "unit": "s"},
       {"name": "screen_compounds_per_s", "unit": "compounds/s"}]


def cell_in(tmp: Path, name: str, base: str = "chembl27-exact",
            traffic: str = "screen", **changes) -> spec.Cell:
    """A cell on a configuration written to ``tmp``: the committed
    configuration ``base`` with ``changes``, as ``serve_cell`` builds its
    cell."""
    b = spec.BENCH_DIR
    cfg = dict(spec.load_json(b / "configs" / f"{base}.json"), name=name,
               **changes)
    path = tmp / f"{name}.json"
    path.write_text(json.dumps(cfg))
    return spec.Cell(name=f"{name}.{traffic}", chips=1, config_name=name,
                     traffic_name=traffic, config=spec.load_json(path),
                     traffic=spec.load_json(b / "traffic" / f"{traffic}.json"),
                     end_to_end=list(E2E))


def hnsw_cell(tmp: Path) -> spec.Cell:
    return shrink(cell_in(tmp, "tiny-hnsw", engine="hnsw",
                          engine_params=PAPER_HNSW, limits=HNSW_LIMITS))


@pytest.fixture(scope="module")
def one_graph():
    """The HNSW build, kept per library and parameters: every run here
    draws the same library, and a 2,000-row build takes ~20 s on a CPU."""
    from repro.core import hnsw as hn
    build, built = hn.build_hnsw, {}

    def cached(db, **kw):
        key = (hashlib.sha256(np.ascontiguousarray(db).tobytes()).hexdigest(),
               repr(sorted(kw.items())))
        if key not in built:
            built[key] = build(db, **kw)
        return copy.deepcopy(built[key])
    mp = pytest.MonkeyPatch()
    mp.setattr(hn, "build_hnsw", cached)
    yield
    mp.undo()


def run(cell: spec.Cell) -> dict:
    return harness.run(cell, seed=2 ** 33, seconds=1.0, trace=False,
                       require_tpu=False)


# -- engine parameters --------------------------------------------------------

def test_engine_params_reach_the_service_config(tmp_path):
    svc = harness.service_config(hnsw_cell(tmp_path))
    assert (svc.hnsw_m, svc.hnsw_ef_construction, svc.hnsw_ef_search) == (
        16, 100, 64)
    assert (svc.k, svc.fp_bits, svc.backend) == (20, 1024, "tpu")


def test_an_unknown_engine_param_is_an_error(tmp_path):
    cell = cell_in(tmp_path, "typo", engine="hnsw",
                   engine_params={"hnsw_ef": 64})
    with pytest.raises(ValueError, match="hnsw_ef"):
        harness.service_config(cell)


@pytest.mark.parametrize("name,fields", [
    ("chembl27-bitbound.screen",
     dict(cutoff=0.8, fold_m=4, fold_scheme=1)),
    ("chembl27-exact.screen", dict(cutoff=0.0, fold_m=1, fold_scheme=1))])
def test_committed_configs_build_the_same_service_config(name, fields):
    cell = spec.resolve(name)
    assert "engine_params" not in cell.config
    assert harness.service_config(cell) == ServiceConfig(
        backend="tpu", metric="tanimoto", fp_bits=1024, k=20,
        max_batch=1024, compact_threshold=4096, residency="device",
        **fields)


# -- the approximate check, one answer at a time -----------------------------

def _answers(seed=3):
    """The reference over 600 rows, six copies each of 100 prints, so that
    scores tie in groups of six."""
    cfg = {"k": 20, "limits": HNSW_LIMITS}
    rng = np.random.default_rng(seed)
    prints = rng.integers(0, 2 ** 32, (100, 32),
                          dtype=np.uint32) & np.uint32(0x11111111)
    lib = rng.permutation(np.repeat(prints, 6, axis=0))
    ref = spec.load_module(spec.BENCH_DIR / "references" / "hnsw.py",
                           "hnsw").Reference(lib, cfg)
    return ref, lib


def _faulty(ids, sims, fault):
    ids, sims = ids.copy(), sims.copy()
    if fault == "ulp":
        sims[3] = np.nextafter(sims[3], np.float32(0))
    elif fault == "twice":
        ids[1], sims[1] = ids[0], sims[0]
    elif fault == "out_of_range":
        ids[-1] = 600
    elif fault == "rising":
        ids[[0, -1]], sims[[0, -1]] = ids[[-1, 0]], sims[[-1, 0]]
    elif fault == "padding_inside":
        ids[5:] = np.r_[-1, ids[5:-1]]
        sims[5:] = np.r_[0, sims[5:-1]]
    elif fault == "short":
        ids, sims = ids[:-1], sims[:-1]
    return ids, sims


@pytest.mark.parametrize("fault", ["ulp", "twice", "out_of_range", "rising",
                                   "padding_inside", "short"])
def test_a_fault_is_misscored(fault):
    ref, lib = _answers()
    q = lib[7]
    ids, sims = ref.answer(q, len(lib))
    assert check.grade(ref, q, ids, sims, len(lib)) == (False, 20, 20)
    assert check.grade(ref, q, *_faulty(ids, sims, fault), len(lib))[0]


def test_recall_counts_ties_at_the_boundary_and_padding_only_at_the_tail():
    ref, lib = _answers()
    q = lib[11]
    wide = type(ref)(lib, {"k": 80})
    ids, sims = wide.answer(q, len(lib))
    kth = sims[19]
    tied = np.flatnonzero(sims == kth)
    assert tied.max() > 19            # rows past rank 20 tie with the 20th
    # an answer of tied rows only at and below the boundary
    pick = np.r_[np.arange(0, 15), tied[-5:]]
    pick = np.sort(np.unique(pick))[:20]
    got = check.grade(ref, q, ids[pick], sims[pick], len(lib))
    assert got == (False, 20, 20)
    # fewer rows than k visible: the tail is padding, every row is a hit
    few = ref.answer(q, 12)
    assert few[0][12] == -1 and check.grade(ref, q, *few, 12) == (
        False, 12, 12)
    assert check.grade(ref, q, *check.degraded(wide, 20, q, len(lib)),
                       len(lib)) == (False, 15, 20)


def test_the_best_visible_prefix_counts():
    ref, lib = _answers()
    q = lib[600 - 1]                  # itself the newest row
    ids, sims = ref.answer(q, 600)
    item = (("lib", 599), ids, sims, 590, 600, None)
    assert check.grade_all(ref, lib, lib, [item]) == (0, 20, 20)
    # at 590 rows the answer holds an id that is not visible yet
    assert check.grade(ref, q, ids, sims, 590)[0]


def test_the_controls_fail_the_approximate_check(tmp_path):
    cell = cell_in(tmp_path, "hnsw-3000", engine="hnsw", n_molecules=3000,
                   limits=HNSW_LIMITS)
    _, library = draw_library(cell.config, 5)
    items = [(int(r), len(library))
             for r in np.random.default_rng(0).integers(0, 3000, 32)]
    Reference = spec.reference_class(cell)
    low = check.control_approximate(Reference, cell.config, library, items)
    assert low["misscored"] == 32
    assert low["degraded_misscored"] == 0
    assert low["recall_shortfall"] == pytest.approx(0.25)
    same = check.control_approximate(Reference, cell.config, library, items,
                                     "f32")
    assert same["misscored"] == 0


# -- whole runs --------------------------------------------------------------

def test_a_tiny_hnsw_run_is_correct(tmp_path, one_graph):
    res = run(hnsw_cell(tmp_path))
    assert res["correct"] is True, res["checks"]
    assert list(res["checks"]) == ["misscored", "recall_shortfall", "lost"]
    assert res["checks"]["misscored"]["value"] == 0
    assert res["checks"]["recall_shortfall"]["value"] <= 0.1
    assert res["attempted"] > 0 and res["failed"] == 0


def _ulp(ids, sims, queries, db):
    sims[:, -1] = np.nextafter(sims[:, -1], np.float32(0))
    return ids, sims


def _twice(ids, sims, queries, db):
    ids[:, 1], sims[:, 1] = ids[:, 0], sims[:, 0]
    return ids, sims


def _swapped(ids, sims, queries, db):
    """Each answer's best quarter swapped for the best rows below the true
    k-th that it lacks, at their true scores."""
    k = ids.shape[1]
    for i, q in enumerate(np.asarray(queries)):
        s = oracle.tanimoto(oracle.intersections(q, db),
                            oracle.popcounts(q[None])[0],
                            oracle.popcounts(db))
        kth = np.sort(s)[::-1][k - 1]
        below = np.flatnonzero((s < kth) & ~np.isin(np.arange(len(db)),
                                                    ids[i]))
        below = below[np.argsort(-s[below], kind="stable")][:k // 4]
        keep = np.r_[ids[i, k // 4:], below]
        order = np.argsort(-s[keep], kind="stable")
        ids[i], sims[i] = keep[order], s[keep][order]
    return ids, sims


@pytest.mark.parametrize("fault,number", [
    (_ulp, "misscored"), (_twice, "misscored"),
    (_swapped, "recall_shortfall")])
def test_a_broken_hnsw_path_is_not_correct(tmp_path, monkeypatch, one_graph,
                                           fault, number):
    from repro.core.engine import HNSWEngine
    search = HNSWEngine.search

    def broken(self, queries, k, *a, **kw):
        ids, sims = search(self, queries, k, *a, **kw)
        return fault(np.array(ids, np.int64), np.array(sims, np.float32),
                     queries, self.index.db)
    monkeypatch.setattr(HNSWEngine, "search", broken)
    res = run(hnsw_cell(tmp_path))
    assert res["correct"] is False
    got = res["checks"][number]
    assert got["value"] > got["limit"]
    if number == "recall_shortfall":
        assert res["checks"]["misscored"]["value"] == 0
        assert got["value"] >= 0.25


def test_the_exact_path_checks_as_before(tmp_path):
    cell = shrink(cell_in(tmp_path, "tiny-brute"))
    assert not check.approximate(cell.config)
    res = run(cell)
    assert res["correct"] is True
    assert res["checks"] == {"mismatched": {"value": 0, "limit": 0},
                             "lost": {"value": 0, "limit": 0}}
