"""The comparison that decides ``correct`` fails what it must.

- The control: the plain reference put in the program's place and computed
  in bfloat16, the precision below the configurations' f32 scores, is held
  to the f32 reference as the program is, and misses.
- Runs with the timed path broken underneath (the harness's look for a chip
  skipped, the rest of a run driven as it is) come out not correct: an
  answer altered where the engine produces it, and an acknowledged insert
  the engine does not see.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

from bench import check, spec  # noqa: E402
from bench.library import draw_library  # noqa: E402
from test_bench_harness import run_tiny  # noqa: E402


@pytest.mark.parametrize("name", ["chembl27-bitbound.screen",
                                  "chembl27-exact.screen"])
def test_control_in_bf16_fails(name):
    cell = spec.resolve(name)
    cfg = dict(cell.config, n_molecules=3000)
    _, library = draw_library(cfg, 5)
    items = [(int(r), len(library))
             for r in np.random.default_rng(0).integers(0, 3000, 32)]
    Reference = spec.reference_class(cell)
    low = check.control_mismatches(Reference, cfg, library, items, "bf16")
    same = check.control_mismatches(Reference, cfg, library, items, "f32")
    assert same == 0
    assert low >= 16, low            # limit 0: any miss fails the run


def _alter(monkeypatch, cls):
    """Every answer's last score one ulp up, where the engine makes it."""
    search = cls.search

    def altered(self, queries, k):
        ids, sims = search(self, queries, k)
        sims = np.array(sims, np.float32)
        sims[:, -1] = np.nextafter(sims[:, -1], np.float32(2))
        return np.asarray(ids), sims
    monkeypatch.setattr(cls, "search", altered)


@pytest.mark.parametrize("name,engine", [
    ("chembl27-bitbound.screen", "BitBoundFoldingEngine"),
    ("chembl27-exact.screen", "BruteForceEngine"),
    ("chembl27-bitbound.serve", "BitBoundFoldingEngine")])
def test_an_altered_answer_is_not_correct(monkeypatch, name, engine):
    from repro.core import engine as eng
    _alter(monkeypatch, getattr(eng, engine))
    res = run_tiny(name)
    assert res["correct"] is False
    assert res["checks"]["mismatched"]["value"] > 0


def test_an_unseen_insert_is_not_correct(monkeypatch):
    from repro.core.engine import BitBoundFoldingEngine
    sync = BitBoundFoldingEngine._sync

    def stale(self):
        # acknowledge writes without moving them to the device
        if self._sync_delta is not None:
            self._sync_delta = self.store.delta_version
        sync(self)
    monkeypatch.setattr(BitBoundFoldingEngine, "_sync", stale)
    res = run_tiny("chembl27-bitbound.serve")
    assert res["correct"] is False
    assert res["checks"]["ryw_violations"]["value"] > 0
