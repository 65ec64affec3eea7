"""The benchmark's own library generator, pinned by a digest.

The pin is on the generator's own output, not a comparison with the
program's generator, so no change to the program can fail it.
"""
import hashlib
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec  # noqa: E402
from bench.library import Library, draw_library, seed_rng  # noqa: E402

CFG = spec.load_json(spec.BENCH_DIR / "configs" / "chembl27-bitbound.json")

#: sha256 of the 2,000-row library of seed 0 and of 16 molecules of its
#: insert stream
LIBRARY_2000 = \
    "4d1501105b3c2adb73071e93cf284ed13ad4bbedc9a637de84800182751ff18e"
INSERTS_16 = \
    "97d1e40264d5daccd6913aca4eff3a9ff9b204ea8f0c21ffc8ab3500be3ab26b"


def digest(rows):
    return hashlib.sha256(np.ascontiguousarray(rows).tobytes()).hexdigest()


def test_library_digest():
    lib, rows = draw_library(dict(CFG, n_molecules=2000), 0)
    assert rows.shape == (2000, 32) and rows.dtype == np.uint32
    assert digest(rows) == LIBRARY_2000
    assert digest(lib.molecules(16, stream=1)) == INSERTS_16


def test_library_follows_the_model():
    _, rows = draw_library(dict(CFG, n_molecules=4000), 7)
    pc = np.bitwise_count(rows).sum(-1)
    assert pc.min() >= 8 and pc.max() <= 256
    assert 58 < pc.mean() < 72 and 14 < pc.std() < 26
    # scaffold structure: most molecules have a near neighbour
    q = rows[:50]
    inter = np.bitwise_count(q[:, None] & rows[None]).sum(-1)
    union = pc[:50, None] + pc[None] - inter
    sim = np.sort(inter / union, axis=1)[:, -2]
    assert np.median(sim) > 0.3


def test_seeds_wider_than_32_bits():
    cfg = dict(CFG, n_molecules=300)
    a = Library(cfg, 300, 2 ** 33 + 1).molecules(300, 0)
    b = Library(cfg, 300, 2 ** 33 + 1).molecules(300, 0)
    c = Library(cfg, 300, 1).molecules(300, 0)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert seed_rng(2 ** 40, 3).integers(1 << 30) == \
        seed_rng(2 ** 40, 3).integers(1 << 30)
