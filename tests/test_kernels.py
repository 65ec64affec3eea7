"""Per-kernel allclose sweeps against the pure-jnp oracle (ref.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitbound as bb
from repro.core.fingerprints import popcount
from repro.data.molecules import SyntheticConfig, synthetic_fingerprints, queries_from_db
from repro.kernels import ops, ref


def _db(n, seed=0, length=1024):
    return synthetic_fingerprints(SyntheticConfig(n=n, seed=seed, length=length))


def _bitbound_topk(queries, db_sorted, counts_sorted, k, cutoff,
                   max_tiles=None, tile_n=None):
    """BitBound-windowed fused KNN over a popcount-sorted DB: the Eq.2 rows
    every engine uses (``bound_range_np``), scanned by the row-window
    kernel. Ids index the sorted DB; empty slots are id -1 / val -inf."""
    counts = np.asarray(counts_sorted)
    lo, hi = bb.bound_range_np(counts, np.asarray(popcount(queries)), cutoff)
    return ops.window_topk(queries, db_sorted, counts, lo, hi, k,
                           max_tiles=max_tiles, tile_n=tile_n)


@pytest.mark.parametrize("n,q,k,tile", [
    (1000, 3, 5, 128),
    (2048, 2, 20, 512),
    (5000, 4, 100, 2048),   # k > tile-boundary interactions
    (300, 2, 10, 128),      # padded final tile
    (130, 1, 64, 128),      # k close to n, single tile + pad
])
def test_fused_topk_matches_oracle(n, q, k, tile):
    db = jnp.asarray(_db(n))
    qs = jnp.asarray(queries_from_db(np.asarray(db), q))
    ids, vals = ops.tanimoto_topk(qs, db, k=k, tile_n=tile)
    rids, rvals = ref.tanimoto_topk_ref(qs, db, k=k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)
    # ids may differ between tied scores; returned ids must realise the values
    s = np.asarray(ref.tanimoto_scores_ref(qs, db))
    got = s[np.arange(q)[:, None], np.asarray(ids)]
    np.testing.assert_allclose(got, np.asarray(rvals), rtol=1e-6)


@pytest.mark.parametrize("length", [256, 512, 1024])
def test_fused_topk_fp_lengths(length):
    """Folded databases have shorter word counts — sweep W."""
    db = jnp.asarray(_db(1500, length=length))
    qs = jnp.asarray(queries_from_db(np.asarray(db), 3))
    ids, vals = ops.tanimoto_topk(qs, db, k=10, tile_n=256)
    _, rvals = ref.tanimoto_topk_ref(qs, db, k=10)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)


@pytest.mark.parametrize("cutoff,tile", [(0.2, 128), (0.4, 512), (0.8, 256),
                                         (0.95, 128)])
def test_bitbound_kernel_matches_oracle(cutoff, tile):
    db = _db(3000, seed=1)
    qs = jnp.asarray(queries_from_db(db, 4))
    idx = bb.build_index(jnp.asarray(db))
    ids, vals = _bitbound_topk(qs, idx.db, idx.counts, k=15, cutoff=cutoff,
                               tile_n=tile)
    rids, rvals = ref.bitbound_topk_ref(qs, idx.db, idx.counts, k=15,
                                        cutoff=cutoff)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)
    # invalid entries agree
    np.testing.assert_array_equal(np.asarray(ids) < 0, np.asarray(rids) < 0)


def test_bitbound_kernel_restricted_window_grid():
    """max_tiles below the full DB: still exact when the block's windows
    fit. The grid is sized as the engine sizes it, to the power-of-two
    bucket of the block's union of Eq.2 windows."""
    db = _db(4096, seed=2)
    idx = bb.build_index(jnp.asarray(db))
    counts = np.asarray(idx.counts)
    qs = idx.db[jnp.asarray([800, 500, 650])]       # unsorted popcounts
    lo, hi = bb.bound_range_np(counts, np.asarray(popcount(qs)), 0.8)
    _, n_t = ops.window_blocks(lo, hi, ops.window_block(3), 256)
    max_tiles = bb.bucket_tiles(n_t.max(), 16)
    assert max_tiles < 16
    ids, vals = _bitbound_topk(qs, idx.db, idx.counts, k=10, cutoff=0.8,
                               tile_n=256, max_tiles=max_tiles)
    rids, rvals = ref.bitbound_topk_ref(qs, idx.db, idx.counts, k=10,
                                        cutoff=0.8)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))


# -- BitBound kernel edge cases (interpret mode vs ref.py) -------------------

def test_bitbound_kernel_empty_window():
    """A sparse query at a high cutoff has an empty Eq.2 window: every slot
    must come back id -1 / val -inf, exactly like the oracle."""
    db = _db(1000, seed=5)
    idx = bb.build_index(jnp.asarray(db))
    q = np.zeros((1, db.shape[1]), dtype=np.uint32)
    q[0, 0] = 0b11    # popcount 2 -> window is popcount {2} only
    assert not (np.asarray(idx.counts) == 2).any()
    qs = jnp.asarray(q)
    ids, vals = _bitbound_topk(qs, idx.db, idx.counts, k=8, cutoff=0.9,
                               tile_n=128)
    rids, rvals = ref.bitbound_topk_ref(qs, idx.db, idx.counts, k=8,
                                        cutoff=0.9)
    assert (np.asarray(ids) == -1).all()
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))


def test_bitbound_kernel_window_smaller_than_k():
    """Eq.2 window holds fewer candidates than k: the valid prefix matches
    the oracle and the tail is -1 / -inf on both."""
    db = _db(2000, seed=6)
    idx = bb.build_index(jnp.asarray(db))
    counts = np.asarray(idx.counts)
    # query popcount = rarest count value -> tiny window at cutoff ~1
    vals_u, freq = np.unique(counts, return_counts=True)
    rare = int(vals_u[np.argmin(freq)])
    q_bits = np.zeros((1, db.shape[1] * 32), dtype=np.uint8)
    q_bits[0, :rare] = 1
    from repro.core import pack_bits
    qs = jnp.asarray(pack_bits(q_bits))
    k = int(freq.min()) + 10
    ids, vals = _bitbound_topk(qs, idx.db, idx.counts, k=k, cutoff=0.999,
                               tile_n=256)
    rids, rvals = ref.bitbound_topk_ref(qs, idx.db, idx.counts, k=k,
                                        cutoff=0.999)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ids) < 0, np.asarray(rids) < 0)
    assert (np.asarray(ids)[0, -1] == -1)   # tail really is unfilled


def test_bitbound_kernel_all_zero_query():
    """Popcount-0 query: window is the popcount-0 rows; Tanimoto with an
    empty print is defined as 0, never NaN/inf."""
    db = _db(1500, seed=7)
    db[:3] = 0    # make the zero-count window non-empty
    idx = bb.build_index(jnp.asarray(db))
    qs = jnp.zeros((2, db.shape[1]), dtype=jnp.uint32)
    ids, vals = _bitbound_topk(qs, idx.db, idx.counts, k=5, cutoff=0.8,
                               tile_n=128)
    rids, rvals = ref.bitbound_topk_ref(qs, idx.db, idx.counts, k=5,
                                        cutoff=0.8)
    assert not np.isnan(np.asarray(vals)).any()
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(ids) < 0, np.asarray(rids) < 0)
    # the zero rows are legitimate window members with similarity 0
    assert (np.asarray(ids)[:, :3] >= 0).all()


def test_bitbound_kernel_non_tile_aligned_n():
    """N not a multiple of the tile: padded tail rows must never appear."""
    db = _db(3001, seed=8)
    idx = bb.build_index(jnp.asarray(db))
    qs = jnp.asarray(queries_from_db(db, 4))
    ids, vals = _bitbound_topk(qs, idx.db, idx.counts, k=12, cutoff=0.5,
                               tile_n=256)
    rids, rvals = ref.bitbound_topk_ref(qs, idx.db, idx.counts, k=12,
                                        cutoff=0.5)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)
    assert (np.asarray(ids) < 3001).all()


# -- row-window kernel (stage 1 of the device two-stage engine) --------------

def test_window_kernel_matches_oracle():
    """Per-query row windows incl. empty, tiny, full-DB and tail windows."""
    db = _db(3000, seed=3)
    qs = jnp.asarray(queries_from_db(db, 5))
    idx = bb.build_index(jnp.asarray(db))
    lo = jnp.asarray([100, 500, 700, 0, 2999], jnp.int32)
    hi = jnp.asarray([2500, 500, 705, 3000, 3000], jnp.int32)
    ids, vals = ops.window_topk(qs, idx.db, idx.counts, lo, hi, k=10,
                                tile_n=256)
    rids, rvals = ref.window_topk_ref(qs, idx.db, idx.counts, lo, hi, k=10)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(ids) < 0, np.asarray(rids) < 0)


@pytest.mark.parametrize("case", ["block", "per_query"])
def test_window_kernel_restricted_max_tiles(case):
    """Static grid smaller than the full DB stays exact when each block's
    windows fit. ``block``: three queries are one block, whose union, rows
    [0, 1200), is 5 tiles of 256. ``per_query``: disjoint windows spanning
    16 tiles together; each query alone is a block of one and needs only
    its own window (at most 5 tiles) to fit."""
    db = _db(4096, seed=9)
    qs = jnp.asarray(queries_from_db(db, 3))
    idx = bb.build_index(jnp.asarray(db))
    if case == "block":
        lo = jnp.asarray([0, 300, 600], jnp.int32)
        hi = jnp.asarray([900, 1100, 1200], jnp.int32)
        ids, vals = ops.window_topk(qs, idx.db, idx.counts, lo, hi, k=7,
                                    tile_n=256, max_tiles=5)
    else:
        lo = jnp.asarray([0, 1000, 3000], jnp.int32)
        hi = jnp.asarray([900, 2000, 4096], jnp.int32)
        got = [ops.window_topk(qs[i:i + 1], idx.db, idx.counts,
                               lo[i:i + 1], hi[i:i + 1], k=7, tile_n=256,
                               max_tiles=5) for i in range(3)]
        ids = jnp.concatenate([g[0] for g in got])
        vals = jnp.concatenate([g[1] for g in got])
    rids, rvals = ref.window_topk_ref(qs, idx.db, idx.counts, lo, hi, k=7)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))


def test_window_kernel_folded_db_full_bounds():
    """The two-stage wiring: bounds from full-resolution popcounts, scores on
    the folded DB — the kernel must not consult folded popcounts for the
    window."""
    from repro.core import folding as fl
    db = _db(2000, seed=10)
    idx = bb.build_index(jnp.asarray(db))
    folded = jnp.asarray(fl.fold(np.asarray(idx.db), 4, 1))
    qs = jnp.asarray(fl.fold(queries_from_db(db, 3), 4, 1))
    lo = jnp.asarray([0, 600, 1990], jnp.int32)
    hi = jnp.asarray([500, 1400, 2000], jnp.int32)
    from repro.core.fingerprints import popcount
    fcnt = popcount(folded)
    ids, vals = ops.window_topk(qs, folded, fcnt, lo, hi, k=9, tile_n=128)
    rids, rvals = ref.window_topk_ref(qs, folded, fcnt, lo, hi, k=9)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)
    got = np.asarray(ids)
    for qi in range(3):
        ok = got[qi] >= 0
        assert (got[qi][ok] >= int(lo[qi])).all()
        assert (got[qi][ok] < int(hi[qi])).all()


def test_bitcount_kernel_sweep():
    for n, w in [(100, 8), (4096, 32), (5000, 16)]:
        rng = np.random.default_rng(n)
        words = jnp.asarray(rng.integers(0, 2**32, (n, w), dtype=np.uint32))
        got = np.asarray(ops.bitcount(words))
        np.testing.assert_array_equal(got, np.asarray(ref.bitcount_ref(words)))


def test_kernel_engine_integration(small_db, queries, brute_truth):
    """BruteForceEngine(use_kernel=True) == oracle top-k."""
    from repro.core import BruteForceEngine
    s, true_ids = brute_truth
    eng = BruteForceEngine(jnp.asarray(small_db), use_kernel=True)
    ids, vals = eng.search(queries, 20)
    expect = np.take_along_axis(s, true_ids, axis=1)
    np.testing.assert_allclose(vals, expect, rtol=1e-6)


@pytest.mark.parametrize("n,q,k,qb,tile", [
    (2000, 16, 10, 8, 256),
    (1500, 5, 20, 4, 512),     # Q padded up to qb multiple
    (4096, 32, 5, 16, 1024),
])
def test_blocked_topk_matches_oracle(n, q, k, qb, tile):
    """Query-blocked engine (one DB sweep per qb queries) stays exact."""
    db = jnp.asarray(_db(n, seed=4))
    qs = jnp.asarray(queries_from_db(np.asarray(db), q))
    ids, vals = ops.tanimoto_topk(qs, db, k=k, qb=qb, tile_n=tile)
    _, rvals = ref.tanimoto_topk_ref(qs, db, k=k)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(rvals), rtol=1e-6)
    s = np.asarray(ref.tanimoto_scores_ref(qs, db))
    got = s[np.arange(q)[:, None], np.asarray(ids)]
    np.testing.assert_allclose(got, np.asarray(rvals), rtol=1e-6)


# -- Mosaic layout edge cases (interpret mode, bit-for-bit vs ref.py) ---------

def _tied_db(n, seed):
    """A library with exact duplicate rows across tile boundaries, so equal
    scores straddle tiles and the kernels' tie order is exercised."""
    db = _db(n, seed=seed)
    db[n // 2:n // 2 + 40] = db[:40]
    db[n - 30:] = db[5:35]
    return db


@pytest.mark.parametrize("q,k,qb", [
    (13, 20, 8),      # Q not a multiple of 8 -> padded query block
    (5, 150, 8),      # k past one 128-lane row -> two lanes rows of k
    (9, 7, 16),       # a 16-query block holding all 9 queries
])
def test_fused_topk_layout_cases_bitwise(q, k, qb):
    db = _tied_db(1500, seed=11)
    qs = jnp.asarray(np.concatenate([db[:3], queries_from_db(db, q - 3)]))
    ids, vals = ops.tanimoto_topk(qs, jnp.asarray(db), k=k, tile_n=256,
                                  qb=qb)
    rids, rvals = ref.tanimoto_topk_ref(qs, jnp.asarray(db), k=k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))


def _layout_windows(case: str, q: int, rng):
    """Row windows over 2,000 rows for the window-kernel layout cases."""
    lo = rng.integers(0, 1500, q)
    hi = np.minimum(lo + rng.integers(0, 2000, q), 2000)
    if case == "disjoint":     # neighbours alternate between the two ends
        lo = np.where(np.arange(q) % 2 == 0, 0, 1700) + rng.integers(0, 50, q)
        hi = lo + rng.integers(1, 250, q)
    elif case == "empty":      # every third window empty, some with hi < lo
        hi[::3] = lo[::3] - rng.integers(0, 40, len(lo[::3]))
    elif case == "wide_union":  # windows of <= 3 tiles whose block spans 16
        lo = (np.arange(q) * 250) % 1800
        hi = lo + 200
    return lo, hi


@pytest.mark.parametrize("q,k,case", [
    pytest.param(11, 20, "random", id="11-20"),
    pytest.param(3, 130, "random", id="3-130"),
    (1, 20, "random"),         # one query: a one-row block
    (5, 20, "random"),         # below the sublane tile: one 5-row block
    (8, 20, "random"),         # exactly one sublane tile
    (9, 20, "random"),         # one past it: one 9-row block
    (20, 20, "random"),        # one 20-row block, not a multiple of 8
    (32, 20, "random"),        # exactly one full block
    (40, 20, "random"),        # two 32-row blocks, the second padded
    (9, 20, "disjoint"),       # unsorted, disjoint windows in one block
    (40, 20, "empty"),         # empty windows mixed into both blocks
    (16, 20, "wide_union"),    # block union needs a larger bucket
])
def test_window_topk_layout_cases_bitwise(q, k, case):
    """Bitwise equal to the oracle with the grid sized as the engine sizes
    it: the power-of-two bucket of the largest block union, in tiles."""
    db = _tied_db(2000, seed=12)
    idx = bb.build_index(jnp.asarray(db))
    qs = jnp.asarray(queries_from_db(db, q, seed=4))
    lo, hi = _layout_windows(case, q, np.random.default_rng(q))
    _, n_t = ops.window_blocks(lo, hi, ops.window_block(q), 128)
    max_tiles = bb.bucket_tiles(n_t.max(), 16)
    if case == "wide_union":
        single = np.where(hi > lo, (hi + 127) // 128 - lo // 128, 0)
        assert max_tiles > bb.bucket_tiles(single.max(), 16)
    ids, vals = ops.window_topk(qs, idx.db, idx.counts, jnp.asarray(lo),
                                jnp.asarray(hi), k=k, tile_n=128,
                                max_tiles=max_tiles)
    rids, rvals = ref.window_topk_ref(qs, idx.db, idx.counts,
                                      jnp.asarray(lo), jnp.asarray(hi), k=k)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))


def test_bitbound_topk_ties_bitwise():
    db = _tied_db(2500, seed=13)
    idx = bb.build_index(jnp.asarray(db))
    qs = jnp.asarray(queries_from_db(db, 10, seed=5))
    ids, vals = _bitbound_topk(qs, idx.db, idx.counts, k=25, cutoff=0.6,
                               tile_n=128)
    rids, rvals = ref.bitbound_topk_ref(qs, idx.db, idx.counts, k=25,
                                        cutoff=0.6)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(rids))


def test_gather_layout_cases_bitwise():
    """Q off the sublane grid, E off the lane grid, repeated and invalid
    ids, and ids past the end (scored as the last row, like the oracle)."""
    db = jnp.asarray(_tied_db(700, seed=14))
    rng = np.random.default_rng(14)
    ids = rng.integers(-1, 700, (11, 37)).astype(np.int32)
    ids[:, 3] = ids[:, 4]
    ids[0, 0] = 900
    got = ops.gather_tanimoto(db[:11], ops.db_lines(db), jnp.asarray(ids))
    want = ref.gather_tanimoto_ref(db[:11], db, jnp.asarray(ids))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("n,w", [(700, 32), (9, 32), (301, 7), (5, 128)])
def test_line_layouts_hold_each_print_once(n, w):
    """db_lines/nbr_slabs: each print sits in its own wp-word group, zero
    padded; numpy and jax inputs give the same lines."""
    rng = np.random.default_rng(n)
    db = rng.integers(0, 2**32, (n, w), dtype=np.uint32)
    lines = ops.db_lines(db)
    assert isinstance(lines, np.ndarray) and lines.shape[1] == 128
    wp = 1 << (w - 1).bit_length()          # words per print in a line
    back = lines.reshape(-1, wp)
    np.testing.assert_array_equal(back[:n, :w], db)
    assert not back[:n, w:].any() and not back[n:].any()
    np.testing.assert_array_equal(np.asarray(ops.db_lines(jnp.asarray(db))),
                                  lines)
    nbr = db[rng.integers(0, n, (3, 10))]
    slabs = ops.nbr_slabs(nbr)
    assert slabs.shape[0] == 3 and slabs.shape[1] % 8 == 0
    np.testing.assert_array_equal(slabs.reshape(3, -1, wp)[:, :10, :w], nbr)


@pytest.mark.parametrize("backend,platforms,tpu_runtime,want", [
    ("tpu", None, True, False),
    ("cpu", "cpu", True, True),          # the tests: CPU asked for
    ("cpu", None, False, True),          # a host with no TPU runtime
    ("cpu", None, True, None),           # the TPU failed to start
    ("cpu", "tpu,cpu", True, None),      # fell back past the TPU
    ("gpu", None, False, None),
])
def test_interpret_only_where_cpu_was_asked_for(backend, platforms,
                                                tpu_runtime, want):
    if want is None:
        with pytest.raises(RuntimeError, match="need a TPU"):
            ops.interpret_on(backend, platforms, tpu_runtime)
    else:
        assert ops.interpret_on(backend, platforms, tpu_runtime) is want
