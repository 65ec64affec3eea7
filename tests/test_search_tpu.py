"""Cross-engine parity of the device-resident two-stage path (`search_tpu`)
against the host-side numpy reference (`search_numpy`).

The reference breaks ties deterministically (stable sort, ascending
sorted-row index) — the same order the device kernel's position-stable
``top_k`` produces — so the m=1 pure-BitBound case must match bit-for-bit.
With folding (m>1) stage-1 float ordering may legitimately differ between the
float32 kernel and the float64 host loop, so the contract is recall parity
against brute-force ground truth.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BitBoundFoldingEngine, BruteForceEngine, recall_at_k


@pytest.mark.parametrize("backend", ["tpu", "jnp"])
@pytest.mark.parametrize("cutoff", [0.2, 0.6, 0.8])
def test_m1_exact_parity(small_db, queries, backend, cutoff):
    """m=1 (pure BitBound): ids AND sims match the numpy reference exactly."""
    ref = BitBoundFoldingEngine(small_db, cutoff=cutoff, m=1)
    dev = BitBoundFoldingEngine(small_db, cutoff=cutoff, m=1, backend=backend)
    rids, rsims = ref.search(queries, 20)
    dids, dsims = dev.search(queries, 20)
    np.testing.assert_array_equal(rids, dids)
    np.testing.assert_array_equal(rsims, dsims)
    assert ref.scanned(len(queries)) == dev.scanned(len(queries))


@pytest.mark.parametrize("m", [2, 4])
@pytest.mark.parametrize("cutoff", [0.6, 0.8])
def test_folded_recall_at_least_reference(small_db, queries, brute_truth,
                                          m, cutoff):
    """m>1: the device path's recall vs brute-force ground truth must be at
    least the numpy reference's (same candidate windows, same k_r1)."""
    _, true_ids = brute_truth
    ref = BitBoundFoldingEngine(small_db, cutoff=cutoff, m=m)
    dev = BitBoundFoldingEngine(small_db, cutoff=cutoff, m=m, backend="tpu")
    rids, _ = ref.search(queries, 20)
    dids, _ = dev.search(queries, 20)
    # one-hit tolerance: fp32 stage-1 ordering on a real TPU may legitimately
    # swap a candidate exactly at the k_r1 boundary vs the fp64 host loop
    assert recall_at_k(dids, true_ids) >= (recall_at_k(rids, true_ids)
                                           - 1.0 / true_ids.size)


def test_search_tpu_returns_device_arrays(small_db, queries):
    """The device path returns jax arrays (no forced host round-trip) and
    reports the scanned-candidate count as the reference does."""
    eng = BitBoundFoldingEngine(small_db, cutoff=0.6, m=2, backend="tpu")
    ids, sims, scanned = eng.search_tpu(queries, 10)
    assert isinstance(ids, jax.Array)
    assert isinstance(sims, jax.Array)
    assert isinstance(scanned, jax.Array)
    assert ids.shape == (len(queries), 10) and sims.shape == ids.shape
    ref = BitBoundFoldingEngine(small_db, cutoff=0.6, m=2)
    ref.search(queries, 10)
    assert int(scanned) == ref.scanned(len(queries))


def test_search_tpu_compilation_is_bucketed(small_db, queries):
    """Repeated searches reuse one compiled pipeline per (bucket, k): no
    per-query or per-batch recompilation."""
    eng = BitBoundFoldingEngine(small_db, cutoff=0.6, m=2, backend="tpu")
    eng.search(queries, 10)
    eng.search(queries, 10)
    eng.search(queries[:8], 10)   # same bucket, different batch shape
    assert len(eng._stage1_cache) == 1
    eng.search(queries, 5)        # new k -> one more pipeline
    assert len(eng._stage1_cache) == 2


def test_scheme2_device_path(small_db, queries):
    """Adjacent-OR folding also runs on device (jax scheme-2 query fold)."""
    eng = BitBoundFoldingEngine(small_db, cutoff=0.0, m=8, scheme=2,
                                backend="tpu")
    ids, sims = eng.search(queries, 5)
    assert (sims[:, 0] >= 1.0 - 1e-6).all()   # self-queries always found


def test_backend_selector_validation(small_db):
    with pytest.raises(ValueError):
        BitBoundFoldingEngine(small_db, backend="fpga")
    with pytest.raises(ValueError):
        BruteForceEngine(small_db, backend="numpy")
    # legacy flag maps onto the selector
    assert BruteForceEngine(small_db, use_kernel=True).backend == "tpu"
    assert BitBoundFoldingEngine(small_db).backend == "numpy"


def test_high_cutoff_empty_windows(small_db):
    """Queries whose Eq.2 window is empty come back id -1 / sim 0 on both
    paths (the all-zero query is the extreme case)."""
    q = np.zeros((2, small_db.shape[1]), dtype=np.uint32)
    q[1] = small_db[0]
    ref = BitBoundFoldingEngine(small_db, cutoff=0.95, m=1)
    dev = BitBoundFoldingEngine(small_db, cutoff=0.95, m=1, backend="tpu")
    rids, rsims = ref.search(q, 10)
    dids, dsims = dev.search(q, 10)
    np.testing.assert_array_equal(rids, dids)
    np.testing.assert_array_equal(rsims, dsims)


def _unsorted_batch(db, n, seed):
    from repro.data.molecules import queries_from_db
    qs = queries_from_db(db, n, seed=seed)
    a = np.bitwise_count(qs).sum(-1).astype(np.int64)
    assert (np.diff(a) < 0).any() and (np.diff(a) > 0).any()
    return qs


@pytest.mark.parametrize("with_delta", [False, True])
@pytest.mark.parametrize("m", [1, 4])
def test_unsorted_batch_bitwise_parity(small_db, m, with_delta):
    """Stage 1 scans the batch in popcount order, a block of queries to a
    tile stream; every answer still equals the numpy reference's bit for
    bit, in the callers' order, main segment alone or with a delta."""
    main, extra = small_db[:1800], small_db[1800:]
    qs = _unsorted_batch(small_db, 20, seed=11)
    ref = BitBoundFoldingEngine(main, cutoff=0.6, m=m)
    dev = BitBoundFoldingEngine(main, cutoff=0.6, m=m, backend="tpu")
    if with_delta:
        ref.insert(extra)
        dev.insert(extra)
    rids, rsims = ref.search(qs, 20)
    dids, dsims = dev.search(qs, 20)
    assert (dev._delta_dev is not None) == with_delta
    np.testing.assert_array_equal(rids, dids)
    np.testing.assert_array_equal(rsims, dsims)
    assert ref.scanned(len(qs)) == dev.scanned(len(qs))


def _far_apart_batch(db):
    """Queries from both popcount tails: at cutoff 0.95 each window is
    one tile of an 8,192-row library, but their block spans the library."""
    order = np.argsort(np.bitwise_count(db).sum(-1), kind="stable")
    rows = np.concatenate([order[-6:], order[:6]])
    return db[np.random.default_rng(0).permutation(rows)]


@pytest.mark.parametrize("m", [1, 4])
def test_wide_block_union_bitwise_parity(m):
    """A block whose union is far wider than any of its windows: the grid
    grows to cover it, and every answer still equals the numpy
    reference's bit for bit."""
    from repro.core import bitbound as bb
    from repro.data.molecules import SyntheticConfig, synthetic_fingerprints
    from repro.kernels import ops
    db = synthetic_fingerprints(SyntheticConfig(n=8000, seed=3))
    qs = _far_apart_batch(db)
    ref = BitBoundFoldingEngine(db, cutoff=0.95, m=m)
    dev = BitBoundFoldingEngine(db, cutoff=0.95, m=m, backend="tpu")
    meta = dev._device_meta()
    lo, hi, _, bucket, _, _ = dev._batch_plan(qs)
    single = ops.window_blocks(lo, hi, 1, meta["tile"])[1]
    assert bucket > bb.bucket_tiles(single.max(), meta["total_tiles"])
    rids, rsims = ref.search(qs, 20)
    dids, dsims = dev.search(qs, 20)
    np.testing.assert_array_equal(rids, dids)
    np.testing.assert_array_equal(rsims, dsims)


def _check_plan(eng, qs):
    """The plan's invariants for one batch."""
    from repro.core import bitbound as bb
    from repro.kernels import ops
    tile = eng._device_meta()["tile"]
    total = eng._device_meta()["total_tiles"]
    lo, hi, perm, bucket, _, _ = eng._batch_plan(qs)
    q_n = len(qs)
    a = np.bitwise_count(qs).sum(-1).astype(np.int64)
    assert sorted(perm.tolist()) == list(range(q_n))
    key = list(zip(a[perm].tolist(), perm.tolist()))
    assert key == sorted(key)                 # popcount, then position
    qb = ops.window_block(q_n)
    single = ops.window_blocks(lo, hi, 1, tile)[1]
    unions = []
    for b in range(0, q_n, qb):
        rows = [r for i in perm[b:b + qb] for r in range(lo[i], hi[i])]
        if rows:
            unions.append(max(rows) // tile - min(rows) // tile + 1)
    assert bucket == bb.bucket_tiles(max(unions), total)
    assert eng.stage1_tiles() == (int(single.sum()), sum(unions))
    assert 0 < sum(unions) <= single.sum()


def test_batch_plan_covers_every_block_union():
    """The blocks hold the queries in a stable popcount order, the grid
    bucket is that of the widest block union, and a query alone streams
    exactly its window."""
    from repro.data.molecules import SyntheticConfig, synthetic_fingerprints
    db = synthetic_fingerprints(SyntheticConfig(n=20000, seed=3))
    eng = BitBoundFoldingEngine(db, cutoff=0.8, m=4, backend="tpu")
    for q_n in (5, 20, 64):
        _check_plan(eng, _unsorted_batch(db, q_n, seed=q_n))
    _check_plan(eng, db[:1])
    window, streamed = eng.stage1_tiles()
    assert window == streamed


def test_window_blocks_cover_live_windows():
    """Each block's tile window is the union of its non-empty row windows;
    the batch is padded to a block multiple, and a block of empty windows
    scans nothing."""
    from repro.kernels import ops
    lo = np.array([5, 30, 50, 70, 0, 90, 0, 0, 0, 0])
    hi = np.array([15, 41, 50, 60, 0, 95, 0, 0, 0, 0])
    lo_t, n_t = ops.window_blocks(lo, hi, 4, tile=10)
    # blocks: rows [5, 41) -> tiles 0..4; [90, 95) -> tile 9; nothing
    assert lo_t.tolist() == [0, 9, 0]
    assert n_t.tolist() == [5, 1, 0]
    assert ops.window_block(1) == 1
    assert ops.window_block(20) == 20
    assert ops.window_block(1024) == ops.WINDOW_QB == 32
