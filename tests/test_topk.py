"""Register-array priority queue invariants + streaming top-k properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # collection-safe fallback (see tests/_propcheck.py)
    from _propcheck import given, settings, strategies as st

from repro.core.topk import (NEG_INF, PQ, merge_sorted, merge_sorted_many,
                             pq_insert, pq_insert_batch, pq_make, pq_pop,
                             pq_pop_many, pq_worst, streaming_topk)

# subnormals are flushed to zero on device; real scores are >= 1/2048 or 0
floats = st.floats(-1e6, 1e6, allow_nan=False, allow_subnormal=False,
                   width=32)


@given(st.lists(floats, min_size=1, max_size=300), st.integers(1, 16),
       st.sampled_from([4, 16, 64]))
@settings(max_examples=60, deadline=None)
def test_streaming_topk_matches_sort(xs, k, tile):
    scores = jnp.asarray(np.asarray(xs, np.float32))
    vals, idxs = streaming_topk(scores, k, tile=tile)
    vals, idxs = np.asarray(vals), np.asarray(idxs)
    expect = np.sort(np.asarray(xs, np.float32))[::-1][:k]
    got = vals[:min(k, len(xs))]
    np.testing.assert_allclose(got, expect[:len(got)], rtol=1e-6)
    # returned indices actually point at the returned values
    for v, i in zip(vals, idxs):
        if i >= 0 and np.isfinite(v):
            assert abs(xs[i] - v) < 1e-3


@given(st.lists(st.tuples(floats, st.integers(0, 10_000)), min_size=1,
                max_size=60), st.integers(1, 12))
@settings(max_examples=60, deadline=None)
def test_pq_invariants(items, cap):
    """Sorted order, fixed capacity, evict-worst: the queue always holds
    exactly the best <= cap entries seen so far, descending."""
    pq = pq_make(cap)
    for n_seen, (s, pay) in enumerate(items, start=1):
        pq = pq_insert(pq, jnp.float32(s), jnp.int32(pay))
        scores = np.asarray(pq.scores)
        assert scores.shape == (cap,)                       # fixed shape
        valid = scores[np.isfinite(scores)]
        assert (np.diff(valid) <= 1e-6).all()               # sorted desc
        seen = np.sort(np.asarray([x for x, _ in items[:n_seen]],
                                  np.float32))[::-1][:cap]
        np.testing.assert_allclose(valid, seen[:len(valid)],
                                   rtol=1e-5, atol=1e-5)    # best-k retained
        # empty lanes are a suffix of sentinels
        n_valid = len(valid)
        assert not np.isfinite(scores[n_valid:]).any()
        assert (np.asarray(pq.payload)[n_valid:] == -1).all()


@given(st.lists(st.tuples(floats, st.integers(0, 10_000)), min_size=1,
                max_size=80), st.integers(1, 12))
@settings(max_examples=40, deadline=None)
def test_pq_batch_insert_matches_sequential(items, cap):
    """pq_insert_batch (sort + rank-merge) == repeated compare-and-shift."""
    seq = pq_make(cap)
    for s, pay in items:
        seq = pq_insert(seq, jnp.float32(s), jnp.int32(pay))
    scores = jnp.asarray(np.asarray([s for s, _ in items], np.float32))
    pays = jnp.asarray(np.asarray([p for _, p in items], np.int32))
    batch = pq_insert_batch(pq_make(cap), scores, pays)
    np.testing.assert_allclose(np.asarray(seq.scores),
                               np.asarray(batch.scores), rtol=1e-6)


@given(st.lists(floats, min_size=1, max_size=40),
       st.lists(floats, min_size=1, max_size=40))
@settings(max_examples=40, deadline=None)
def test_merge_sorted_matches_full_sort(xs, ys):
    a = np.sort(np.asarray(xs, np.float32))[::-1].copy()
    b = np.sort(np.asarray(ys, np.float32))[::-1].copy()
    ia = np.arange(len(a), dtype=np.int32)
    ib = 1000 + np.arange(len(b), dtype=np.int32)
    ms, mi = merge_sorted(jnp.asarray(a), jnp.asarray(ia),
                          jnp.asarray(b), jnp.asarray(ib))
    expect = np.sort(np.concatenate([a, b]))[::-1][:len(a)]
    np.testing.assert_allclose(np.asarray(ms), expect, rtol=1e-6)
    # payloads track their scores
    both_s = np.concatenate([a, b])
    both_i = np.concatenate([ia, ib])
    for s, i in zip(np.asarray(ms), np.asarray(mi)):
        assert s in both_s[both_i == i]


def _shard_runs(valid_counts, cap, seed=0, scores=None):
    """Stacked (S, cap) descending runs with ``-1``/``NEG_INF`` pad tails of
    per-run length ``cap - valid_counts[s]`` — the sharded fan-out's result
    shape. Ids encode (shard, slot) as ``shard * 1000 + slot``."""
    rng = np.random.default_rng(seed)
    S = len(valid_counts)
    s_out = np.full((S, cap), -np.inf, dtype=np.float32)
    i_out = np.full((S, cap), -1, dtype=np.int32)
    for s, n in enumerate(valid_counts):
        vals = (np.sort(rng.random(n).astype(np.float32))[::-1]
                if scores is None else np.asarray(scores[s], np.float32))
        s_out[s, :n] = vals[:n]
        i_out[s, :n] = s * 1000 + np.arange(n)
    return s_out, i_out


def _merge_oracle(s_runs, i_runs, cap):
    """Stable shard-major merge: ties keep lower shard, then run order."""
    flat_s = s_runs.reshape(-1)
    flat_i = i_runs.reshape(-1)
    order = np.argsort(-flat_s, kind="stable")[:cap]
    return flat_s[order], flat_i[order]


@given(st.lists(st.integers(0, 8), min_size=1, max_size=5),
       st.sampled_from([1, 3, 8]))
@settings(max_examples=30, deadline=None)
def test_merge_sorted_many_matches_stable_oracle(valid_counts, cap):
    """The rank-merge tree over S shard runs == a stable sort of the
    concatenation, for any mix of run lengths incl. empty/padded runs."""
    valid_counts = [min(v, cap) for v in valid_counts]
    s_runs, i_runs = _shard_runs(valid_counts, cap, seed=cap)
    ms, mi = merge_sorted_many(jnp.asarray(s_runs), jnp.asarray(i_runs))
    es, ei = _merge_oracle(s_runs, i_runs, cap)
    np.testing.assert_array_equal(np.asarray(ms), es)
    np.testing.assert_array_equal(np.asarray(mi), ei)


def test_merge_sorted_many_unequal_counts_and_pads():
    """Shards returning fewer than cap rows (id -1 / -inf pads): pads never
    displace real entries and only surface when valid entries run out."""
    s_runs, i_runs = _shard_runs([4, 0, 2, 1], cap=4, seed=3)
    ms, mi = merge_sorted_many(jnp.asarray(s_runs), jnp.asarray(i_runs))
    ms, mi = np.asarray(ms), np.asarray(mi)
    assert (mi >= 0).all()                      # 7 valid entries, cap 4
    es, ei = _merge_oracle(s_runs, i_runs, 4)
    np.testing.assert_array_equal(ms, es)
    np.testing.assert_array_equal(mi, ei)
    # fewer valid entries than cap: the tail is sentinel pads
    s_runs, i_runs = _shard_runs([1, 0, 1], cap=4, seed=4)
    ms, mi = merge_sorted_many(jnp.asarray(s_runs), jnp.asarray(i_runs))
    assert (np.asarray(mi)[2:] == -1).all()
    assert not np.isfinite(np.asarray(ms)[2:]).any()


def test_merge_sorted_many_duplicate_scores_stable_by_shard():
    """Equal scores come back ordered by shard index then slot (the
    left-leaning tree keeps run A first at every level) — the deterministic
    cross-shard tie order the sharded engines rely on."""
    dup = [[0.5, 0.5, 0.25], [0.5, 0.5, 0.25], [0.5, 0.25, 0.25]]
    s_runs, i_runs = _shard_runs([3, 3, 3], cap=3, scores=dup)
    ms, mi = merge_sorted_many(jnp.asarray(s_runs), jnp.asarray(i_runs))
    np.testing.assert_allclose(np.asarray(ms), [0.5] * 3)
    # all five 0.5-entries exist; the best 3 are shard 0's pair then shard 1
    np.testing.assert_array_equal(np.asarray(mi), [0, 1, 1000])


def test_merge_sorted_many_single_run_identity():
    """S == 1 is the identity — the sharded traversal's 1-shard bit-parity
    contract rests on this."""
    s_runs, i_runs = _shard_runs([3], cap=5, seed=9)
    ms, mi = merge_sorted_many(jnp.asarray(s_runs), jnp.asarray(i_runs))
    np.testing.assert_array_equal(np.asarray(ms), s_runs[0])
    np.testing.assert_array_equal(np.asarray(mi), i_runs[0])


def test_pq_pop_order():
    pq = pq_make(4)
    for s in [0.2, 0.9, 0.5, 0.7, 0.1]:
        pq = pq_insert(pq, jnp.float32(s), jnp.int32(int(s * 10)))
    out = []
    for _ in range(4):
        s, p, pq = pq_pop(pq)
        out.append(float(s))
    assert out == sorted(out, reverse=True)
    assert abs(out[0] - 0.9) < 1e-6
    # queue now empty: sentinel pops
    s, p, pq = pq_pop(pq)
    assert not np.isfinite(float(s)) and int(p) == -1


def test_pq_pop_many_beam():
    pq = pq_make(6)
    for s in [0.1, 0.4, 0.9, 0.3, 0.8]:
        pq = pq_insert(pq, jnp.float32(s), jnp.int32(int(s * 10)))
    top_s, top_p, rest = pq_pop_many(pq, 3)
    np.testing.assert_allclose(np.asarray(top_s), [0.9, 0.8, 0.4], rtol=1e-6)
    assert list(np.asarray(top_p)) == [9, 8, 4]
    # remaining entries shifted up, tail refilled with sentinels
    np.testing.assert_allclose(np.asarray(rest.scores)[:2], [0.3, 0.1],
                               rtol=1e-6)
    assert not np.isfinite(np.asarray(rest.scores)[2:]).any()


def test_pq_worst_tracks_eviction_threshold():
    pq = pq_make(3)
    assert not np.isfinite(float(pq_worst(pq)))     # not full: inserts free
    for s in [0.3, 0.6, 0.9]:
        pq = pq_insert(pq, jnp.float32(s), jnp.int32(0))
    assert abs(float(pq_worst(pq)) - 0.3) < 1e-6    # full: worst retained
    pq = pq_insert(pq, jnp.float32(0.5), jnp.int32(1))
    assert abs(float(pq_worst(pq)) - 0.5) < 1e-6    # 0.3 evicted


# -- merge_sorted, bit for bit against the stable sort ------------------------

# few distinct values, so ties within a run and across the two runs abound;
# -0.0 and 0.0 compare equal, so they tie as well
_TIE_GRID = np.float32([1.0, 0.75, 0.5, 0.5, 0.25, 1 / 3, 0.0, -0.0])


def _tied_runs(rng, rows, n):
    """``rows`` descending runs of length ``n`` over :data:`_TIE_GRID`,
    each with a ``NEG_INF`` / ``-1`` pad tail of random length (0 to n);
    ids distinct across runs, so a misplaced entry shows in the ids."""
    s = -np.sort(-rng.choice(_TIE_GRID, (rows, n)), axis=1)
    i = rng.permutation(1 << 20)[:rows * n].reshape(rows, n).astype(np.int32)
    pads = rng.integers(0, n + 1, rows)
    tail = np.arange(n)[None, :] >= (n - pads)[:, None]
    return (np.where(tail, -np.inf, s).astype(np.float32),
            np.where(tail, -1, i).astype(np.int32))


def _stable_oracle(s_a, i_a, s_b, i_b):
    """The best ``len(s_a)`` of the concatenation by a stable descending
    sort: ties keep run A first and each run's own order."""
    s = np.concatenate([s_a, s_b], axis=-1)
    i = np.concatenate([i_a, i_b], axis=-1)
    order = np.argsort(-s, axis=-1, kind="stable")[..., :s_a.shape[-1]]
    return (np.take_along_axis(s, order, axis=-1),
            np.take_along_axis(i, order, axis=-1))


def _assert_bits_equal(got_s, got_i, want_s, want_i):
    np.testing.assert_array_equal(np.asarray(got_s).view(np.int32),
                                  want_s.view(np.int32))
    np.testing.assert_array_equal(np.asarray(got_i), want_i)


@pytest.mark.parametrize("a,b", [(64, 64), (40, 7), (7, 40), (1, 1)])
def test_merge_sorted_is_the_stable_sort_bit_for_bit(a, b):
    """Scores and ids both: the best ``a`` of the two runs, descending, run A
    ahead of run B on ties, each run in its own order, pads last."""
    # the property is drawn inside the case: ``_propcheck``'s ``given``
    # wraps a test that takes no pytest arguments
    @given(st.integers(0, 2**32 - 1))
    @settings(max_examples=25, deadline=None)
    def check(seed):
        rng = np.random.default_rng(seed)
        s_a, i_a = _tied_runs(rng, 1, a)
        s_b, i_b = _tied_runs(rng, 1, b)
        got = merge_sorted(jnp.asarray(s_a[0]), jnp.asarray(i_a[0]),
                           jnp.asarray(s_b[0]), jnp.asarray(i_b[0]))
        _assert_bits_equal(*got,
                           *_stable_oracle(s_a[0], i_a[0], s_b[0], i_b[0]))

    check()


def test_merge_sorted_vmapped_at_the_traversal_shape():
    """(Q, 64) + (Q, 64) under ``vmap``, as the HNSW traversal merges each
    iteration's sorted run into its two queues."""
    rng = np.random.default_rng(17)
    s_a, i_a = _tied_runs(rng, 256, 64)
    s_b, i_b = _tied_runs(rng, 256, 64)
    got = jax.jit(jax.vmap(merge_sorted))(s_a, i_a, s_b, i_b)
    _assert_bits_equal(*got, *_stable_oracle(s_a, i_a, s_b, i_b))


def _primitive_names(jaxpr):
    """Every primitive of ``jaxpr``, sub-jaxprs (loop bodies, branches,
    nested jits) included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                if isinstance(sub, ClosedJaxpr):
                    names |= _primitive_names(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    names |= _primitive_names(sub)
    return names


@pytest.mark.parametrize("batched", [False, True])
def test_merge_sorted_has_no_loop_gather_or_scatter(batched):
    """The merge runs twice per traversal iteration: a binary search lowers
    to a loop of batched gathers, and placing by scatter to a scatter, so
    neither may come back."""
    fn = jax.vmap(merge_sorted) if batched else merge_sorted
    shape = (8, 64) if batched else (64,)
    s, i = jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.int32)
    names = _primitive_names(jax.make_jaxpr(fn)(s, i, s, i).jaxpr)
    banned = {n for n in names
              if n in ("while", "scan") or "gather" in n or "scatter" in n}
    assert not banned, banned
