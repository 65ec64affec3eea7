"""BitBound (Eq. 2) pruning: the bound must be *sound* — no fingerprint
outside the popcount window can reach the similarity cutoff."""
import jax.numpy as jnp
import numpy as np
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # collection-safe fallback (see tests/_propcheck.py)
    from _propcheck import given, settings, strategies as st

from repro.core import bitbound as bb
from repro.core import pack_bits
from repro.core.fingerprints import popcount


@given(st.integers(0, 2**32 - 1), st.sampled_from([0.3, 0.5, 0.7, 0.9]))
@settings(max_examples=30, deadline=None)
def test_eq2_bound_is_sound(seed, cutoff):
    rng = np.random.default_rng(seed)
    bits = (rng.random((64, 128)) < rng.uniform(0.02, 0.3)).astype(np.uint8)
    db = jnp.asarray(pack_bits(bits))
    q = db[0]
    a = int(popcount(q))
    cnt = np.asarray(popcount(db))
    # similarity of q against all
    inter = np.bitwise_count(np.asarray(q)[None] & np.asarray(db)).sum(-1)
    union = a + cnt - inter
    sim = np.where(union > 0, inter / np.maximum(union, 1), 0.0)
    outside = (cnt < np.ceil(a * cutoff)) | (cnt > np.floor(a / cutoff))
    assert (sim[outside] < cutoff).all(), "Eq.2 pruned a true neighbour"


def test_index_sorted_and_complete(small_db):
    idx = bb.build_index(jnp.asarray(small_db))
    counts = np.asarray(idx.counts)
    assert (np.diff(counts) >= 0).all()
    # order is a permutation
    assert len(np.unique(np.asarray(idx.order))) == small_db.shape[0]
    # sorted db rows match original rows through the permutation
    np.testing.assert_array_equal(np.asarray(idx.db),
                                  small_db[np.asarray(idx.order)])


def test_bound_range_contains_all_hits(small_db, queries):
    idx = bb.build_index(jnp.asarray(small_db))
    cutoff = 0.6
    for q in jnp.asarray(queries)[:4]:
        lo, hi = bb.bound_range_np(np.asarray(idx.counts),
                                   np.asarray(popcount(q)), cutoff)
        lo, hi = int(lo), int(hi)
        inter = np.bitwise_count(np.asarray(q)[None] & np.asarray(idx.db)).sum(-1)
        union = int(popcount(q)) + np.asarray(idx.counts) - inter
        sim = np.where(union > 0, inter / np.maximum(union, 1), 0.0)
        hits = np.where(sim >= cutoff)[0]
        assert (hits >= lo).all() and (hits < hi).all()


def test_expected_speedup_monotonic():
    mu, sigma = 62.0, 22.0
    speedups = [bb.expected_speedup(mu, sigma, c) for c in (0.3, 0.5, 0.7, 0.9)]
    assert all(s2 >= s1 for s1, s2 in zip(speedups, speedups[1:]))
    assert speedups[0] >= 1.0


def test_gaussian_model_normalises():
    xs = np.linspace(0, 1024, 8192)
    dens = bb.gaussian_model(xs, 62.0, 22.0)
    assert abs(np.trapezoid(dens, xs) - 1.0) < 1e-2
