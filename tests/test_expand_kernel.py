"""Fused beam-expansion kernel (kernels/expand.py) vs the pure-jnp oracle.

ISSUE 4 satellite: edge cases of the gather/expand stage — expansion widths
and 2M off the 128-lane grid, duplicate neighbour ids inside one expansion,
fully masked (all ``-1``) expansion rows, and odd word counts (W padding) —
each checked bit-for-bit against ``ref.expand_sorted_ref``, plus the jnp
twin (``core.hnsw.expand_scores_jnp``) against both.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fingerprints import popcount
from repro.core.hnsw import expand_scores_jnp
from repro.data.molecules import SyntheticConfig, synthetic_fingerprints
from repro.kernels import ops, ref


def _case(n, q_n, m2, beam, w_words=None, seed=0, masked_frac=0.3):
    rng = np.random.default_rng(seed)
    db = np.asarray(synthetic_fingerprints(SyntheticConfig(n=n, seed=seed)))
    if w_words is not None:                   # W padding: truncate words
        db = np.ascontiguousarray(db[:, :w_words])
    adj = rng.integers(0, n, (n, m2)).astype(np.int32)
    adj[rng.random(adj.shape) < 0.15] = -1
    nbr = db[np.maximum(adj, 0)].copy()
    nbr[adj < 0] = 0
    cnt = np.bitwise_count(nbr).sum(-1).astype(np.int32)
    pop = rng.integers(0, n, (q_n, beam)).astype(np.int32)
    flat = adj[np.maximum(pop, 0)].reshape(q_n, beam * m2).copy()
    flat[rng.random(flat.shape) < masked_frac] = -1
    worst = np.full((q_n,), -np.inf, dtype=np.float32)
    return db, nbr, cnt, pop, flat, worst


def _run_all(db, nbr, cnt, pop, flat, worst, kk):
    args = (jnp.asarray(db[:pop.shape[0]]), jnp.asarray(nbr),
            jnp.asarray(cnt), jnp.asarray(pop), jnp.asarray(flat),
            jnp.asarray(worst))
    ks, ki = ops.expand_tanimoto_sorted(args[0], ops.nbr_slabs(args[1]),
                                        *args[3:], kk)
    rs, ri = ref.expand_sorted_ref(*args, kk)
    q = args[0]
    ts, ti = expand_scores_jnp(q, popcount(q), *args[1:], kk)
    return (np.asarray(ks), np.asarray(ki)), (np.asarray(rs), np.asarray(ri)), \
        (np.asarray(ts), np.asarray(ti))


@pytest.mark.parametrize("n,q_n,m2,beam,kk,seed", [
    (400, 3, 16, 4, 32, 0),     # E=64, lane-aligned-ish
    (500, 2, 10, 3, 20, 1),     # 2M=10 and E=30: neither a lane multiple
    (257, 1, 5, 1, 5, 2),       # single-slot beam, odd everything
    (300, 4, 12, 5, 60, 3),     # kk == n_exp (full sorted expansion)
])
def test_expand_matches_oracle_and_twin(n, q_n, m2, beam, kk, seed):
    case = _case(n, q_n, m2, beam, seed=seed)
    (ks, ki), (rs, ri), (ts, ti) = _run_all(*case, kk)
    np.testing.assert_array_equal(ks, rs)
    np.testing.assert_array_equal(ki, ri)
    np.testing.assert_array_equal(ts, rs)
    np.testing.assert_array_equal(ti, ri)


def test_expand_odd_word_count():
    """W padding: fingerprints whose word count is off the lane grid (W=7)
    must still score exactly (the kernel recurs over whatever W it's given)."""
    case = _case(300, 3, 8, 2, w_words=7, seed=4)
    (ks, ki), (rs, ri), (ts, ti) = _run_all(*case, kk=10)
    np.testing.assert_array_equal(ks, rs)
    np.testing.assert_array_equal(ki, ri)
    np.testing.assert_array_equal(ts, rs)


def test_expand_duplicate_neighbour_ids():
    """Duplicate ids inside one expansion (a repeated id within one
    adjacency row, or two popped nodes sharing a neighbour) must each score
    identically and survive the sort as distinct slots — dedup is the
    traversal's visited-mask job, not the kernel's."""
    rng = np.random.default_rng(5)
    n, m2, beam, q_n = 200, 6, 2, 2
    db = np.asarray(synthetic_fingerprints(SyntheticConfig(n=n, seed=5)))
    adj = rng.integers(0, n, (n, m2)).astype(np.int32)
    adj[3, 4] = adj[3, 1]                        # duplicate within one row
    adj[7, 0] = adj[9, 2]                        # shared across two rows
    nbr = db[np.maximum(adj, 0)].copy()
    nbr[adj < 0] = 0
    cnt = np.bitwise_count(nbr).sum(-1).astype(np.int32)
    pop = np.array([[3, 5], [7, 9]], dtype=np.int32)
    flat = adj[pop].reshape(q_n, beam * m2)      # traversal-invariant flat
    worst = np.full((q_n,), -np.inf, dtype=np.float32)
    kk = beam * m2
    (ks, ki), (rs, ri), _ = _run_all(db, nbr, cnt, pop, flat, worst, kk)
    np.testing.assert_array_equal(ks, rs)
    np.testing.assert_array_equal(ki, ri)
    # both copies of each duplicate survive, with identical scores
    dup = adj[3, 1]
    slots = np.where(ki[0] == dup)[0]
    assert len(slots) >= 2, (ki[0], dup)
    assert len(set(np.round(ks[0][slots], 7))) == 1
    shared = adj[7, 0]
    slots = np.where(ki[1] == shared)[0]
    assert len(slots) >= 2, (ki[1], shared)
    assert len(set(np.round(ks[1][slots], 7))) == 1


def test_expand_all_invalid_row():
    """A fully masked expansion row (all -1 — e.g. every neighbour already
    visited) must come back all -inf / -1, and must not disturb other rows."""
    db, nbr, cnt, pop, flat, worst = _case(250, 3, 8, 2, seed=6)
    flat[1, :] = -1
    (ks, ki), (rs, ri), (ts, ti) = _run_all(db, nbr, cnt, pop, flat, worst,
                                            kk=8)
    np.testing.assert_array_equal(ks, rs)
    np.testing.assert_array_equal(ki, ri)
    assert not np.isfinite(ks[1]).any()
    assert (ki[1] == -1).all()


def test_expand_invalid_pop_ids():
    """-1 popped slots (queue underflow) are clamped for addressability and
    fully masked via their flat ids."""
    db, nbr, cnt, pop, flat, worst = _case(220, 2, 6, 3, seed=7)
    pop[0, 1] = -1
    flat[0, 6:12] = -1                           # the slot's ids masked too
    (ks, ki), (rs, ri), _ = _run_all(db, nbr, cnt, pop, flat, worst, kk=9)
    np.testing.assert_array_equal(ks, rs)
    np.testing.assert_array_equal(ki, ri)


def test_expand_worst_threshold_filters():
    """Scores <= worst[q] are dropped (score -inf, id -1): the result-queue
    eviction bound applied inside the kernel."""
    db, nbr, cnt, pop, flat, worst = _case(300, 2, 8, 2, seed=8,
                                           masked_frac=0.0)
    worst[0] = 1.1                               # nothing can beat it
    (ks, ki), (rs, ri), _ = _run_all(db, nbr, cnt, pop, flat, worst, kk=10)
    np.testing.assert_array_equal(ks, rs)
    np.testing.assert_array_equal(ki, ri)
    assert not np.isfinite(ks[0]).any() and (ki[0] == -1).all()
    assert np.isfinite(ks[1]).any()


def test_expand_inside_jitted_loop():
    """The traversal launches the kernel from inside lax.while_loop — it
    must trace there with loop-carried pop/flat ids."""
    db, nbr, cnt, pop, flat, worst = _case(150, 2, 6, 2, seed=9)
    q = jnp.asarray(db[:2])
    slabs = ops.nbr_slabs(jnp.asarray(nbr))
    worst_j = jnp.asarray(worst)

    def f(pop0, flat0):
        def body(carry):
            i, p, fl, acc = carry
            s, _ = ops.expand_tanimoto_sorted(q, slabs, p, fl, worst_j, 6)
            acc = acc + jnp.where(jnp.isfinite(s), s, 0.0).sum()
            return i + 1, (p + 1) % 150, fl, acc

        return jax.lax.while_loop(lambda c: c[0] < 3, body,
                                  (0, pop0, flat0, jnp.float32(0)))[3]

    out = jax.jit(f)(jnp.asarray(pop), jnp.asarray(flat))
    assert np.isfinite(float(out)) and float(out) > 0
