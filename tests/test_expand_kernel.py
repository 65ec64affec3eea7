"""Fused beam-expansion kernel (kernels/expand.py) vs the pure-jnp oracle.

ISSUE 4 satellite: edge cases of the gather/expand stage — expansion widths
and 2M off the 128-lane grid, duplicate neighbour ids inside one expansion,
fully masked (all ``-1``) expansion rows, and odd word counts (W padding) —
each checked bit-for-bit against ``ref.expand_sorted_ref``, plus the jnp
twin (``core.hnsw.expand_scores_jnp``) against both. Under every metric,
both equal the row-gather chain bit for bit: each neighbour's row gathered
by id, scored, filtered at ``worst`` and sorted.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fingerprints import metric_from_counts, popcount
from repro.core.hnsw import expand_scores_jnp
from repro.data.molecules import SyntheticConfig, synthetic_fingerprints
from repro.kernels import ops, ref
from test_metrics import M_IDS, METRICS


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


def _row_chain(q, db, flat, worst, kk, metric):
    """The expansion by rows: gather each neighbour's print by its id,
    score it, drop ``-1`` ids and scores not above ``worst``, keep the best
    ``kk`` (``lax.top_k`` order)."""
    q, db, ids = jnp.asarray(q), jnp.asarray(db), jnp.asarray(flat)
    safe = jnp.maximum(ids, 0)
    inter = jnp.sum(jax.lax.population_count(
        q[:, None, :] & db[safe]).astype(jnp.int32), axis=-1)
    s = metric_from_counts(metric, inter, popcount(q)[:, None],
                           popcount(db)[safe])
    s = jnp.where(ids >= 0, s, -jnp.inf)
    keep = s > jnp.asarray(worst)[:, None]
    s = jnp.where(keep, s, -jnp.inf)
    ids = jnp.where(keep, ids, -1)
    s_srt, pos = jax.lax.top_k(s, kk)
    return np.asarray(s_srt), np.asarray(jnp.take_along_axis(ids, pos, 1))


@pytest.mark.parametrize("metric", METRICS, ids=M_IDS)
def test_expand_equals_the_row_gather_chain(metric):
    """The expand kernel (interpret mode) and its jnp twin return the
    row-gather chain's run bit for bit, eviction filter included."""
    db, nbr, cnt, pop, flat, worst = _case(400, 4, 16, 4, seed=11)
    worst[:] = [-np.inf, 0.15, 0.3, 0.45]
    kk = 32
    q = jnp.asarray(db[:4])
    want_s, want_i = _row_chain(q, db, flat, worst, kk, metric)
    args = (jnp.asarray(pop), jnp.asarray(flat), jnp.asarray(worst), kk)
    ks, ki = ops.expand_tanimoto_sorted(q, ops.nbr_slabs(jnp.asarray(nbr)),
                                        *args, metric=metric)
    ts, ti = expand_scores_jnp(q, popcount(q), jnp.asarray(nbr),
                               jnp.asarray(cnt), *args, metric=metric)
    for s, i in ((ks, ki), (ts, ti)):
        np.testing.assert_array_equal(np.asarray(s).view(np.int32),
                                      want_s.view(np.int32))
        np.testing.assert_array_equal(np.asarray(i), want_i)
    # the filter dropped some neighbours and kept others
    assert (want_i == -1).any() and (want_i >= 0).any()
