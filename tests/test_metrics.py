"""Cross-engine metric conformance suite (ISSUE 10 tentpole).

Every similarity metric is a rational function of the popcount triple
``(a=|A|, b=|B|, c=|A∩B|)``; the ``Metric`` descriptor maps the shared
triple to a score at trace time. This suite pins:

* the numpy oracle (``metric_from_counts_np``) against the closed-form
  float64 formulas and the device map (``metric_from_counts``) against the
  oracle **bit-exactly** — jitted and eager, so XLA fast-math rewrites
  (rsqrt, FMA contraction) cannot split the backends;
* engine × backend × metric parity: brute vs bitbound vs
  HNSW-rescore agree with the oracle on scores everywhere, and on ids
  modulo permutation within equal-f32-score tie groups (non-Tanimoto
  metrics compress score resolution, so ties are common and the numpy
  heap vs device ``top_k`` tie orders legitimately differ);
* Tanimoto-default identity: ``metric=None`` and explicit
  ``Metric("tanimoto")`` trace the same programs and return identical
  results (the bit-identity-with-pre-metric-code contract);
* BitBound window soundness per metric: nothing scoring ``>= cutoff``
  ever has a popcount outside the metric's window, m=1 engines never
  drop a qualifying true top-k value, and unbounded metrics
  (``tversky(0,0)``) fall back to a full scan with ``scanned``
  reflecting it;
* Tversky asymmetry (α≠β ⇒ sim(q,d) ≠ sim(d,q)) and the degenerate
  cases: empty fingerprint, all-ones, q==d ⇒ score exactly 1.0;
* variable widths: odd word counts (fp_bits off the 128-lane grid)
  through every engine, and ``fp_bits`` mismatches raising up front.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # collection-safe fallback (see tests/_propcheck.py)
    from _propcheck import given, settings, strategies as st

from repro.core import BitBoundFoldingEngine, BruteForceEngine, HNSWEngine
from repro.core import bitbound as bb
from repro.core import fingerprints as fp
from repro.core import hnsw as hn
from repro.core.fingerprints import (Metric, TANIMOTO, TVERSKY_SCALE,
                                     metric_from_counts,
                                     metric_from_counts_np, pack_bits,
                                     resolve_metric)
from repro.data.molecules import (SyntheticConfig, queries_from_db,
                                  synthetic_fingerprints)

METRICS = [
    Metric("tanimoto"),
    Metric("dice"),
    Metric("cosine"),
    resolve_metric("tversky(0.3,0.7)"),
]
UNBOUNDED = resolve_metric("tversky(0,0)")
M_IDS = [m.spec for m in METRICS]

DB = np.asarray(synthetic_fingerprints(SyntheticConfig(n=500, seed=0)))
QUERIES = np.asarray(queries_from_db(DB, 6, seed=1))
K = 8


def _triples(queries, db):
    """Independent popcount-triple computation (the conformance ground
    truth shares no code with the engines)."""
    a = np.bitwise_count(queries).sum(axis=1).astype(np.int64)
    b = np.bitwise_count(db).sum(axis=1).astype(np.int64)
    c = np.bitwise_count(queries[:, None, :] & db[None, :, :]) \
        .sum(axis=2).astype(np.int64)
    return a, b, c


def _oracle(metric, queries, db):
    """(Q, N) float32 oracle score matrix."""
    a, b, c = _triples(queries, db)
    return metric_from_counts_np(metric, c, a[:, None], b[None, :])


def _closed_form(metric, a, b, c):
    """Float64 closed form straight from the paper definitions."""
    a, b, c = (x.astype(np.float64) for x in (a, b, c))
    if metric.name == "tanimoto":
        den = a + b - c
    elif metric.name == "dice":
        c, den = 2.0 * c, a + b
    elif metric.name == "cosine":
        with np.errstate(invalid="ignore", divide="ignore"):
            return np.where(a * b > 0, c / np.sqrt(a * b), 0.0)
    else:
        den = c + metric.alpha * (a - c) + metric.beta * (b - c)
    with np.errstate(invalid="ignore", divide="ignore"):
        return np.where(den > 0, c / den, 0.0)


@pytest.mark.parametrize("ulps", [-2, -1, 1, 2])
def test_exact_ratio_correction_restores_ieee_quotient(ulps):
    """``exact_ratio``'s residual step returns the IEEE quotient when the
    divide it corrects lands ``ulps`` off it, for every pair
    1 <= num <= den <= 2048 (every Tanimoto and Dice score of 1024-bit
    prints). The CPU divide is already IEEE, so the step is fed the off
    quotient directly; bits must match numpy's ``num / den``."""
    den, num = np.tril_indices(2049)
    keep = num >= 1
    num = num[keep].astype(np.float32)
    den = den[keep].astype(np.float32)
    want = num / den
    off = want
    for _ in range(abs(ulps)):
        off = np.nextafter(off, np.float32(np.copysign(np.inf, ulps)))
    assert (off != want).all()
    got = jax.jit(fp._correct)(off, num, den)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  want.view(np.uint32))


def _assert_results_match_oracle(metric, ids, vals, oracle, k, cutoff=None):
    """Tie-tolerant conformance: per query, the returned value vector must
    equal the oracle's sorted top-k values exactly (restricted to values
    >= cutoff when the engine prunes), and every returned id's true score
    must equal its returned value — together these pin the result set up
    to permutation within equal-score groups, the strongest property that
    survives f32 ties."""
    ids, vals = np.asarray(ids), np.asarray(vals)
    for qi in range(oracle.shape[0]):
        row = oracle[qi]
        want = np.sort(row)[::-1][:k]
        got = vals[qi]
        if cutoff is None:
            np.testing.assert_array_equal(got, want, err_msg=f"q{qi} vals")
        else:
            w = want[want >= cutoff]
            np.testing.assert_array_equal(
                got[:len(w)], w, err_msg=f"q{qi} vals >= cutoff")
        for slot, (i, v) in enumerate(zip(ids[qi], vals[qi])):
            if i < 0:
                continue
            if cutoff is not None and v < cutoff:
                continue
            assert row[i] == v, (
                f"{metric.spec} q{qi} slot{slot}: id {i} true score "
                f"{row[i]!r} != returned {v!r}")


def _assert_tie_equivalent(ids_a, vals_a, ids_b, vals_b, label="",
                           oracle=None):
    """Cross-backend parity modulo tie order: value vectors bit-equal, id
    sets equal within every maximal equal-value run.  The final run may
    straddle the rank-k cut (more equal-score items exist than slots), so
    when ``oracle`` (full [nq, n_db] score matrix) is given, a divergent
    final group is accepted iff every id on both sides truly scores the
    tie value."""
    ids_a, ids_b = np.asarray(ids_a), np.asarray(ids_b)
    vals_a, vals_b = np.asarray(vals_a), np.asarray(vals_b)
    np.testing.assert_array_equal(vals_a, vals_b, err_msg=f"{label}: vals")
    for qi in range(ids_a.shape[0]):
        row = vals_a[qi]
        start = 0
        for end in range(1, len(row) + 1):
            if end == len(row) or row[end] != row[start]:
                ga = np.sort(ids_a[qi, start:end])
                gb = np.sort(ids_b[qi, start:end])
                if (end == len(row) and oracle is not None
                        and not np.array_equal(ga, gb)):
                    for i in np.concatenate([ga, gb]):
                        assert oracle[qi, i] == row[start], (
                            f"{label}: q{qi} boundary tie id {i} does not "
                            f"score {row[start]!r}")
                else:
                    np.testing.assert_array_equal(
                        ga, gb, err_msg=f"{label}: q{qi} tie group "
                                        f"[{start}:{end}] val={row[start]!r}")
                start = end


# -- score map ---------------------------------------------------------------

@pytest.mark.parametrize("metric", METRICS + [UNBOUNDED],
                         ids=M_IDS + [UNBOUNDED.spec])
def test_np_oracle_matches_closed_form(metric):
    rng = np.random.default_rng(0)
    a = rng.integers(0, 1025, 4000)
    b = rng.integers(0, 1025, 4000)
    c = rng.integers(0, np.minimum(a, b) + 1)
    got = metric_from_counts_np(metric, c, a, b)
    want = _closed_form(metric, a, b, c)
    np.testing.assert_allclose(got, want.astype(np.float32), rtol=2e-6,
                               atol=0)
    # exact corners: no overlap -> 0, identical sets -> 1
    assert metric_from_counts_np(metric, np.int64(0), np.int64(0),
                                 np.int64(0)) == 0.0
    nz = a[a > 0]
    ones = metric_from_counts_np(metric, nz, nz, nz)
    np.testing.assert_array_equal(ones, np.float32(1.0))


@pytest.mark.parametrize("metric", METRICS + [UNBOUNDED],
                         ids=M_IDS + [UNBOUNDED.spec])
def test_device_map_matches_np_oracle_bitwise(metric):
    """The jitted device map must equal the numpy oracle bit-for-bit — the
    property the per-metric op sequences (exact-int divides, explicit
    rsqrt, 1/256-quantized Tversky weights) were chosen to guarantee."""
    import jax
    rng = np.random.default_rng(1)
    a = rng.integers(0, 1025, 2048).astype(np.int32)
    b = rng.integers(0, 1025, 2048).astype(np.int32)
    c = rng.integers(0, np.minimum(a, b) + 1).astype(np.int32)
    want = metric_from_counts_np(metric, c.astype(np.int64),
                                 a.astype(np.int64), b.astype(np.int64))
    eager = np.asarray(metric_from_counts(metric, jnp.asarray(c),
                                          jnp.asarray(a), jnp.asarray(b)))
    jitted = np.asarray(jax.jit(
        lambda cc, aa, bb: metric_from_counts(metric, cc, aa, bb)
    )(jnp.asarray(c), jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(eager, want)
    np.testing.assert_array_equal(jitted, want)


def test_tversky_weights_quantized():
    m = resolve_metric("tversky(0.3,0.7)")
    assert m.alpha == round(0.3 * TVERSKY_SCALE) / TVERSKY_SCALE
    assert m.beta == round(0.7 * TVERSKY_SCALE) / TVERSKY_SCALE
    assert resolve_metric(m.spec) == m      # spec round-trips


def test_tversky_asymmetry():
    """α≠β weights the two set differences differently: for q ⊂ d the two
    directions must disagree, and each must hit the closed form exactly."""
    met = resolve_metric("tversky(0.3,0.7)")
    q = pack_bits(np.arange(1024) < 4)[None]      # |q| = 4
    d = pack_bits(np.arange(1024) < 8)[None]      # |d| = 8, q ⊂ d
    s_qd = float(_oracle(met, q, d)[0, 0])
    s_dq = float(_oracle(met, d, q)[0, 0])
    assert s_qd == pytest.approx(4 / (4 + met.beta * 4), abs=1e-6)
    assert s_dq == pytest.approx(4 / (4 + met.alpha * 4), abs=1e-6)
    assert s_qd != s_dq
    # symmetric metrics stay symmetric on the same pair
    for sym in METRICS[:3] + [resolve_metric("tversky")]:
        assert _oracle(sym, q, d)[0, 0] == _oracle(sym, d, q)[0, 0]


@pytest.mark.parametrize("metric", METRICS + [UNBOUNDED],
                         ids=M_IDS + [UNBOUNDED.spec])
def test_degenerate_fingerprints(metric):
    empty = np.zeros((1, 32), dtype=np.uint32)
    ones = np.full((1, 32), 0xFFFFFFFF, dtype=np.uint32)
    some = DB[:3]
    # empty vs anything (and vs itself) scores 0
    for other in (empty, ones, some):
        assert np.all(_oracle(metric, empty, other) == 0.0)
        assert np.all(_oracle(metric, other, empty) == 0.0)
    # q == d scores exactly 1 (all-ones included)
    for row in (ones, some):
        np.testing.assert_array_equal(
            np.diagonal(_oracle(metric, row, row)), np.float32(1.0))


# -- engine conformance ------------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "tpu"])
@pytest.mark.parametrize("metric", METRICS, ids=M_IDS)
def test_brute_engine_matches_oracle(metric, backend):
    eng = BruteForceEngine(DB, backend=backend, metric=metric)
    ids, vals = eng.search(QUERIES, K)
    _assert_results_match_oracle(metric, ids, vals,
                                 _oracle(metric, QUERIES, DB), K)


@pytest.mark.parametrize("metric", METRICS, ids=M_IDS)
def test_bitbound_m1_soundness_and_parity(metric):
    """m=1 two-stage scan: the only candidate filter is the metric's
    popcount window, so no qualifying (>= cutoff) true top-k value may go
    missing — and the three backends must agree exactly."""
    oracle = _oracle(metric, QUERIES, DB)
    for cutoff in (0.3, 0.5):
        results = {}
        for backend in ("numpy", "jnp", "tpu"):
            eng = BitBoundFoldingEngine(DB, cutoff=cutoff, m=1,
                                        backend=backend, metric=metric)
            results[backend] = eng.search(QUERIES, K)
        for backend in ("jnp", "tpu"):
            _assert_tie_equivalent(*results["numpy"], *results[backend],
                                   label=f"{metric.spec} m=1 numpy vs "
                                         f"{backend} Sc={cutoff}",
                                   oracle=oracle)
        _assert_results_match_oracle(metric, *results["numpy"], oracle, K,
                                     cutoff=cutoff)


@pytest.mark.parametrize("m", [2, 4])
@pytest.mark.parametrize("metric", METRICS, ids=M_IDS)
def test_bitbound_folded_backend_parity(metric, m):
    """m>1 adds the stage-1 fold truncation heuristic (a recall knob shared
    by all metrics, Tanimoto included) — the conformance property is exact
    backend parity against the numpy fold-aware reference."""
    results = {}
    for backend in ("numpy", "jnp", "tpu"):
        eng = BitBoundFoldingEngine(DB, cutoff=0.4, m=m, backend=backend,
                                    metric=metric)
        results[backend] = eng.search(QUERIES, K)
    oracle = _oracle(metric, QUERIES, DB)
    for backend in ("jnp", "tpu"):
        _assert_tie_equivalent(*results["numpy"], *results[backend],
                               label=f"{metric.spec} m={m} numpy vs "
                                     f"{backend}", oracle=oracle)
    # every returned candidate's score is the true metric score
    ids, vals = (np.asarray(x) for x in results["numpy"])
    for qi in range(ids.shape[0]):
        for i, v in zip(ids[qi], vals[qi]):
            if i >= 0 and np.isfinite(v):
                assert oracle[qi, i] == v


def test_bitbound_unbounded_metric_full_scans():
    """tversky(0,0) has no sound popcount window in either direction: the
    engine must widen to a full scan and report it through ``scanned``."""
    assert not UNBOUNDED.bounded
    for backend in ("numpy", "jnp"):
        eng = BitBoundFoldingEngine(DB, cutoff=0.5, m=1, backend=backend,
                                    metric=UNBOUNDED)
        ids, vals = eng.search(QUERIES, K)
        # full scan: nothing pruned (the window may also sweep the store's
        # power-of-two capacity pad rows, so >= rather than ==)
        assert eng.scanned(len(QUERIES)) >= len(QUERIES) * eng.n_total, \
            backend
        # everything overlapping scores 1.0 under tversky(0,0)
        assert np.all(np.asarray(vals) == 1.0)


@pytest.mark.parametrize("backend", ["numpy", "jnp", "tpu"])
@pytest.mark.parametrize("metric", METRICS, ids=M_IDS)
def test_hnsw_backend_parity_and_rescore(metric, backend):
    """One graph (built under the metric on the host) searched through
    every traversal path: score vectors bit-equal to the numpy reference,
    ids equal within tie groups, every id rescored at its true score."""
    index = hn.build_hnsw(DB, m=6, ef_construction=20, seed=3, metric=metric)
    ref_eng = HNSWEngine(DB, index=index, backend="numpy", ef_search=24)
    ref_ids, ref_vals = ref_eng.search(QUERIES, K)
    eng = HNSWEngine(DB, index=index, backend=backend, ef_search=24)
    ids, vals = eng.search(QUERIES, K)
    oracle = _oracle(metric, QUERIES, DB)
    _assert_tie_equivalent(ref_ids, ref_vals, ids, vals,
                           label=f"{metric.spec} hnsw numpy vs "
                                 f"{backend}", oracle=oracle)
    ids, vals = np.asarray(ids), np.asarray(vals)
    for qi in range(ids.shape[0]):
        for i, v in zip(ids[qi], vals[qi]):
            if i >= 0 and np.isfinite(v):
                assert oracle[qi, i] == v, f"q{qi} id {i}"


def test_hnsw_engine_refuses_metric_mismatch():
    index = hn.build_hnsw(DB[:200], m=4, ef_construction=10, seed=0,
                          metric=Metric("dice"))
    with pytest.raises(ValueError, match="metric"):
        HNSWEngine(DB[:200], index=index, metric="cosine")
    # matching (or inherited) metric is fine
    eng = HNSWEngine(DB[:200], index=index)
    assert eng.metric == Metric("dice")


# -- Tanimoto-default identity ----------------------------------------------

def test_tanimoto_default_identity():
    """metric=None, metric="tanimoto" and metric=TANIMOTO must be the same
    engine configuration — same scores, same ids, same everything."""
    assert resolve_metric(None) == TANIMOTO
    base = BruteForceEngine(DB, backend="jnp").search(QUERIES, K)
    for spec in ("tanimoto", TANIMOTO):
        got = BruteForceEngine(DB, backend="jnp", metric=spec) \
            .search(QUERIES, K)
        np.testing.assert_array_equal(np.asarray(base[0]),
                                      np.asarray(got[0]))
        np.testing.assert_array_equal(np.asarray(base[1]),
                                      np.asarray(got[1]))
    b1 = BitBoundFoldingEngine(DB, cutoff=0.4, m=2, backend="jnp")
    b2 = BitBoundFoldingEngine(DB, cutoff=0.4, m=2, backend="jnp",
                               metric=TANIMOTO)
    r1, r2 = b1.search(QUERIES, K), b2.search(QUERIES, K)
    np.testing.assert_array_equal(np.asarray(r1[0]), np.asarray(r2[0]))
    np.testing.assert_array_equal(np.asarray(r1[1]), np.asarray(r2[1]))


# -- BitBound window soundness (property) ------------------------------------

@settings(max_examples=24, deadline=None)
@given(st.sampled_from(METRICS),
       st.floats(min_value=float(np.float32(0.05)),
                 max_value=float(np.float32(0.95)), width=32),
       st.integers(min_value=0, max_value=2**31 - 1))
def test_bound_window_soundness(metric, cutoff, seed):
    """The defining BitBound property, per metric: any candidate whose f32
    score is >= cutoff has its popcount inside the window every engine
    scans (``bitbound.bound_counts_np``: the Eq.2-style ``[ceil(a*lo),
    floor(a*hi)]`` widened to the f32 boundary) — checked against random
    fingerprints at several densities and widths."""
    rng = np.random.default_rng(seed)
    words = int(rng.choice([4, 7, 32]))
    density = float(rng.uniform(0.05, 0.6))
    db = pack_bits(rng.random((64, words * 32)) < density)
    q = pack_bits(rng.random((4, words * 32)) < density)
    a, b, c = _triples(q, db)
    scores = metric_from_counts_np(metric, c, a[:, None], b[None, :])
    lo_cnt, hi_cnt = bb.bound_counts_np(a, cutoff, metric=metric)
    for qi in range(q.shape[0]):
        qual = scores[qi] >= cutoff
        if not qual.any():
            continue
        bq = b[qual]
        assert np.all(bq >= lo_cnt[qi]), \
            f"{metric.spec} Sc={cutoff}: qualifying count below window"
        assert np.all(bq <= hi_cnt[qi]), \
            f"{metric.spec} Sc={cutoff}: qualifying count above window"


@settings(max_examples=8, deadline=None)
@given(st.sampled_from(METRICS),
       st.sampled_from([0.35, 0.55]),
       st.integers(min_value=0, max_value=10_000))
def test_bitbound_m1_never_drops_qualifying_topk(metric, cutoff, seed):
    """Engine-level soundness sweep: at m=1 (pure window pruning, no fold
    truncation) every true top-k member scoring >= cutoff is returned, for
    arbitrary databases."""
    rng = np.random.default_rng(seed)
    n = int(rng.integers(60, 200))
    db = np.asarray(synthetic_fingerprints(
        SyntheticConfig(n=n, seed=int(rng.integers(0, 1000)))))
    qs = np.asarray(queries_from_db(db, 3, seed=int(rng.integers(0, 1000))))
    k = 5
    eng = BitBoundFoldingEngine(db, cutoff=cutoff, m=1, backend="numpy",
                                metric=metric)
    ids, vals = eng.search(qs, k)
    _assert_results_match_oracle(metric, ids, vals, _oracle(metric, qs, db),
                                 k, cutoff=cutoff)


# -- variable widths ---------------------------------------------------------

ODD_DB = np.asarray(synthetic_fingerprints(
    SyntheticConfig(n=300, length=224, seed=5)))       # 7 words: off-lane
ODD_QS = np.asarray(queries_from_db(ODD_DB, 4, seed=6))


@pytest.mark.parametrize("metric", [METRICS[0], METRICS[2]],
                         ids=[METRICS[0].spec, METRICS[2].spec])
def test_odd_width_brute_and_bitbound(metric):
    assert ODD_DB.shape[1] == 7
    oracle = _oracle(metric, ODD_QS, ODD_DB)
    for backend in ("jnp", "tpu"):
        ids, vals = BruteForceEngine(ODD_DB, backend=backend,
                                     metric=metric).search(ODD_QS, K)
        _assert_results_match_oracle(metric, ids, vals, oracle, K)
    # folded stage-1 at m=2 pads ceil(7/2)=4 words; backends stay in parity
    results = {}
    for backend in ("numpy", "jnp"):
        eng = BitBoundFoldingEngine(ODD_DB, cutoff=0.4, m=2,
                                    backend=backend, metric=metric)
        results[backend] = eng.search(ODD_QS, K)
    _assert_tie_equivalent(*results["numpy"], *results["jnp"],
                           label=f"{metric.spec} odd-width m=2")


def test_odd_width_hnsw():
    index = hn.build_hnsw(ODD_DB, m=4, ef_construction=12, seed=1,
                          metric=Metric("dice"))
    ref_eng = HNSWEngine(ODD_DB, index=index, backend="numpy", ef_search=16)
    dev_eng = HNSWEngine(ODD_DB, index=index, backend="jnp", ef_search=16)
    _assert_tie_equivalent(*ref_eng.search(ODD_QS, K),
                           *dev_eng.search(ODD_QS, K),
                           label="dice odd-width hnsw")


def test_fp_bits_validation():
    # declared width must match the data
    with pytest.raises(ValueError, match="fp_bits"):
        BruteForceEngine(ODD_DB, fp_bits=1024)
    with pytest.raises(ValueError, match="fp_bits"):
        BitBoundFoldingEngine(DB, fp_bits=224)
    # matching declaration is accepted and echoed back resolved
    eng = BruteForceEngine(ODD_DB, fp_bits=224)
    assert eng.fp_bits == 224
    eng = HNSWEngine(DB[:100], m=4, ef_construction=8, fp_bits=1024)
    assert eng.fp_bits == 1024
