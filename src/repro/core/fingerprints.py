"""Packed binary molecular fingerprints and bit-vector similarity metrics.

Fingerprints are L-bit binary vectors (paper: L=1024, Morgan radius-2).
We store them packed little-endian into ``uint32`` words, shape (..., L//32),
so a database of N molecules is an ``(N, 32)`` uint32 array for L=1024.
All similarity math runs on packed words via ``lax.population_count`` —
the TPU-native analogue of the paper's BitCnt LUT tree (DESIGN.md §2).

Every supported metric is a rational function of the same popcount triple
``(a=|A|, b=|B|, c=|A∩B|)`` the kernels already compute, so metric choice
is a trace-time parameter (:class:`Metric`): each (metric, shape) pair
compiles once and the inner loop is unchanged (docs/ARCHITECTURE.md
§Metric parameterization). Tanimoto and Dice divide through
:func:`exact_ratio`, the IEEE quotient on every backend — TPU v5e's f32
divide is off by an ulp on some popcount pairs, which would break the
device's bit-equality with the numpy oracle and between backends.
"""
from __future__ import annotations

import functools
import re
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32
DEFAULT_LEN = 1024  # paper: 1024-bit Morgan fingerprint

METRIC_NAMES = ("tanimoto", "dice", "cosine", "tversky")
TVERSKY_SCALE = 256  # tversky weights quantize to this dyadic grid


@dataclass(frozen=True)
class Metric:
    """A bit-vector similarity over the popcount triple (a, b, c).

    * ``tanimoto``  c / (a + b - c)
    * ``dice``      2c / (a + b)
    * ``cosine``    c / sqrt(a * b)
    * ``tversky``   c / (c + alpha*(a - c) + beta*(b - c)) — asymmetric
      when alpha != beta (alpha weighs the query-only bits, beta the
      database-only bits; alpha = beta = 0.5 is Dice, 1/1 is Tanimoto).

    Frozen and hashable: instances are used directly as jit static
    arguments and engine cache-key components. ``alpha``/``beta`` are
    only meaningful for ``tversky`` and are pinned to 0 elsewhere so two
    descriptors of the same metric always compare equal.

    Tversky weights are quantized to the 1/256 grid (``TVERSKY_SCALE``)
    at construction: the score is then a single f32 divide of exact
    integers (``256c / (256c + p_a(a-c) + p_b(b-c))``), which is the only
    formulation that survives XLA:CPU's fast-math FMA contraction — a
    fused mul+add chain evaluates 1-ulp differently inside vs outside jit,
    so a float-weighted denominator cannot satisfy the cross-engine
    bit-parity contract. 0, 0.5 and 1 are on the grid, so the Dice and
    Tanimoto correspondences stay exact.
    """
    name: str = "tanimoto"
    alpha: float = 0.0
    beta: float = 0.0

    def __post_init__(self):
        if self.name not in METRIC_NAMES:
            raise ValueError(
                f"unknown metric {self.name!r}; expected one of {METRIC_NAMES}")
        if self.name != "tversky" and (self.alpha or self.beta):
            raise ValueError(
                f"alpha/beta only apply to tversky, not {self.name!r}")
        if self.name == "tversky":
            if self.alpha < 0 or self.beta < 0:
                raise ValueError("tversky alpha/beta must be >= 0")
            if self.alpha > 16 or self.beta > 16:
                raise ValueError("tversky alpha/beta must be <= 16 "
                                 "(integer-exact arithmetic bound)")
            object.__setattr__(
                self, "alpha", round(self.alpha * TVERSKY_SCALE) / TVERSKY_SCALE)
            object.__setattr__(
                self, "beta", round(self.beta * TVERSKY_SCALE) / TVERSKY_SCALE)

    @property
    def spec(self) -> str:
        """Canonical string form, round-trippable via :func:`resolve_metric`
        (what snapshot meta / ServiceConfig store)."""
        if self.name == "tversky":
            return f"tversky({self.alpha!r},{self.beta!r})"
        return self.name

    @property
    def bounded_below(self) -> bool:
        """A popcount lower bound exists (b >= f(a, Sc) for sim >= Sc)."""
        return self.name != "tversky" or self.alpha > 0

    @property
    def bounded_above(self) -> bool:
        return self.name != "tversky" or self.beta > 0

    @property
    def bounded(self) -> bool:
        """Whether a non-trivial BitBound-style popcount window exists at
        all. Metrics without one (tversky with alpha=beta=0: any overlap
        scores 1) fall back to a full scan — ``scanned`` reflects it."""
        return self.bounded_below or self.bounded_above

    def bound_ratios(self, cutoff: float):
        """Per-metric Eq.2-style popcount window as multipliers of the
        query popcount ``a``: sim(q, d) >= cutoff requires
        ``lo_ratio * a <= b <= hi_ratio * a``. Derivations in
        docs/ARCHITECTURE.md §Metric parameterization; all follow from
        max_c sim at c = min(a, b). Returned as python floats so both the
        float64 host windows and the float32 device masks scale ``a`` by
        the same precomputed constant. Unbounded sides are 0.0 / +inf.
        """
        sc = max(float(cutoff), 1e-6)
        if self.name == "tanimoto":
            # max sim = min/max  =>  b in [a*Sc, a/Sc] (paper Eq. 2)
            return float(cutoff), 1.0 / sc
        if self.name == "dice":
            # max sim = 2*min/(a+b)  =>  b in [a*Sc/(2-Sc), a*(2-Sc)/Sc]
            return float(cutoff) / (2.0 - float(cutoff)), (2.0 - float(cutoff)) / sc
        if self.name == "cosine":
            # max sim = sqrt(min/max)  =>  b in [a*Sc^2, a/Sc^2]
            return float(cutoff) ** 2, 1.0 / sc**2
        # tversky: for b <= a, max sim = b/(b + alpha*(a-b)) >= Sc
        #   <=> b >= a * Sc*alpha / (1 - Sc + Sc*alpha); symmetric above.
        lo = (float(cutoff) * self.alpha
              / max(1.0 - float(cutoff) + float(cutoff) * self.alpha, 1e-9)
              if self.bounded_below else 0.0)
        hi = ((1.0 - float(cutoff) + float(cutoff) * self.beta)
              / max(float(cutoff) * self.beta, 1e-9)
              if self.bounded_above else float("inf"))
        return lo, hi


TANIMOTO = Metric("tanimoto")

_TVERSKY_RE = re.compile(
    r"^tversky\(\s*([0-9.eE+-]+)\s*,\s*([0-9.eE+-]+)\s*\)$")


def resolve_metric(metric) -> Metric:
    """Coerce ``None`` / name string / spec string / Metric to a Metric."""
    if metric is None:
        return TANIMOTO
    if isinstance(metric, Metric):
        return metric
    if isinstance(metric, str):
        m = _TVERSKY_RE.match(metric)
        if m:
            return Metric("tversky", float(m.group(1)), float(m.group(2)))
        if metric == "tversky":
            return Metric("tversky", 0.5, 0.5)   # Dice-weighted default
        return Metric(metric)
    raise TypeError(f"cannot resolve metric from {metric!r}")


def exact_ratio(num, den):
    """IEEE (correctly rounded) f32 quotient of int32 ``0 <= num <= den``,
    ``0 < den < 2**16``, from ops every backend computes exactly.

    An accelerator's f32 divide may land an ulp off the IEEE quotient; one
    residual step puts it back. The residual ``q * den - num`` is exact:
    ``q`` is split into three 8-bit pieces, each piece times a 16-bit
    integer fits a 24-bit significand, and the partial sums are
    representable. ``q - residual / den`` then sits far closer to
    ``num / den`` than ``num / den`` (16-bit denominator) ever sits to a
    rounding midpoint, so its rounding is the IEEE one."""
    n = num.astype(jnp.float32)
    d = den.astype(jnp.float32)
    return _correct(n / d, n, d)


def _correct(q, n, d):
    """The IEEE quotient ``n / d`` (f32 holding integers, as in
    :func:`exact_ratio`) from an approximation ``q`` a few ulp off it."""
    def top8(x):     # x with its significand cut to the leading 8 bits
        bits = jax.lax.bitcast_convert_type(x, jnp.int32) & -(1 << 16)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)

    q1 = top8(q)
    q2 = top8(q - q1)
    q3 = (q - q1) - q2
    residual = ((q1 * d - n) + q2 * d) + q3 * d
    return q - residual / d


def metric_from_counts(metric: Metric, inter, q_cnt, d_cnt):
    """Trace-time metric dispatch on the popcount triple (jnp arrays).

    ``inter``/``q_cnt``/``d_cnt`` are int32 (broadcastable); returns f32.
    Every kernel body and engine closure routes through here. Tanimoto and
    Dice are the IEEE quotient (:func:`exact_ratio`); cosine and Tversky
    use the backend's f32 divide.
    """
    if metric.name == "tanimoto":
        union = q_cnt + d_cnt - inter
        return jnp.where(union > 0, exact_ratio(inter, jnp.maximum(union, 1)),
                         0.0)
    if metric.name == "dice":
        denom = q_cnt + d_cnt
        return jnp.where(denom > 0,
                         exact_ratio(2 * inter, jnp.maximum(denom, 1)), 0.0)
    if metric.name == "cosine":
        # sqrt of the exact integer ratio c^2/(a*b), NOT c / sqrt(a*b) or
        # c * rsqrt(a*b): XLA's algebraic simplifier rewrites
        # divide-by-sqrt into multiply-by-rsqrt inside jitted programs but
        # not in eager dispatch (1 ulp nondeterminism across compilation
        # contexts), and the rsqrt form loses the q==d -> exactly-1.0
        # corner. c^2 and a*b are exact ints, so this is two
        # correctly-rounded f32 ops (one divide, one sqrt) with no
        # divide-by-sqrt pattern for the simplifier to touch.
        prod = q_cnt * d_cnt
        ratio = ((inter * inter).astype(jnp.float32)
                 / jnp.maximum(prod, 1).astype(jnp.float32))
        return jnp.where(prod > 0, jnp.sqrt(ratio), 0.0)
    # tversky — exact int32 numerator/denominator on the quantized weight
    # grid, then one f32 divide: no mul+add chain for fast-math to contract
    pa = int(round(metric.alpha * TVERSKY_SCALE))
    pb = int(round(metric.beta * TVERSKY_SCALE))
    num = TVERSKY_SCALE * inter
    den = num + pa * (q_cnt - inter) + pb * (d_cnt - inter)
    return jnp.where(den > 0,
                     num.astype(jnp.float32) / den.astype(jnp.float32), 0.0)


def metric_from_counts_np(metric: Metric, inter, q_cnt, d_cnt) -> np.ndarray:
    """Numpy oracle twin of :func:`metric_from_counts`, returning f32
    values bit-equal to the device f32 path.

    tanimoto/dice divide in float64 and cast (safe: double rounding of
    p/q with q <= 2^12 can only disagree with single f32 rounding at
    exact f32 halfway points, where both round half-even identically);
    tversky is a single f32 divide of exact scaled integers; cosine has a
    second rounding (sqrt of the divide), so it mirrors the f32 op order.
    """
    inter = np.asarray(inter, dtype=np.int64)
    q_cnt = np.asarray(q_cnt, dtype=np.int64)
    d_cnt = np.asarray(d_cnt, dtype=np.int64)
    if metric.name == "tanimoto":
        union = q_cnt + d_cnt - inter
        s = np.where(union > 0, inter / np.maximum(union, 1), 0.0)
        return s.astype(np.float32)
    if metric.name == "dice":
        denom = q_cnt + d_cnt
        s = np.where(denom > 0, 2 * inter / np.maximum(denom, 1), 0.0)
        return s.astype(np.float32)
    if metric.name == "cosine":
        # mirror the device op order exactly: one f32 divide of the exact
        # integer ratio c^2/(a*b), one f32 sqrt — see metric_from_counts
        prod = q_cnt * d_cnt
        ratio = ((inter * inter).astype(np.float32)
                 / np.maximum(prod, 1).astype(np.float32))
        return np.where(prod > 0, np.sqrt(ratio, dtype=np.float32),
                        np.float32(0.0)).astype(np.float32)
    # exact int64 numerator/denominator on the quantized grid, one f32
    # divide — same two correctly-rounded ops as the device path
    pa = int(round(metric.alpha * TVERSKY_SCALE))
    pb = int(round(metric.beta * TVERSKY_SCALE))
    num = TVERSKY_SCALE * inter
    den = num + pa * (q_cnt - inter) + pb * (d_cnt - inter)
    return np.where(den > 0,
                    num.astype(np.float32)
                    / np.maximum(den, 1).astype(np.float32),
                    np.float32(0.0)).astype(np.float32)


def n_words(length: int = DEFAULT_LEN) -> int:
    if length % WORD_BITS != 0:
        raise ValueError(f"fingerprint length {length} must be a multiple of {WORD_BITS}")
    return length // WORD_BITS


def pack_bits(bits: np.ndarray) -> np.ndarray:
    """Pack a (..., L) 0/1 array into (..., L//32) uint32 words (little-endian)."""
    bits = np.asarray(bits, dtype=np.uint8)
    L = bits.shape[-1]
    w = n_words(L)
    shaped = bits.reshape(*bits.shape[:-1], w, WORD_BITS).astype(np.uint32)
    weights = (np.uint32(1) << np.arange(WORD_BITS, dtype=np.uint32))
    return (shaped * weights).sum(axis=-1, dtype=np.uint32)


def unpack_bits(words: np.ndarray, length: int | None = None) -> np.ndarray:
    """Inverse of :func:`pack_bits` -> (..., L) uint8."""
    words = np.asarray(words, dtype=np.uint32)
    L = length or words.shape[-1] * WORD_BITS
    shifts = np.arange(WORD_BITS, dtype=np.uint32)
    bits = (words[..., :, None] >> shifts) & np.uint32(1)
    return bits.reshape(*words.shape[:-1], words.shape[-1] * WORD_BITS)[..., :L].astype(np.uint8)


def popcount(words: jax.Array) -> jax.Array:
    """Number of set bits per fingerprint: (..., W) uint32 -> (...,) int32."""
    per_word = jax.lax.population_count(words)
    return jnp.sum(per_word.astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnames=())
def tanimoto(a: jax.Array, b: jax.Array) -> jax.Array:
    """Tanimoto similarity between packed fingerprints (broadcasting).

    a: (..., W) uint32, b: (..., W) uint32 -> (...,) float32 in [0, 1].
    S = |A&B| / |A|B| = c / (cnt_a + cnt_b - c).  Empty/empty pairs -> 0.
    """
    return metric_from_counts(TANIMOTO, popcount(a & b), popcount(a),
                              popcount(b))


def tanimoto_scores(query: jax.Array, db: jax.Array, db_popcount: jax.Array | None = None) -> jax.Array:
    """Scores of one packed query (W,) against a packed DB (N, W) -> (N,) f32.

    ``db_popcount`` may be precomputed (the paper stores DB bit counts once —
    the BitCnt stage runs per query only on the query itself).
    """
    return metric_scores(query, db, TANIMOTO, db_popcount)


def batched_tanimoto_scores(queries: jax.Array, db: jax.Array,
                            db_popcount: jax.Array | None = None) -> jax.Array:
    """(Q, W) x (N, W) -> (Q, N) f32 score matrix (brute-force reference)."""
    return batched_metric_scores(queries, db, TANIMOTO, db_popcount)


def metric_scores(query: jax.Array, db: jax.Array, metric: Metric = TANIMOTO,
                  db_popcount: jax.Array | None = None) -> jax.Array:
    """Metric-generic twin of :func:`tanimoto_scores` ((W,) x (N, W) -> (N,))."""
    inter = popcount(query[None, :] & db)
    q_cnt = popcount(query)
    d_cnt = popcount(db) if db_popcount is None else db_popcount
    return metric_from_counts(metric, inter, q_cnt, d_cnt)


def batched_metric_scores(queries: jax.Array, db: jax.Array,
                          metric: Metric = TANIMOTO,
                          db_popcount: jax.Array | None = None) -> jax.Array:
    """Metric-generic twin of :func:`batched_tanimoto_scores`."""
    if db_popcount is None:
        db_popcount = popcount(db)
    q_cnt = popcount(queries)
    inter = popcount(queries[:, None, :] & db[None, :, :])
    return metric_from_counts(metric, inter, q_cnt[:, None],
                              db_popcount[None, :])


def batched_metric_scores_np(queries: np.ndarray, db: np.ndarray,
                             metric: Metric = TANIMOTO,
                             db_popcount: np.ndarray | None = None
                             ) -> np.ndarray:
    """Host oracle: (Q, W) x (N, W) -> (Q, N) f32, bit-equal to the device
    f32 paths (see :func:`metric_from_counts_np`)."""
    queries = np.asarray(queries)
    db = np.asarray(db)
    if db_popcount is None:
        db_popcount = np.bitwise_count(db).sum(-1, dtype=np.int64)
    q_cnt = np.bitwise_count(queries).sum(-1, dtype=np.int64)
    inter = np.bitwise_count(queries[:, None, :] & db[None, :, :]).sum(
        -1, dtype=np.int64)
    return metric_from_counts_np(metric, inter, q_cnt[:, None],
                                 np.asarray(db_popcount, np.int64)[None, :])
