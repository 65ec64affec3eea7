"""Modulo-OR compression ("folding") — paper §III-B, Fig. 3, Table I.

For an L-bit fingerprint and folding level m (power of two):

* **Scheme 1** (strided / "modulo"): split into m sections of L/m bits and OR
  the sections together — bit j of the folded print is OR of bits
  j, j + L/m, j + 2L/m, ...  (This is the classic modulo-OR fold; the paper's
  Fig. 3 draws it as OR between L/m-strided sections.)
* **Scheme 2** (adjacent): OR every m neighbouring bits —
  folded bit j = OR of bits j*m .. j*m + m-1.

Scheme 1 preserves accuracy much better (Table I: 99.3% vs 91.5% at m=2)
because Morgan bits are locally correlated: hashing-adjacent bits collide
under scheme 2 far more often than L/m-strided bits.

Two-stage search (GPUsimilarity-style, paper §III-B): stage 1 scans the
m-folded DB (L/m bits/print -> m× less memory traffic) and returns
``k_r1 = k * m * log2(2m)`` candidates; stage 2 rescores only those on the
full-resolution DB and returns the top k.

Because folding only ORs bits, folded Tanimoto is a *biased estimate*; the
two-stage rescore restores exactness on everything stage 1 retains, so the
only error source is stage-1 recall (Table I).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from .fingerprints import WORD_BITS, pack_bits, unpack_bits


def kr1_for(k: int, m: int) -> int:
    """Paper: k_r1 = k * m * log2(2m)."""
    if m <= 1:
        return k
    return int(k * m * math.log2(2 * m))


def folded_words(W: int, m: int) -> int:
    """Folded word count for scheme 1: ceil(W / m) — widths off the m grid
    zero-pad the word axis (OR with zero words is the identity), so every
    ``fp_bits`` that packs to whole words folds at every level."""
    return -(-int(W) // int(m))


def fold_scheme1(words: np.ndarray, m: int, length: int = None) -> np.ndarray:
    """Strided modulo-OR fold of packed prints: (..., W) -> (..., ceil(W/m)).

    With L a multiple of 32*m, sections are whole words: word-level OR of
    m word-sections. Pure word ops — no unpacking needed (and this is how the
    TPU kernel folds on the fly). Word counts not divisible by m are
    zero-padded up to ``folded_words(W, m) * m`` first — identical scores,
    one extra partial section."""
    words = np.asarray(words)
    W = words.shape[-1]
    sec = folded_words(W, m)
    if sec * m != W:
        pad = np.zeros((*words.shape[:-1], sec * m - W), dtype=words.dtype)
        words = np.concatenate([words, pad], axis=-1)
    out = words.reshape(*words.shape[:-1], m, sec)
    result = out[..., 0, :]
    for i in range(1, m):
        result = result | out[..., i, :]
    return result


def fold_scheme1_jax(words: jax.Array, m: int) -> jax.Array:
    """Jit-traceable word-level scheme-1 fold (used on the query path).
    Must stay value-identical to :func:`fold_scheme1`, including the
    odd-width zero padding."""
    W = words.shape[-1]
    sec = folded_words(W, m)
    if sec * m != W:
        pad = jnp.zeros((*words.shape[:-1], sec * m - W), dtype=words.dtype)
        words = jnp.concatenate([words, pad], axis=-1)
    sections = words.reshape(*words.shape[:-1], m, sec)
    out = sections[..., 0, :]
    for i in range(1, m):
        out = out | sections[..., i, :]
    return out


def fold_scheme2_jax(words: jax.Array, m: int) -> jax.Array:
    """Jit-traceable adjacent-OR fold (query path of the device engine).

    Bit-level: unpack each uint32 word to its 32 bits, OR every m neighbouring
    bits, repack. Matches :func:`fold_scheme2` exactly, including the
    zero-bit padding for lengths off the m*32 grid."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (words[..., :, None] >> shifts) & jnp.uint32(1)    # (..., W, 32)
    L = words.shape[-1] * WORD_BITS
    bits = bits.reshape(*words.shape[:-1], L)
    Lp = -(-L // (m * WORD_BITS)) * (m * WORD_BITS)
    if Lp != L:
        bits = jnp.concatenate(
            [bits, jnp.zeros((*bits.shape[:-1], Lp - L), dtype=bits.dtype)],
            axis=-1)
    folded = bits.reshape(*words.shape[:-1], Lp // m, m).max(axis=-1)
    out_words = folded.reshape(*words.shape[:-1], Lp // m // WORD_BITS,
                               WORD_BITS)
    weights = jnp.uint32(1) << shifts
    return jnp.sum(out_words.astype(jnp.uint32) * weights, axis=-1,
                   dtype=jnp.uint32)


def fold_jax(words: jax.Array, m: int, scheme: int = 1) -> jax.Array:
    """Jit-traceable :func:`fold` (both schemes), for on-device query folding."""
    if m == 1:
        return words
    if scheme == 1:
        return fold_scheme1_jax(words, m)
    if scheme == 2:
        return fold_scheme2_jax(words, m)
    raise ValueError(f"unknown folding scheme {scheme}")


def fold_scheme2(words: np.ndarray, m: int) -> np.ndarray:
    """Adjacent-OR fold: unpack, OR every m neighbouring bits, repack.
    Lengths off the m*32 grid zero-pad the bit axis first (identical
    scores; the folded print still packs to whole words)."""
    bits = unpack_bits(words)
    L = bits.shape[-1]
    Lp = -(-L // (m * WORD_BITS)) * (m * WORD_BITS)
    if Lp != L:
        bits = np.concatenate(
            [bits, np.zeros((*bits.shape[:-1], Lp - L), dtype=bits.dtype)],
            axis=-1)
    grouped = bits.reshape(*bits.shape[:-1], Lp // m, m)
    folded = grouped.max(axis=-1)
    return pack_bits(folded)


def fold(words: np.ndarray, m: int, scheme: int = 1) -> np.ndarray:
    if m == 1:
        return np.asarray(words)
    if scheme == 1:
        return fold_scheme1(words, m)
    if scheme == 2:
        return fold_scheme2(words, m)
    raise ValueError(f"unknown folding scheme {scheme}")


@dataclass
class FoldedDB:
    """Compressed + full-resolution database pair for 2-stage search."""
    full: jax.Array      # (N, W) uint32
    folded: jax.Array    # (N, W/m) uint32
    m: int
    scheme: int


def build_folded(db: np.ndarray, m: int, scheme: int = 1) -> FoldedDB:
    return FoldedDB(full=jnp.asarray(db), folded=jnp.asarray(fold(db, m, scheme)),
                    m=m, scheme=scheme)
