"""The benchmark's own synthetic ChEMBL library, drawn on the device.

The model is the one the paper fits to ChEMBL 27.1 Morgan-1024 prints
(Eq. 3) with scaffold structure: molecules come from scaffolds of about
``molecules_per_scaffold`` members; each scaffold is a base pattern whose
popcount follows N(mu, sigma) clipped to [8, bits/4]; each molecule keeps
every scaffold bit with probability ``scaffold_keep`` and then gains fresh
bits, uniformly among the bits it lacks, until its popcount reaches its own
N(mu, sigma) draw. Bits are uniform over positions, as hashed Morgan bits
are.

This copy belongs to the benchmark so that a change to the program cannot
move the yardstick. It draws the same model as the program's host
generator, not the same rows: the normal draws come from numpy (exact on
every platform), the per-bit draws from ``jax.random`` integer bits, and
"pick d random positions among the allowed ones" is a radix select of the
d largest of per-position keys that are unique within a row. Every step is
integer arithmetic, so a seed gives the same library on the CPU and on the
chip. ``bench/tests/test_bench_library.py`` pins it by a digest.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

WORD_BITS = 32
#: rows drawn per device call: (chunk, bits) uint32 keys take 256 MiB
CHUNK_ROWS = 1 << 16
MIN_BITS = 8


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """A numpy generator for stream ``stream`` of run seed ``seed`` (any
    non-negative integer, wider than 32 bits included)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def _jax_key(rng: np.random.Generator):
    return jax.random.key(int(rng.integers(0, 2 ** 31 - 1)))


def _select(keys, d):
    """Mask of the ``d[r]`` largest keys in each row of ``keys`` (uint32,
    unique within a row; 0 marks a position that may not be chosen).
    A 32-step radix select of the d-th largest key."""
    t = jnp.zeros(keys.shape[:1], jnp.uint32)
    for b in range(31, -1, -1):
        cand = t | jnp.uint32(1 << b)
        n = jnp.sum(keys >= cand[:, None], axis=1, dtype=jnp.int32)
        t = jnp.where(n >= d, cand, t)
    return (keys >= t[:, None]) & (keys > 0) & (d > 0)[:, None]


def _unique_keys(key, shape):
    """Random uint32 keys, unique within a row and never 0: random high
    bits over the position plus one in the low 11 bits."""
    pos = jnp.arange(1, shape[1] + 1, dtype=jnp.uint32)
    hi = jax.random.bits(key, shape, jnp.uint32) & jnp.uint32(0xFFFFF800)
    return hi | pos[None, :]


def _pack(bits):
    """(R, L) bool -> (R, L/32) uint32, bit j of word w = position 32w+j."""
    r, length = bits.shape
    w = bits.reshape(r, length // WORD_BITS, WORD_BITS).astype(jnp.uint32)
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    return jnp.sum(w << shifts, axis=-1, dtype=jnp.uint32)


@partial(jax.jit, static_argnames=("bits",))
def _scaffolds(key, counts, *, bits: int):
    keys = _unique_keys(key, (counts.shape[0], bits))
    return _select(keys, counts)


@jax.jit
def _molecules(key, scaffolds, targets, keep_u32):
    k_assign, k_keep, k_add = jax.random.split(key, 3)
    n = targets.shape[0]
    assign = jax.random.randint(k_assign, (n,), 0, scaffolds.shape[0])
    base = scaffolds[assign]
    keep = jax.random.bits(k_keep, base.shape, jnp.uint32) < keep_u32
    have = base & keep
    deficit = jnp.maximum(targets - jnp.sum(have, axis=1, dtype=jnp.int32), 0)
    keys = jnp.where(have, jnp.uint32(0), _unique_keys(k_add, base.shape))
    return _pack(have | _select(keys, deficit))


def _gaussian_counts(rng, n: int, cfg: dict) -> np.ndarray:
    """Popcount draws of the paper's Eq. 3 model, clipped to [8, bits/4]
    and truncated to integers."""
    x = rng.normal(cfg["popcount_mu"], cfg["popcount_sigma"], n)
    return np.clip(x, MIN_BITS, cfg["fp_bits"] // 4).astype(np.int32)


class Library:
    """Scaffolds drawn once from the seed; molecules drawn from them in
    streams, so the library, the inserts and any later draw share one
    chemistry."""

    def __init__(self, cfg: dict, n_rows: int, seed: int):
        self.seed = seed
        self.bits = int(cfg["fp_bits"])
        gen = cfg["generator"]
        self.gen = dict(gen, fp_bits=self.bits)
        n_scaf = max(n_rows // int(gen["molecules_per_scaffold"]), 1)
        rng = seed_rng(seed, 0)
        counts = _gaussian_counts(rng, n_scaf, self.gen)
        self.scaffolds = _scaffolds(_jax_key(rng), jnp.asarray(counts),
                                    bits=self.bits)
        self.keep_u32 = jnp.uint32(
            min(int(round(gen["scaffold_keep"] * 2 ** 32)), 2 ** 32 - 1))

    def molecules(self, n: int, stream: int) -> np.ndarray:
        """``n`` packed molecules of stream ``stream``: (n, bits/32) uint32
        on the host, drawn on the device in chunks."""
        rng = seed_rng(self.seed, 1, stream)
        targets = _gaussian_counts(rng, n, self.gen)
        base = _jax_key(rng)
        out = []
        for i, lo in enumerate(range(0, n, CHUNK_ROWS)):
            t = targets[lo:lo + CHUNK_ROWS]
            # every chunk has the same shape, so one program serves them all
            pad = CHUNK_ROWS - len(t) if n > CHUNK_ROWS else 0
            rows = _molecules(jax.random.fold_in(base, i),
                              self.scaffolds,
                              jnp.asarray(np.pad(t, (0, pad))), self.keep_u32)
            out.append(rows[:len(t)])
        return np.asarray(jnp.concatenate(out)) if out else np.zeros(
            (0, self.bits // WORD_BITS), np.uint32)


def draw_library(cfg: dict, seed: int):
    """The configuration's library (stream 0) from ``seed``, and the
    ``Library`` that draws more of its chemistry (the inserts: stream 1)."""
    lib = Library(cfg, int(cfg["n_molecules"]), seed)
    return lib, lib.molecules(int(cfg["n_molecules"]), stream=0)
