"""Synthetic ChEMBL-like fingerprint generator (DESIGN.md §4).

ChEMBL 27.1 + RDKit are unavailable offline; the paper itself models the
database popcount distribution as Gaussian (Eq. 3). We generate 1024-bit
prints whose popcount ~ N(mu=62, sigma=22) (clipped), with *scaffold
structure*: molecules are drawn from clusters, each cluster sharing a base
bit pattern with per-molecule mutations. This keeps nearest-neighbour
structure realistic (without clusters, i.i.d. prints make every search
algorithm look artificially good/bad).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

from ..core.fingerprints import pack_bits


@dataclass(frozen=True)
class SyntheticConfig:
    n: int = 100_000
    length: int = 1024
    mu: float = 62.0          # ChEMBL Morgan-1024 mean popcount (paper Eq. 3 fit)
    sigma: float = 22.0
    n_scaffolds: int = 0      # 0 -> n // 50
    scaffold_keep: float = 0.7  # fraction of bits inherited from the scaffold
    bit_skew: float = 0.0     # optional zipf-ish exponent of the per-bit
    #   frequency distribution (0 = uniform, like hash-based Morgan bits).
    #   NOTE on paper Table I: the paper measures strided folding (scheme 1)
    #   beating adjacent folding (scheme 2) on real ChEMBL prints. That gap
    #   depends on RDKit's actual bit-layout correlations, which no synthetic
    #   layout reproduces faithfully: under uniform bits the two schemes are
    #   statistically identical (verified), and under popularity-sorted
    #   layouts scheme 2 can even win. We reproduce the scheme-independent
    #   claims (accuracy vs m trend, two-stage rescore recovery) and document
    #   this as a data-fidelity gap — see EXPERIMENTS.md §Folding fidelity.
    seed: int = 0


def _bit_probs(cfg) -> np.ndarray:
    L = cfg.length
    if cfg.bit_skew <= 0:
        return np.full(L, 1.0 / L)
    p = 1.0 / np.power(np.arange(L) + 8.0, cfg.bit_skew)
    return p / p.sum()


#: libraries up to this size draw every molecule from one random stream in
#: one pass, row for row the library this generator has always produced:
#: every test library was made from such a stream and stays reproducible.
#: Larger ones (a different library from the one-pass stream's) draw
#: chunks of ``CHUNK_ROWS // 8`` rows from
#: independent child streams, split across worker threads (numpy releases
#: the GIL in the heavy kernels). Each worker reuses one pair of
#: (chunk, length) float64 buffers for all its chunks: the host holds
#: ~0.27 GB per worker, and no chunk allocates or frees large arrays (freed
#: memory may be handed back to the host more slowly than a worker churns
#: through it, so the resident set would otherwise grow with the library)
CHUNK_ROWS = 1 << 17


def synthetic_fingerprints(cfg: SyntheticConfig) -> np.ndarray:
    """Returns packed (n, length//32) uint32 fingerprints."""
    rng = np.random.default_rng(cfg.seed)
    n_scaf = cfg.n_scaffolds or max(cfg.n // 50, 1)
    L = cfg.length
    probs = _bit_probs(cfg)
    # scaffold base patterns: popcount drawn from the Gaussian model,
    # bit positions drawn from the skewed frequency law
    scaf_counts = np.clip(rng.normal(cfg.mu, cfg.sigma, n_scaf), 8, L // 4).astype(np.int64)
    scaffolds = np.zeros((n_scaf, L), dtype=np.uint8)
    for i, c in enumerate(scaf_counts):
        scaffolds[i, rng.choice(L, size=c, replace=False, p=probs)] = 1
    if cfg.n <= CHUNK_ROWS:
        return _molecules(rng, cfg, scaffolds, probs, _buffers(cfg.n, L))
    chunk = CHUNK_ROWS // 8
    starts = list(range(0, cfg.n, chunk))
    children = rng.spawn(len(starts))
    workers = min(8, os.cpu_count() or 1)
    out = np.empty((cfg.n, L // 32), dtype=np.uint32)

    def work(w):
        buf = _buffers(chunk, L)
        for lo, child in zip(starts[w::workers], children[w::workers]):
            hi = min(lo + chunk, cfg.n)
            out[lo:hi] = _molecules(child, cfg, scaffolds, probs,
                                    tuple(b[:hi - lo] for b in buf))

    with ThreadPoolExecutor(max_workers=workers) as ex:
        list(ex.map(work, range(workers)))
    return out


def _buffers(n: int, L: int):
    return np.empty((n, L)), np.empty((n, L))


def _molecules(rng, cfg: SyntheticConfig, scaffolds: np.ndarray,
               probs: np.ndarray, buf) -> np.ndarray:
    """``len(buf[0])`` packed molecules drawn from the scaffolds, computed
    in the two (n, length) float64 buffers ``buf``."""
    noise, srt = buf
    n, L = noise.shape
    assign = rng.integers(0, scaffolds.shape[0], size=n)
    base = scaffolds[assign]
    # per-molecule: keep `scaffold_keep` of scaffold bits, add fresh feature bits
    rng.random(out=noise)
    bits = (base & (noise < cfg.scaffold_keep)).astype(np.uint8)
    target = np.clip(rng.normal(cfg.mu, cfg.sigma, n), 8, L // 4).astype(np.int64)
    deficit = np.maximum(target - bits.sum(axis=1, dtype=np.int64), 0).astype(np.int64)
    # add extra bits, frequency-weighted (vectorised: weighted random scores,
    # take the top-deficit new bits per row)
    rng.random(out=noise)
    np.power(noise, 1.0 / np.maximum(probs * L, 1e-9), out=noise)
    np.multiply(noise, 1 - bits, out=noise)
    np.copyto(srt, noise)
    srt.sort(axis=1)                    # ascending: the deficit-th largest
    thresh = srt[np.arange(n), L - 1 - np.minimum(deficit, L - 1)]
    bits |= (noise > thresh[:, None]).astype(np.uint8)
    return pack_bits(bits)


def queries_from_db(db: np.ndarray, n_queries: int, seed: int = 1) -> np.ndarray:
    """Paper-style query set: random database members (self-hit included in
    ground truth, as in the ChEMBL benchmarks). Asking for more queries than
    the database holds falls back to sampling with replacement (and warns)
    instead of crashing — small serve/CI configs hit this routinely."""
    rng = np.random.default_rng(seed)
    n = db.shape[0]
    replace = n_queries > n
    if replace:
        import warnings
        warnings.warn(
            f"queries_from_db: {n_queries} queries requested from a database "
            f"of {n}; sampling with replacement", stacklevel=2)
    idx = rng.choice(n, size=n_queries, replace=replace)
    return np.asarray(db)[idx]
