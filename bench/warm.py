"""Which batches set-up sends, so that nothing compiles in the window.

The served path compiles a program per batch bucket and, for a windowed
engine, per bucket of the batch's widest Eq. 2 window. What the window will
ask is known before it opens, so the batches it can make are too:

- closed loop: each batch is drawn from the seed; set-up looks at the first
  ``CLOSED_BATCHES`` of them (many more than a window sends) and warms one
  batch for each popcount that is some batch's widest;
- open loop: the front end hands the service what arrived since its
  dispatcher last ran, a run of consecutive arrivals. A run longer than the
  arrivals of ``STALL_S`` at the cell's rate (beyond a 1e-9 Poisson tail)
  would need the dispatcher held for longer than that. For every length up
  to that, set-up warms one batch for each popcount that is the widest of
  some run of that length, padded to the length's power-of-two bucket with
  the narrowest query.

A warm batch is the widest query and copies of the narrowest, so it meets
the same programs as the batches it stands for, whatever the program
buckets: it sees the same batch size and the same widest window.
"""
from __future__ import annotations

import math

import numpy as np

from bench import load, oracle

#: the longest the served process was seen to stand still on the chip was
#: 0.33 s (every thread silent in the trace, the device idle)
STALL_S = 0.5
TAIL = 1e-9
CLOSED_BATCHES = 4096


def poisson_max(mean: float, tail: float = TAIL) -> int:
    """The least n with P(N > n) < ``tail`` for N ~ Poisson(mean)."""
    n, cdf = 0, 0.0
    while True:
        log_pmf = (n * math.log(mean) if mean > 0 else 0.0) - mean \
            - math.lgamma(n + 1)
        cdf += math.exp(log_pmf)
        if 1.0 - cdf < tail:
            return n
        n += 1


def longest_run(traffic: dict) -> int:
    """The most consecutive arrivals an open-loop batch can hold."""
    return min(int(traffic["service"]["max_batch"]),
               poisson_max(float(traffic["rate_per_s"]) * STALL_S))


def window_rows(counts: np.ndarray, cfg: dict) -> np.ndarray:
    """Library rows in the Eq. 2 window of a query of each popcount
    0..bits (the whole library for an engine without a window)."""
    bits = int(cfg["fp_bits"])
    if "cutoff" not in cfg:
        return np.full(bits + 1, len(counts))
    hist = np.bincount(counts, minlength=bits + 1)
    return np.array([hist[oracle.window_mask(a, cfg["cutoff"], bits)].sum()
                     for a in range(bits + 1)])


def plan(traffic: dict, cfg: dict, library: np.ndarray,
         library_counts: np.ndarray, ops: list, fresh: np.ndarray,
         seed: int) -> list:
    """Batches (arrays of rows) to send once before the window: one for
    each (batch bucket, widest query) the window can make, padded with the
    narrowest query."""
    w = window_rows(library_counts, cfg)
    windowed = "cutoff" in cfg
    need = {}
    if traffic["loop"] == "closed":
        size = int(traffic["queries_per_batch"])
        for i in range(CLOSED_BATCHES):
            rows = load.closed_batch(seed, i, len(library), size)
            j = rows[np.argmax(w[library_counts[rows]])]
            key = int(library_counts[j]) if windowed else 0
            need.setdefault((size, key), library[j])
        low = library[np.argmin(w[library_counts])]
    else:
        rows = np.stack([library[op.row] if op.kind == "query"
                         else fresh[op.row] for op in ops
                         if op.kind != "insert"])
        pc = np.bitwise_count(rows).sum(-1).astype(np.int64)
        wq = w[pc]
        low = rows[np.argmin(wq)]
        for n in range(1, min(longest_run(traffic), len(rows)) + 1):
            runs = np.lib.stride_tricks.sliding_window_view(wq, n)
            widest = np.argmax(runs, axis=1) + np.arange(len(runs))
            bucket = 1 << (n - 1).bit_length()
            for j in widest:
                key = int(pc[j]) if windowed else 0
                need.setdefault((bucket, key), rows[j])
    return [np.stack([row] + [low] * (b - 1))
            for (b, _), row in sorted(need.items(), key=lambda x: x[0])]
