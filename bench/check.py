"""Whether what the timed window answered is correct.

Once the window has closed, a sample of its answers drawn from the seed
(and, in an open-loop window, every read-back of an insert) is compared
with the configuration's plain reference at the window's own sizes. The
numbers compared, each held to the limit the configuration file states:

- ``mismatched``: sampled answers whose ids or scores differ from the
  reference in any bit;
- ``lost``: requests that never completed, a minute past the close;
- ``ryw_violations``: read-backs, submitted after their insert was acked,
  whose answer does not hold the inserted row (read-your-writes).

A configuration whose engine answers approximately says so by a
``recall_shortfall`` limit. Its sampled answers are not held to the
reference's answer but to the reference's exact scores, and ``mismatched``
gives way to two numbers:

- ``misscored``: sampled answers with a returned score that is not, bit
  for bit, the reference's score of that query and row, an id that repeats
  or lies outside the visible rows, scores that rise, or padding (id -1,
  score 0) anywhere but at the tail;
- ``recall_shortfall``: 1 - recall@k over the sampled answers. A returned
  row is a hit when its exact score reaches the query's exact k-th best
  (ties at the boundary count); the denominator is the sum over answers of
  min(k, visible rows).

An insert whose ack had not come back when a query was submitted may or may
not be visible to it: a query is matched against the reference over every
prefix of the inserts from those acked before it was submitted to those
called before it completed, and the prefix that answers it best counts.
"""
from __future__ import annotations

import math
import time

import numpy as np

from bench import oracle
from bench.library import seed_rng
from bench.load import Window


def same(ids, sims, want_ids, want_sims) -> bool:
    return (np.array_equal(np.asarray(ids, np.int64), want_ids)
            and np.array_equal(np.asarray(sims, np.float32), want_sims))


def _items(win: Window, n_fixed: int, sample: int, seed: int):
    """(query row, ids, sims, visible lo, visible hi, read-back gid or
    None) for the sampled answers."""
    rng = seed_rng(seed, 4)
    if win.batches:
        done = [(b, j) for b in win.batches if b["error"] is None
                for j in range(len(b["rows"]))]
        pick = rng.choice(len(done), min(sample, len(done)), replace=False)
        return [(("lib", int(b["rows"][j])), b["ids"][j], b["sims"][j],
                 n_fixed, n_fixed, None)
                for b, j in (done[i] for i in np.sort(pick))]
    calls = sorted(op.t_submit for op in win.ops if op.kind == "insert")
    answered = [op for op in win.ops
                if op.kind != "insert" and not op.failed]
    back = [op for op in answered if op.kind == "readback"]
    rest = [op for op in answered if op.kind == "query"]
    pick = rng.choice(len(rest), min(sample, len(rest)), replace=False)
    items = []
    for op in back + [rest[i] for i in np.sort(pick)]:
        called = int(np.searchsorted(calls, op.t_done, side="left"))
        gid = n_fixed + op.row if op.kind == "readback" else None
        src = ("lib", op.row) if op.kind == "query" else ("new", op.row)
        items.append((src, op.ids, op.sims, n_fixed + op.acked_before,
                      n_fixed + max(called, op.acked_before), gid))
    return items


def compare(reference, library, fresh, items) -> int:
    """How many of ``items`` no visible prefix of the reference answers
    the same."""
    def one(i):
        (src, row), ids, sims, lo, hi, _ = items[i]
        q = library[row] if src == "lib" else fresh[row]
        for visible in range(lo, hi + 1):
            if same(ids, sims, *reference.answer(q, visible)):
                return 0
        return 1
    return sum(oracle.map_queries(one, len(items)))


def ryw_violations(win: Window, n_fixed: int) -> int:
    """Read-backs submitted after their insert's ack that miss its row."""
    bad = 0
    for op in win.ops:
        if op.kind != "readback" or op.failed:
            continue
        if op.acked_before > op.row and (n_fixed + op.row) not in op.ids:
            bad += 1
    return bad


def approximate(cfg: dict) -> bool:
    """Whether the configuration's engine answers approximately."""
    return "recall_shortfall" in cfg["limits"]


def grade(reference, q: np.ndarray, ids, sims,
          visible: int) -> tuple[bool, int, int]:
    """One approximate answer over rows ``[0, visible)``: whether it is
    misscored, its hits, and the hits it could have had."""
    ids = np.asarray(ids, np.int64)
    sims = np.asarray(sims, np.float32)
    k = reference.k
    possible = min(k, visible)
    if ids.shape != (k,) or sims.shape != (k,):
        return True, 0, possible
    real = ids != -1
    n_real = int(real.sum())
    valid = real & (ids >= 0) & (ids < visible)
    got = ids[valid]
    exact = reference.score(q, got)
    bad = (bool(real[n_real:].any())              # padding before a row
           or bool((sims[~real] != 0).any())
           or len(got) != n_real                   # an id out of range
           or len(np.unique(got)) != n_real        # an id twice
           or bool((np.diff(sims) > 0).any())
           or not np.array_equal(sims[valid].view(np.uint32),
                                 exact.view(np.uint32)))
    if not possible:
        return bad, 0, 0
    kth = reference.answer(q, visible)[1][possible - 1]
    _, first = np.unique(got, return_index=True)
    return bad, int((exact[first] >= kth).sum()), possible


def grade_all(reference, library, fresh, items) -> tuple[int, int, int]:
    """``misscored``, hits and possible hits over ``items``, each at the
    visible prefix that answers it best."""
    def one(i):
        (src, row), ids, sims, lo, hi, _ = items[i]
        q = library[row] if src == "lib" else fresh[row]
        return min((grade(reference, q, ids, sims, visible)
                    for visible in range(lo, hi + 1)),
                   key=lambda g: (g[0], 1 - g[1] / g[2] if g[2] else 0.0))
    got = oracle.map_queries(one, len(items))
    return tuple(sum(g[i] for g in got) for i in range(3))


def shortfall(hits: int, possible: int) -> float:
    """1 - recall; a sample that could hold no hit shows no recall."""
    return 1.0 - hits / possible if possible else 1.0


def check(Reference, cfg: dict, traffic: dict, win: Window,
          library: np.ndarray, fresh: np.ndarray, n_preload: int,
          seed: int) -> dict:
    """The numbers compared, with what the check itself took."""
    t = time.perf_counter()
    n_new = sum(1 for op in win.ops if op.kind == "insert")
    rows = np.concatenate([library, fresh[:n_preload + n_new]])
    ref = Reference(rows, cfg)
    n_fixed = len(library) + n_preload
    items = _items(win, n_fixed, int(traffic["check_sample"]), seed)
    if approximate(cfg):
        bad, hits, possible = grade_all(ref, library, fresh[n_preload:],
                                        items)
        out = {"misscored": bad,
               "recall_shortfall": shortfall(hits, possible)}
    else:
        out = {"mismatched": compare(ref, library, fresh[n_preload:], items)}
    out["lost"] = (sum(op.error == "lost" for op in win.ops)
                   + sum(b["error"] is not None and "Timeout" in b["error"]
                         for b in win.batches))
    if win.ops:
        out["ryw_violations"] = ryw_violations(win, n_fixed)
    return {"numbers": out, "checked": len(items),
            "seconds": time.perf_counter() - t}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct``, and each number beside its limit."""
    shown = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return all(v <= limits[k] for k, v in numbers.items()), shown


def control_mismatches(Reference, cfg: dict, library: np.ndarray,
                       items, precision: str = "bf16") -> int:
    """The control: the reference put in the program's place, computed in
    ``precision``, compared with the reference as the check compares the
    program. ``items`` are (library row, visible) pairs."""
    exact = Reference(library, cfg)
    low = Reference(library, cfg, precision)

    def one(i):
        row, visible = items[i]
        q = library[row]
        return 0 if same(*low.answer(q, visible),
                         *exact.answer(q, visible)) else 1
    return sum(oracle.map_queries(one, len(items)))


def degraded(wide, k: int, q: np.ndarray, visible: int):
    """The exact top-k with its best ceil(k/4) rows swapped for the best
    rows that score below the k-th, at their true scores: an answer that
    misses a quarter of the true neighbours (rows tied with the k-th would
    be hits, so none of them stands in). ``wide`` is the reference at 4k."""
    c = -(-k // 4)
    ids, sims = wide.answer(q, visible)
    kth = sims[min(k, visible) - 1]
    below = np.flatnonzero((ids != -1) & (sims < kth))[:c]
    keep = np.r_[np.arange(c, min(k, visible)), below]
    return oracle.pad_answer(ids[keep], sims[keep], k)


def control_approximate(Reference, cfg: dict, library: np.ndarray, items,
                        precision: str = "bf16") -> dict:
    """The approximate check's controls over ``items``, (library row,
    visible) pairs: ``misscored`` with the reference computed in
    ``precision`` put in the program's place, and ``recall_shortfall`` of
    the degraded exact answers (``degraded``)."""
    k = int(cfg["k"])
    exact = Reference(library, cfg)
    low = Reference(library, cfg, precision)
    wide = Reference(library, dict(cfg, k=4 * k))

    def one(i):
        row, visible = items[i]
        q = library[row]
        low_bad = grade(exact, q, *low.answer(q, visible), visible)[0]
        return (low_bad,) + grade(exact, q, *degraded(wide, k, q, visible),
                                  visible)
    got = oracle.map_queries(one, len(items))
    return {"misscored": sum(g[0] for g in got),
            "degraded_misscored": sum(g[1] for g in got),
            "recall_shortfall": shortfall(sum(g[2] for g in got),
                                          sum(g[3] for g in got))}


def finite(x: float) -> float:
    """A latency quantile that fell on a failed request is infinite; the
    result line carries it as the largest finite float."""
    return x if math.isfinite(x) else float(np.finfo(np.float64).max)
