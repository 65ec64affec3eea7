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

An insert whose ack had not come back when a query was submitted may or may
not be visible to it: a query is matched against the reference over every
prefix of the inserts from those acked before it was submitted to those
called before it completed.
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
    out = {"mismatched": compare(ref, library, fresh[n_preload:], items),
           "lost": sum(op.error == "lost" for op in win.ops)
           + sum(b["error"] is not None and "Timeout" in b["error"]
                 for b in win.batches)}
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


def finite(x: float) -> float:
    """A latency quantile that fell on a failed request is infinite; the
    result line carries it as the largest finite float."""
    return x if math.isfinite(x) else float(np.finfo(np.float64).max)
