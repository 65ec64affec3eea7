"""The least bytes of an HNSW traversal, and its program's device time.

The least bytes come from counts the service keeps (neighbours scored,
nodes expanded, queries answered), so the count is the same whatever
layout the traversal runs on. The device time is that of the traversal's
jitted program, which the trace names on its ``XLA Modules`` line; the
``XLA Ops`` line the other readers use names instructions, not programs.
"""
from __future__ import annotations

import glob

from bench import tracereduce

ROW_BYTES = 128       # a 1024-bit print
ADJ_BYTES = 4         # an int32 neighbour id
QUERY_BYTES = 128
ANSWER_BYTES = 8      # an int32 id and an f32 score per answer slot
MODULES_LINE = "XLA Modules"


def traversal_bytes(evals: float, expansions: float, queries: float,
                    m: int, k: int, fp_bits: int = 1024) -> float:
    """Least HBM bytes of traversals that scored ``evals`` neighbours and
    expanded ``expansions`` nodes for ``queries`` queries: each scored
    neighbour's print, each expanded node's 2M-wide adjacency row, the
    queries and the answers."""
    row = fp_bits // 8
    return (evals * row + expansions * 2 * m * ADJ_BYTES
            + queries * row + queries * k * ANSWER_BYTES)


def program_s(modules: dict, window: tuple, name: str) -> float:
    """Device seconds of the runs of program ``name`` (``jit_<function>``)
    inside ``window``, per device on average. ``modules`` maps each device
    plane to its ``(name, start_s, end_s)`` program runs."""
    if not modules:
        return 0.0
    lo, hi = window
    return sum(e - s for ev in modules.values()
               for n, s, e in tracereduce.clip(ev, lo, hi)
               if n.split("(")[0] == name) / len(modules)


def load_modules(out_dir, window: tuple):
    """The program runs of the newest trace under ``out_dir``, or ``None``
    where there is none or its traced window is not ``window`` (a trace
    left by another run)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    modules, seen = {}, None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ev = [(e.name, e.start_ns / 1e9,
                   (e.start_ns + e.duration_ns) / 1e9)
                  for line in plane.lines if line.name == MODULES_LINE
                  for e in line.events]
            if ev:
                modules[plane.name] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == tracereduce.WINDOW_SPAN:
                        seen = (e.start_ns / 1e9,
                                (e.start_ns + e.duration_ns) / 1e9)
    if seen is None or abs(seen[0] - window[0]) > 1e-6 \
            or abs(seen[1] - window[1]) > 1e-6:
        return None
    return modules
