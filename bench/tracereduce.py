"""From a profiler trace to device busy time, kernel time and idle gaps.

Events are ``(name, start_s, end_s)`` on one clock: the device's operations
(the ``XLA Ops`` line of each ``/device:`` plane) and the host's spans
(every line of the ``/host:`` planes, the benchmark's own
``TraceAnnotation``s among them). The traced window is the host span
``bench.traced_window``.

- busy: the union of the intervals in which an operation ran on a device,
  inside the window, averaged over the devices;
- kernel time: the summed device durations of the operations whose HLO
  instruction name holds the kernel's name (the trace prints each
  operation as its HLO text; a Mosaic kernel's custom call is named after
  the jitted function that launches it);
- idle gaps: the parts of the window no device operation covers, laid
  against what the host was doing then, stretch by stretch: each stretch
  takes the name of the innermost host span open in it, preferring spans
  of the program and of JAX over the benchmark's own, the benchmark's
  waiting for an answer (host busy in code with no span) next, and its
  waiting for the next arrival (no work offered) last.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field


WINDOW_SPAN = "bench.traced_window"
DEVICE_OPS_LINE = "XLA Ops"
TOP = 10


@dataclass
class Trace:
    window: tuple                                  # (start_s, end_s)
    device: dict = field(default_factory=dict)     # plane -> [(name, s, e)]
    host: list = field(default_factory=list)       # [(name, s, e)]


def clip(events, lo: float, hi: float):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union_s(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, reach = 0.0, None
    for s, e in sorted(intervals):
        if reach is None or s > reach:
            total += e - s
            reach = e
        elif e > reach:
            total += e - reach
            reach = e
    return total


def busy_s(trace: Trace) -> float:
    """Device-busy seconds in the window, averaged over the devices."""
    lo, hi = trace.window
    if not trace.device:
        return 0.0
    return sum(union_s([(s, e) for _, s, e in clip(ev, lo, hi)])
               for ev in trace.device.values()) / len(trace.device)


def idle_share(trace: Trace) -> float:
    """Share of the window, in %, in which no device operation ran."""
    lo, hi = trace.window
    return 100.0 * (1.0 - busy_s(trace) / (hi - lo))


_HLO = re.compile(r"%?([^\s=]+) = (.*?) ([a-z][\w-]*)\(")


def op_label(text: str) -> str:
    """``name opcode shape`` of an operation printed as HLO text, layouts
    dropped; other names as they are."""
    m = _HLO.match(text)
    if not m:
        return text[:120]
    shape = re.sub(r"\{[^{}]*\}", "", m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape}"[:120]


def kernel_s(trace: Trace, names) -> float:
    """Summed device seconds of the operations whose instruction name
    holds one of ``names``, per device on average."""
    lo, hi = trace.window
    if not trace.device:
        return 0.0
    return sum(e - s for ev in trace.device.values()
               for n, s, e in clip(ev, lo, hi)
               if any(k in op_label(n).split(" ")[0] for k in names)
               ) / len(trace.device)


def gaps(trace: Trace) -> list:
    """(start, end) stretches of the window in which the first device ran
    nothing."""
    lo, hi = trace.window
    ev = next(iter(trace.device.values()), [])
    out, t = [], lo
    for _, s, e in sorted(clip(ev, lo, hi), key=lambda x: x[1]):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _rank(name: str) -> int:
    if name == WINDOW_SPAN:
        return 9
    if name.startswith("bench.wait_arrival"):
        return 3
    if name.startswith("bench.await"):
        return 2
    if name.startswith("bench."):
        return 1
    return 0


def idle_by_activity(gap_list, host) -> dict:
    """Seconds of the gaps by what the host was doing: each stretch between
    span edges goes to the best-ranked span open over it, the innermost
    (latest started) among equals; "unattributed" where none is open. One
    sweep over the sorted span edges."""
    edges = sorted((t, kind, i) for i, (n, s, e) in enumerate(host)
                   if _rank(n) < 9 for t, kind in ((s, 1), (e, 0)))
    out = defaultdict(float)
    active: set = set()
    k = 0

    def label():
        best = min(((_rank(host[i][0]), -host[i][1], host[i][0])
                    for i in active), default=None)
        return best[2] if best else "unattributed"

    for lo, hi in sorted(gap_list):
        while k < len(edges) and edges[k][0] <= lo:
            t, kind, i = edges[k]
            (active.add if kind else active.discard)(i)
            k += 1
        t0 = lo
        while k < len(edges) and edges[k][0] < hi:
            t, kind, i = edges[k]
            out[label()] += t - t0
            (active.add if kind else active.discard)(i)
            t0 = t
            k += 1
        out[label()] += hi - t0
    return out


def breakdown(trace: Trace) -> dict:
    """The device operations that took most time, and the idle time by
    what the host was doing, each at most ``TOP`` entries."""
    lo, hi = trace.window
    ops = defaultdict(float)
    for ev in trace.device.values():
        for n, s, e in clip(ev, lo, hi):
            ops[op_label(n)] += (e - s) / len(trace.device)
    idle = idle_by_activity(gaps(trace), trace.host)
    top = lambda d: [[n, v] for n, v in  # noqa: E731
                     sorted(d.items(), key=lambda x: -x[1])[:TOP]
                     if v > 0]
    return {"device_ops": top(ops), "idle_gaps": top(idle)}


def load(out_dir) -> Trace:
    """Read the newest ``.xplane.pb`` under ``out_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {out_dir}")
    data = ProfileData.from_file(paths[-1])
    device, host, window = {}, [], None
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ev = [(e.name, e.start_ns / 1e9,
                   (e.start_ns + e.duration_ns) / 1e9)
                  for line in plane.lines if line.name == DEVICE_OPS_LINE
                  for e in line.events]
            if ev:
                device[plane.name] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns / 1e9,
                            (e.start_ns + e.duration_ns) / 1e9)
                    if e.name == WINDOW_SPAN:
                        window = span[1:]
                    elif e.duration_ns > 0:
                        host.append(span)
    if window is None:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    return Trace(window=window, device=device, host=host)
