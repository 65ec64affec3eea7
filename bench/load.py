"""The one general traffic generator, and the two ways of offering it.

A traffic file (``traffic/<name>.json``) holds parameters only. Its
``loop`` says how the load is offered:

- ``closed``: one client sends a batch of ``queries_per_batch`` library
  members, waits for the answer, and sends the next, until ``seconds``
  have passed. Each batch is a new draw from the seed.
- ``open``: requests arrive on a schedule fixed before the window, at
  ``rate_per_s`` for ``seconds``, whatever the system does. A share
  ``insert_share`` of the operations are single-row inserts of fresh
  molecules; the first query due ``readback_delay_ms`` after an insert asks
  for the inserted molecule itself. The arrival times and the places of
  the inserts are one Poisson draw (stream ``arrival_stream``), the same for
  every seed; the seed draws the library, the queries and the molecules.
  In a queue at this load the order of arrivals alone moves a p95 by about
  a tenth from one draw to the next, so a seed that reordered them would
  change the work.

Latency runs from each request's due time. A request that is refused, that
expires, or that never completes is failed and counts as infinitely late.
"""
from __future__ import annotations

import math
import queue
import threading
import time
from dataclasses import dataclass, field

import numpy as np

from bench.library import seed_rng

#: how long past the window's close a run waits for late answers
LATE_S = 60.0


@dataclass
class Op:
    due: float                  # seconds after the window opens
    kind: str                   # "query" | "readback" | "insert"
    row: int                    # library row (query) / insert number
    t_submit: float = math.nan
    t_done: float = math.nan
    acked_before: int = 0       # inserts acked when it was submitted
    error: str | None = None
    ids: np.ndarray | None = None
    sims: np.ndarray | None = None

    @property
    def failed(self) -> bool:
        return self.error is not None

    def latency_s(self, t0: float) -> float:
        return math.inf if self.failed else self.t_done - (t0 + self.due)


@dataclass
class Window:
    t0: float
    ops: list = field(default_factory=list)         # open loop
    batches: list = field(default_factory=list)     # closed loop
    traced: list = field(default_factory=list)      # batches in the trace


# -- schedules -----------------------------------------------------------------

def open_schedule(traffic: dict, seconds: float, seed: int,
                  n_library: int) -> tuple[list, int]:
    """The operations of one open-loop window and the number of inserts.
    Conditioned on their number, Poisson arrivals in [0, seconds) are the
    normalised partial sums of exponential gaps."""
    rate = float(traffic["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    trace = np.random.default_rng(int(traffic.get("arrival_stream", 0)))
    gaps = trace.exponential(1.0, n + 1)
    due = seconds * np.cumsum(gaps)[:n] / gaps.sum()
    n_ins = int(round(float(traffic.get("insert_share", 0.0)) * n))
    is_ins = np.zeros(n, bool)
    is_ins[trace.choice(n, n_ins, replace=False)] = True
    rows = seed_rng(seed, 2).integers(0, n_library, n)
    ops, ins_no = [], 0
    for i in range(n):
        if is_ins[i]:
            ops.append(Op(float(due[i]), "insert", ins_no))
            ins_no += 1
        else:
            ops.append(Op(float(due[i]), "query", int(rows[i])))
    delay = float(traffic.get("readback_delay_ms", 0.0)) / 1e3
    for op in [o for o in ops if o.kind == "insert"]:
        nxt = next((o for o in ops if o.kind == "query"
                    and o.due >= op.due + delay), None)
        if nxt is not None:
            nxt.kind, nxt.row = "readback", op.row
    return ops, n_ins


def closed_batch(seed: int, i: int, n_library: int, size: int) -> np.ndarray:
    """Library rows of closed-loop batch ``i``."""
    return seed_rng(seed, 3, i).integers(0, n_library, size)


# -- tracing control -----------------------------------------------------------

class Tracer:
    """Runs the profiler from the window's start to the end of one steady
    sub-window (``bench.traced_window``, from ``start_s`` for ``length_s``
    seconds), which is all the reduction reads, and wraps the benchmark's
    own host calls in named annotations so that idle gaps on the device
    can be laid against them. ``on_open`` and ``on_close`` run at the
    sub-window's edges. The profiler starts before the window opens, so
    starting it delays no request; it stops as the sub-window closes, so
    the trace stays a few seconds long. Off, it costs nothing."""

    def __init__(self, out_dir=None, start_s: float = 0.0,
                 length_s: float = 0.0, on_open=None, on_close=None):
        self.out_dir = out_dir
        self.start_s = start_s
        self.length_s = length_s
        self.on_open = on_open
        self.on_close = on_close
        self.state = "off" if out_dir is None else "armed"
        self._window = None

    def annotate(self, name: str):
        if self.out_dir is None:
            return _NULL
        import jax
        return jax.profiler.TraceAnnotation(name)

    def begin(self) -> None:
        if self.state != "armed":
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no event per Python call
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)

    def poll(self, elapsed: float) -> None:
        """Open or close the sub-window at ``elapsed`` seconds into the
        window."""
        if self.state == "armed" and elapsed >= self.start_s:
            if self.on_open:
                self.on_open()
            self._window = self.annotate("bench.traced_window")
            self._window.__enter__()
            self.state = "on"
        elif self.state == "on" and elapsed >= self.start_s + self.length_s:
            self._close()

    def _close(self) -> None:
        import jax
        self._window.__exit__(None, None, None)
        if self.on_close:
            self.on_close()
        jax.profiler.stop_trace()
        self.state = "done"

    def end(self) -> None:
        if self.state == "on":
            self._close()
        elif self.state == "armed":
            import jax
            jax.profiler.stop_trace()
            self.state = "off"


class _Null:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


# -- closed loop ---------------------------------------------------------------

def run_closed(fe, library: np.ndarray, traffic: dict, cfg: dict,
               seconds: float, seed: int, tracer: Tracer) -> Window:
    """Whole batches, one after another, until ``seconds`` have passed."""
    from repro.serve.frontend import (DeadlineExceeded, Overloaded,
                                      Unavailable)
    size = int(traffic["queries_per_batch"])
    k = int(cfg["k"])
    deadline = traffic["frontend"].get("deadline_ms")
    tracer.begin()
    win = Window(t0=time.perf_counter())
    i = 0
    while True:
        elapsed = time.perf_counter() - win.t0
        tracer.poll(elapsed)
        with tracer.annotate("bench.draw"):
            rows = closed_batch(seed, i, len(library), size)
            q = library[rows]
        b = {"rows": rows, "t_submit": time.perf_counter(), "error": None}
        try:
            with tracer.annotate("bench.batch"):
                b["ids"], b["sims"] = fe.search(q, k, deadline_ms=deadline,
                                                timeout=LATE_S + seconds)
        except (DeadlineExceeded, Overloaded, Unavailable,
                TimeoutError) as e:
            b["error"] = repr(e)
        b["t_done"] = time.perf_counter()
        win.batches.append(b)
        if tracer.state == "on":
            win.traced.append(rows)
        i += 1
        if b["t_done"] - win.t0 >= seconds:
            break
    tracer.end()
    return win


# -- open loop -----------------------------------------------------------------

def run_open(fe, library: np.ndarray, fresh: np.ndarray, traffic: dict,
             cfg: dict, seconds: float, seed: int, tracer: Tracer,
             ops: list) -> Window:
    """Offer ``ops`` at their due times. Queries go through
    ``fe.submit`` from this thread; inserts go to a writer thread, so an
    insert waiting for its ack holds back no arrival; a collector thread
    takes each answer as it completes."""
    from repro.serve.frontend import Overloaded, Unavailable
    k = int(cfg["k"])
    deadline = traffic["frontend"].get("deadline_ms", -1.0)
    tracer.begin()
    win = Window(t0=time.perf_counter() + 0.05, ops=ops)
    acked = [0]
    writes: queue.Queue = queue.Queue()
    answers: queue.Queue = queue.Queue()
    t_end = win.t0 + seconds + LATE_S

    def writer():
        while (op := writes.get()) is not None:
            op.t_submit = time.perf_counter()
            try:
                op.ids = fe.insert(fresh[op.row:op.row + 1])
                op.t_done = time.perf_counter()
                acked[0] += 1
            except Exception as e:  # noqa: BLE001 — a failed write is a result
                op.error = repr(e)
                op.t_done = time.perf_counter()

    def collector():
        while (item := answers.get()) is not None:
            op, fut = item
            try:
                with tracer.annotate("bench.await_answer"):
                    ids, sims = fut.result(
                        max(t_end - time.perf_counter(), 0.0))
                op.t_done = time.perf_counter()
                op.ids, op.sims = ids[0], sims[0]
            except TimeoutError:
                op.error = "lost"
            except Exception as e:  # noqa: BLE001 — shed or expired
                op.error = repr(e)
                op.t_done = time.perf_counter()

    threads = [threading.Thread(target=writer, name="bench-writer"),
               threading.Thread(target=collector, name="bench-collector")]
    for t in threads:
        t.start()
    try:
        for op in ops:
            wait = win.t0 + op.due - time.perf_counter()
            if wait > 0:
                with tracer.annotate("bench.wait_arrival"):
                    time.sleep(wait)
            tracer.poll(op.due)
            if op.kind == "insert":
                writes.put(op)
                continue
            row = library[op.row] if op.kind == "query" else fresh[op.row]
            op.acked_before = acked[0]
            op.t_submit = time.perf_counter()
            try:
                with tracer.annotate("bench.submit"):
                    fut = fe.submit(row, k, deadline_ms=deadline)
            except (Overloaded, Unavailable) as e:
                op.error = repr(e)
                op.t_done = op.t_submit
                continue
            answers.put((op, fut))
    finally:
        writes.put(None)
        answers.put(None)
        for t in threads:
            t.join(LATE_S + 10.0)
        tracer.end()
    return win


# -- what the window measured ----------------------------------------------------

def quantile(values, q: float) -> float:
    """The ``q``-quantile of all values by the nearest-rank rule (inf
    counts as larger than anything)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return math.nan
    return float(v[min(max(int(math.ceil(q * v.size)) - 1, 0), v.size - 1)])


def latencies_ms(win: Window) -> np.ndarray:
    """Every query's latency from its due time, in ms; inf if it failed."""
    return np.array([op.latency_s(win.t0) * 1e3 for op in win.ops
                     if op.kind != "insert"], np.float64)


def screen_rate(win: Window, rows: int) -> float:
    """Compounds scored per second: queries completed times library rows,
    over the time from the window's start to the last completion."""
    done = [b for b in win.batches if b["error"] is None]
    if not done:
        return math.nan
    n = sum(len(b["rows"]) for b in done)
    return n * rows / (max(b["t_done"] for b in done) - win.t0)


def lateness_ms(win: Window) -> np.ndarray:
    """How late the generator submitted each query, in ms."""
    return np.array([(op.t_submit - win.t0 - op.due) * 1e3 for op in win.ops
                     if op.kind != "insert"
                     and not math.isnan(op.t_submit)], np.float64)


def counts(win: Window) -> tuple[int, int]:
    """(operations attempted, operations failed)."""
    if win.batches:
        n = sum(len(b["rows"]) for b in win.batches)
        f = sum(len(b["rows"]) for b in win.batches if b["error"])
        return n, f
    return len(win.ops), sum(op.failed for op in win.ops)
