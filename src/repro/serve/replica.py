"""Worker-thread replica: single-owner access to one deterministic
:class:`~repro.serve.service.SearchService` (ISSUE 9 tentpole).

The deterministic core is synchronous and single-caller by design — the
parity suites depend on it. The concurrent tier therefore never shares a
service between threads: each replica owns exactly one service and one
worker thread, and *every* access (query batches, insert fan-out, state
extraction for snapshots, compaction, degradation-knob changes) is a
:class:`_Task` enqueued on the replica's FIFO queue and executed by the
worker. FIFO ordering is the consistency model: inserts enqueued under the
front end's insert lock land in the same order on every replica, so replica
states never diverge; a query sees exactly the inserts enqueued before it
on *its* replica.

Failure model: a worker that raises marks the replica ``dead`` and exits; a
worker stuck inside an engine call past the health timeout is marked
``dead`` externally by the front end's monitor (``Replica.busy_for``). A
dead replica's queue is :meth:`drain`-ed by the front end and its tasks
re-dispatched to a surviving replica — task callables take the service as
their only argument precisely so they can be re-bound. The abandoned worker
thread (daemon) may still finish its in-flight task; result futures are
first-write-wins, so a late result from a wedged worker and the re-dispatch
cannot race each other into a double completion.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable

from ..obs.trace import TRACER as _TR
from .service import SearchService


class ReplicaDead(RuntimeError):
    """The task's replica died before (or while) executing it."""


class Future:
    """Minimal thread-safe one-shot result cell (first write wins)."""

    __slots__ = ("_ev", "_value", "_exc", "_lock")

    def __init__(self):
        self._ev = threading.Event()
        self._value = None
        self._exc = None
        self._lock = threading.Lock()

    def set_result(self, value) -> bool:
        with self._lock:
            if self._ev.is_set():
                return False
            self._value = value
            self._ev.set()
            return True

    def set_exception(self, exc: BaseException) -> bool:
        with self._lock:
            if self._ev.is_set():
                return False
            self._exc = exc
            self._ev.set()
            return True

    def done(self) -> bool:
        return self._ev.is_set()

    def result(self, timeout: float | None = None):
        if not self._ev.wait(timeout):
            raise TimeoutError("replica task did not complete in time")
        if self._exc is not None:
            raise self._exc
        return self._value


@dataclass
class _Task:
    """One unit of work: ``fn(service)`` run by the owning worker.

    ``abandon`` is called (with the terminal exception) when the task can
    never run anywhere — e.g. every replica is dead — so composite tasks
    like query batches can fail their inner request futures instead of
    leaving clients hanging.
    """
    fn: Callable[[SearchService], object]
    label: str = "task"
    future: Future = field(default_factory=Future)
    abandon: Callable[[BaseException], None] | None = None
    #: span metadata of the worker's run (a batch's ``batch`` and ``rid``)
    trace: dict = field(default_factory=dict)

    def fail(self, exc: BaseException) -> None:
        if self.abandon is not None:
            self.abandon(exc)
        self.future.set_exception(exc)


LIVE, DEAD, STOPPED = "live", "dead", "stopped"


class Replica:
    """One service + one worker thread + one FIFO task queue."""

    def __init__(self, index: int, service: SearchService, *,
                 generation: int = 0, clock=time.perf_counter):
        self.index = int(index)
        self.generation = int(generation)
        self.svc = service
        self.clock = clock
        self.state = LIVE
        self.error: BaseException | None = None
        self._q: queue.Queue[_Task | None] = queue.Queue()
        self._busy_since: float | None = None
        self._tasks_done = 0
        self._thread = threading.Thread(
            target=self._run, daemon=True,
            name=f"replica-{self.index}.{self.generation}")
        self._thread.start()

    # -- scheduling ----------------------------------------------------------
    def call(self, fn, label: str = "task", abandon=None,
             **trace) -> Future:
        """Enqueue ``fn(service)``; returns the result future. ``trace``
        goes on the ``replica.task`` span of its run."""
        task = _Task(fn, label=label, abandon=abandon, trace=trace)
        self.put(task)
        return task.future

    def put(self, task: _Task) -> None:
        if self.state != LIVE:
            task.fail(ReplicaDead(
                f"replica {self.index} is {self.state}"))
            return
        self._q.put(task)

    def queue_depth(self) -> int:
        """Pending tasks (+1 while the worker is inside one) — the load
        balancing key and the ``frontend_queue_depth`` gauge."""
        return self._q.qsize() + (1 if self._busy_since is not None else 0)

    def busy_for(self, now: float | None = None) -> float:
        """Seconds the worker has spent inside its current task (0 when
        idle) — the wedge-detection signal."""
        t0 = self._busy_since
        if t0 is None:
            return 0.0
        return (now if now is not None else self.clock()) - t0

    # -- lifecycle -----------------------------------------------------------
    def mark_dead(self, error: BaseException | None = None) -> None:
        """Externally declare this replica failed (wedge timeout, divergent
        insert, explicit kill). The worker thread is abandoned — it exits
        at its next queue pop; a task it is still inside may complete its
        future first-write-wins."""
        if self.state == LIVE:
            self.state = DEAD
            self.error = error

    def drain(self) -> list[_Task]:
        """Pull every not-yet-started task off a dead replica's queue so
        the front end can re-dispatch them to a survivor."""
        tasks = []
        while True:
            try:
                t = self._q.get_nowait()
            except queue.Empty:
                return tasks
            if t is not None:
                tasks.append(t)

    def stop(self, timeout: float | None = 5.0) -> None:
        """Graceful shutdown: the worker finishes queued tasks, then exits."""
        if self.state == LIVE:
            self.state = STOPPED
        self._q.put(None)                  # wake + terminate sentinel
        self._thread.join(timeout)

    # -- worker --------------------------------------------------------------
    def _run(self) -> None:
        while True:
            task = self._q.get()
            if task is None:
                return
            if self.state == DEAD:
                # drained concurrently with our pop: hand the task back so
                # the front end's failover can re-dispatch it
                task.fail(ReplicaDead(
                    f"replica {self.index} died before task "
                    f"{task.label!r} ran"))
                continue
            self._busy_since = self.clock()
            try:
                with _TR.span("replica.task", label=task.label,
                              **task.trace):
                    task.future.set_result(task.fn(self.svc))
            except BaseException as e:     # noqa: BLE001 — fault isolation
                # a failing task poisons the replica (the service may be in
                # a partially-applied state — divergence risk); the front
                # end's monitor sees DEAD and fails over
                self.state = DEAD
                self.error = e
                task.fail(e)
                self._busy_since = None
                return
            self._busy_since = None
            self._tasks_done += 1
