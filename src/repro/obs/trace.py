"""Program spans on the JAX profiler's clock.

One process-wide :data:`TRACER` names the work of each layer of the served
path (front end, replica, service, engine, WAL, snapshots) as
``jax.profiler.TraceAnnotation`` spans. A span is recorded when, and only
when, a profiler session is on (``jax.profiler.start_trace`` or
:func:`session`): it lands on the ``/host:`` plane of the same trace that
holds the device's operations, on one clock, so a stretch in which the
device ran nothing can be laid against the host code that held it. There
is no recorder, buffer, clock or exporter here; the session is the only
switch.

Two recording shapes:

* ``with TRACER.span(name, **args) as s:`` — a span on the calling thread;
  ``s.set(**kv)`` adds metadata before it closes.
* ``h = TRACER.begin(name, **args); ...; h.end(**kv)`` — a span whose end
  is observed later than its start and need not nest with the spans opened
  in between (the tiered path's host->HBM transfers). Opened and closed on
  one thread.

Metadata (a request's ``rid``, a dispatched ``batch``, bucket sizes) is
attached only while a session is on; a span that covers several requests
carries their ids space-separated, so one request's spans share its id.
With no session, ``span()`` and ``begin()`` return shared do-nothing
singletons (one ``is_enabled()`` read, no allocation), and call sites that
build costly arguments gate them on :attr:`Tracer.enabled`. A span never
waits on the device.

jax is imported on first use, so importing this module costs nothing.
"""
from __future__ import annotations

from contextlib import contextmanager

_ANNOTATION = None


def _annotation():
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation
        _ANNOTATION = TraceAnnotation
    return _ANNOTATION


class _NullSpan:
    """Shared do-nothing span: the no-session fast path."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kv):
        return self

    def end(self, **kv):
        pass


NULL_SPAN = _NullSpan()
#: what ``begin()`` returns with no session: the same do-nothing span
NULL_HANDLE = NULL_SPAN


class _Span:
    __slots__ = ("_ann",)

    def __init__(self, name: str, args: dict):
        self._ann = _annotation()(name, **args)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False

    def set(self, **kv):
        self._ann.set_metadata(**kv)
        return self

    def end(self, **kv):
        """Close a span opened by :meth:`Tracer.begin`."""
        if kv:
            self.set(**kv)
        self.__exit__(None, None, None)


class Tracer:
    """Names program work on the profiler's timeline."""

    @property
    def enabled(self) -> bool:
        """Whether a profiler session is recording now."""
        return _annotation().is_enabled()

    def span(self, name: str, **args):
        """Context-manager span on the calling thread (``NULL_SPAN`` when
        no session is on)."""
        if not _annotation().is_enabled():
            return NULL_SPAN
        return _Span(name, args)

    def begin(self, name: str, **args):
        """Open a span now; the returned handle's ``.end()`` closes it
        (``NULL_HANDLE`` when no session is on)."""
        if not _annotation().is_enabled():
            return NULL_HANDLE
        return _Span(name, args).__enter__()


@contextmanager
def session(out_dir, perfetto: bool = False):
    """A profiler session writing to ``out_dir``: the program's spans, the
    runtime's and the device's operations, without an event per Python
    call. ``perfetto`` also writes ``perfetto_trace.json.gz`` beside the
    ``.xplane.pb``, for https://ui.perfetto.dev."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out_dir), create_perfetto_trace=perfetto,
                             profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


#: process-wide tracer every instrumentation point records through
TRACER = Tracer()
