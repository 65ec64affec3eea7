"""End-to-end observability (ISSUE 8): metrics registry + trace spans.

* :mod:`repro.obs.metrics` — thread-safe, constant-memory counters /
  gauges / log-bucketed histograms with Prometheus text exposition and
  JSONL export (:class:`MetricsRegistry`; ``NULL_METRICS`` no-op twin).
* :mod:`repro.obs.trace` — process-wide span tracer (:data:`TRACER`)
  recording ``jax.profiler`` annotations, so program spans share the
  device trace's clock; with no profiler session a span is a shared
  do-nothing singleton.
* :mod:`repro.obs.schema` — validator of the registry's JSONL export
  (CLI: ``benchmarks/check_obs_schema.py``).

This package deliberately imports nothing from the rest of ``repro``, and
jax only on first use, so every layer (kernels, engines, serving, WAL) can
instrument itself without import cycles. docs/ARCHITECTURE.md
§Observability has the span taxonomy and overhead budget.
"""
from .metrics import MetricsRegistry, NullMetrics, NULL_METRICS  # noqa: F401
from .trace import Tracer, TRACER, NULL_SPAN, NULL_HANDLE  # noqa: F401
from . import schema  # noqa: F401
