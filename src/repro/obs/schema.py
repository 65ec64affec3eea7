"""Schema validation for the metrics registry's JSONL export.

:func:`validate_metrics_jsonl` returns a (possibly empty) list of
human-readable error strings — empty means valid — and enforces the
required-family floor (:data:`REQUIRED_METRIC_FAMILIES`): a serving run
that silently stopped exporting request latencies must fail CI, not
produce an empty dashboard. Spans need no schema of their own: they are
the profiler's trace (``repro.obs.trace``).

``benchmarks/check_obs_schema.py`` is the CLI wrapper CI runs.
"""
from __future__ import annotations

import json
from pathlib import Path

#: metric families every SearchService export must contain (the serving
#: dashboards and the SLO harness key on these)
REQUIRED_METRIC_FAMILIES = (
    "service_queries_total",
    "service_request_latency_ms",
    "service_scanned_total",
)

#: families a service with a BitBound engine on the row-window kernel
#: (``backend="tpu"``) additionally exports: the kernel's per-query window
#: tiles against the query-block union tiles its grid streams
BITBOUND_METRIC_FAMILIES = (
    "service_stage1_tiles_total",
)

#: families a service with an HNSW engine additionally exports: the bulk
#: build (gauges, set once it is done) and the traversal's work per batch
HNSW_METRIC_FAMILIES = (
    "service_hnsw_build_rows",
    "service_hnsw_build_seconds",
    "service_hnsw_build_rounds",
    "service_hnsw_neighbour_evals_total",
    "service_hnsw_iters_total",
    "service_hnsw_max_iters_hit_total",
    "service_hnsw_deduped_slots_total",
)

#: families the concurrent front end (ISSUE 9) must additionally export —
#: CI's overload smoke passes these via ``--require-family`` (note that
#: flag *replaces* the service floor, so callers list both sets)
FRONTEND_METRIC_FAMILIES = (
    "frontend_admitted_total",
    "frontend_shed_total",
    "frontend_deadline_expired_total",
    "frontend_queue_depth",
    "frontend_degradation_level",
    "frontend_replica_live",
    "frontend_request_latency_ms",
)


def _validate_metric_row(row: dict, where: str) -> list[str]:
    errors = []
    for key in ("name", "type", "labels"):
        if key not in row:
            errors.append(f"{where}: missing {key!r}")
    kind = row.get("type")
    if kind not in ("counter", "gauge", "histogram"):
        errors.append(f"{where}: unknown type {kind!r}")
    if not isinstance(row.get("labels", {}), dict):
        errors.append(f"{where}: labels must be an object")
    if kind in ("counter", "gauge"):
        if not isinstance(row.get("value"), (int, float)):
            errors.append(f"{where}: {kind} missing numeric value")
    elif kind == "histogram":
        for key in ("count", "sum"):
            if not isinstance(row.get(key), (int, float)):
                errors.append(f"{where}: histogram missing numeric {key!r}")
        buckets = row.get("buckets")
        if not isinstance(buckets, dict):
            errors.append(f"{where}: histogram missing buckets object")
        else:
            n_in_buckets = 0
            for le, n in buckets.items():
                try:
                    float(le)
                except ValueError:
                    if le not in ("inf", "+Inf"):
                        errors.append(f"{where}: bucket edge {le!r} "
                                      f"not numeric")
                if not isinstance(n, int) or n < 0:
                    errors.append(f"{where}: bucket count {n!r} invalid")
                else:
                    n_in_buckets += n
            if isinstance(row.get("count"), int) \
                    and n_in_buckets != row["count"]:
                errors.append(f"{where}: bucket counts sum to "
                              f"{n_in_buckets} != count {row['count']}")
    return errors


def validate_metrics_jsonl(path, *, require_families: tuple | None = None
                           ) -> list[str]:
    """Validate a registry JSONL export; ``require_families=None`` applies
    :data:`REQUIRED_METRIC_FAMILIES`, ``()`` disables the floor."""
    if require_families is None:
        require_families = REQUIRED_METRIC_FAMILIES
    try:
        text = Path(path).read_text()
    except OSError as e:
        return [f"{path}: unreadable ({e})"]
    errors: list[str] = []
    seen: set[str] = set()
    lines = [ln for ln in text.splitlines() if ln.strip()]
    if not lines:
        errors.append(f"{path}: empty metrics export")
    for i, line in enumerate(lines):
        where = f"line {i + 1}"
        try:
            row = json.loads(line)
        except json.JSONDecodeError as e:
            errors.append(f"{where}: invalid JSON ({e})")
            continue
        if not isinstance(row, dict):
            errors.append(f"{where}: not an object")
            continue
        errors.extend(_validate_metric_row(row, where))
        if isinstance(row.get("name"), str):
            seen.add(row["name"])
    for fam in require_families:
        if fam not in seen:
            errors.append(f"required metric family {fam!r} missing")
    return errors
