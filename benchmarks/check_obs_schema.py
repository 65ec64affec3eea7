"""CI gate for the metrics registry's export (ISSUE 8 satellite).

Thin CLI over :mod:`repro.obs.schema`: validates a metrics-registry JSONL
export, printing every schema error and exiting non-zero if it fails. CI
runs it right after the `search_serve` smoke so a silently-broken exporter
(missing family, malformed bucket counts) fails the build instead of
shipping an empty dashboard. Spans are the profiler's trace and need no
check here.

    PYTHONPATH=src python benchmarks/check_obs_schema.py \
        --metrics /tmp/metrics.jsonl
"""
from __future__ import annotations

import argparse
import sys

from repro.obs.schema import (FRONTEND_METRIC_FAMILIES,
                              REQUIRED_METRIC_FAMILIES,
                              validate_metrics_jsonl)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--metrics", required=True,
                    help="metrics-registry JSONL export to validate")
    ap.add_argument("--require-family", action="append", default=None,
                    help="metric family that must appear in the JSONL "
                         "(repeatable; default: the serving floor "
                         f"{', '.join(REQUIRED_METRIC_FAMILIES)})")
    ap.add_argument("--require-frontend", action="store_true",
                    help="demand the concurrent-tier floor too: the service "
                         "families plus "
                         f"{', '.join(FRONTEND_METRIC_FAMILIES)}")
    args = ap.parse_args(argv)
    fams = (tuple(args.require_family)
            if args.require_family is not None else None)
    if args.require_frontend:
        fams = (REQUIRED_METRIC_FAMILIES + FRONTEND_METRIC_FAMILIES
                + (fams or ()))
    errors = validate_metrics_jsonl(args.metrics, require_families=fams)
    print(f"[check-obs] metrics {args.metrics}: "
          f"{'OK' if not errors else f'{len(errors)} error(s)'}")
    for e in errors:
        print(f"[check-obs] [metrics] {e}", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
