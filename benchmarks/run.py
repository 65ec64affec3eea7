"""Benchmark driver — one section per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV (harness contract) and stores JSON
under experiments/bench/. ``--fast`` shrinks DB sizes for CI-style runs.
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller DBs (used by the final tee run on CPU)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    from . import (bitbound_speedup, engine_throughput, exhaustive_qps,
                   folding_accuracy, hnsw_grid, pareto)

    sections = [
        ("table1_folding_accuracy", lambda: folding_accuracy.run(
            n_db=6_000 if args.fast else 20_000, n_queries=32)),
        ("fig2_bitbound_speedup", lambda: bitbound_speedup.run(
            n_db=20_000 if args.fast else 60_000, n_queries=48)),
        ("fig7_exhaustive_qps", lambda: exhaustive_qps.run(
            n_db=20_000 if args.fast else 60_000, n_queries=16)),
        ("fig8_hnsw_grid", lambda: hnsw_grid.run(
            n_db=3_000 if args.fast else 8_000, n_queries=24,
            ms=(5, 10) if args.fast else (5, 10, 20),
            efs=(20, 60, 120) if args.fast else (20, 60, 120, 200))),
        ("fig10_pareto", lambda: pareto.run(
            n_db=3_000 if args.fast else 8_000, n_queries=24)),
        ("engine_throughput", lambda: engine_throughput.run(
            n_db=20_000 if args.fast else 60_000)),
    ]

    failures = 0
    for name, fn in sections:
        if args.only and args.only != name:
            continue
        print(f"### {name}")
        try:
            fn()
        except Exception:
            failures += 1
            traceback.print_exc()
        print()

    # roofline table (reads dry-run artifacts if present)
    print("### roofline")
    try:
        from . import roofline
        roofline.run()
    except Exception:
        traceback.print_exc()

    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
