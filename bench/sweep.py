"""Find the highest rate an open-loop cell sustains: one set-up, then one
window per rate, lowest first.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 50,100,200

A rate is sustained when the 95th percentile, from due times, stays under
the traffic's ``latency_limit_ms``, nothing fails, and the backlog does not
grow: the last fifth of the requests waits no more than twice what the
first fifth waits. One JSON line per rate. The cell's rate is then fixed
at 0.8 of the highest sustained one, in its traffic file; the benchmark's
own runs never sweep.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness, load, spec  # noqa: E402


def summary(win, limit_ms: float) -> dict:
    lat = load.latencies_ms(win)
    fifth = max(len(lat) // 5, 1)
    first, last = np.median(lat[:fifth]), np.median(lat[-fifth:])
    row = {"requests": int(lat.size),
           "failed": int(np.isinf(lat).sum()),
           "p50_ms": load.quantile(lat, 0.5),
           "p95_ms": load.quantile(lat, 0.95),
           "p99_ms": load.quantile(lat, 0.99),
           "first_fifth_p50_ms": float(first),
           "last_fifth_p50_ms": float(last),
           "lateness_p99_ms": load.quantile(load.lateness_ms(win), 0.99)}
    row["sustained"] = bool(row["failed"] == 0 and row["p95_ms"] < limit_ms
                            and last <= 2 * max(first, 1e-9))
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    tr = cell.traffic
    n_lib = int(cell.config["n_molecules"])
    plans = [load.open_schedule(dict(tr, rate_per_s=float(r)), args.seconds,
                                args.seed + i, n_lib)
             for i, r in enumerate(args.rates.split(","))]
    used = 0                    # the windows draw on one pool of molecules
    for ops, n in plans:
        for op in ops:
            if op.kind != "query":
                op.row += used
        used += n
    harness.device_info(cell.chips, require_tpu=True)
    harness.use_compile_cache(spec.ROOT)
    cell.traffic = dict(tr, rate_per_s=max(float(r) for r in
                                           args.rates.split(",")))
    p = harness.prepare(cell, args.seed, [o for ops, _ in plans for o in ops],
                        used)
    over = 0
    try:
        for i, (r, (ops, n)) in enumerate(zip(args.rates.split(","), plans)):
            win, _, compiles = harness.window(p, args.seconds, args.seed + i,
                                              load.Tracer(), ops)
            row = {"rate_per_s": float(r), "compiles_in_window": compiles,
                   **summary(win, float(tr["latency_limit_ms"]))}
            print(json.dumps(row), flush=True)
            over = 0 if row["sustained"] else over + 1
            if over == 2:
                break
    finally:
        harness.close(p)
    return 0


if __name__ == "__main__":
    sys.exit(main())
