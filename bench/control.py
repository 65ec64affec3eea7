"""The control of a cell's comparison, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

For each seed: the library the run would draw, and as many sampled answers
as a run checks, of queries drawn as the cell's traffic draws them. The
plain reference computed in bfloat16 is put in the program's place and held
to the f32 reference as a run holds the program: the number of answers that
differ is the control's reading of ``mismatched``.

A configuration checked approximately (a ``recall_shortfall`` limit) reads
the bfloat16 answers' ``misscored`` instead, which has to come out as the
number of answers, and ``recall_shortfall`` of the exact answers with their
best ceil(k/4) rows swapped for the best rows below the k-th, at their true
scores (``check.degraded``), which reads about 0.25; their ``misscored`` is
printed too, and is 0.

The benchmark's own runs never run this. One JSON line per seed.
"""
import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import check, load, spec  # noqa: E402
from bench.library import draw_library, seed_rng  # noqa: E402


def items(cell, seed: int, n_library: int) -> list:
    """(library row, visible rows) of the answers a run would check."""
    tr = cell.traffic
    n = int(tr["check_sample"])
    if tr["loop"] == "closed":
        rows = load.closed_batch(seed, 0, n_library,
                                 int(tr["queries_per_batch"]))[:n]
    else:
        rows = seed_rng(seed, 4).integers(0, n_library, n)
    return [(int(r), n_library) for r in rows]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--precision", default="bf16")
    args = ap.parse_args(argv)
    cell = spec.resolve(args.workload)
    Reference = spec.reference_class(cell)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        _, library = draw_library(cell.config, seed)
        picked = items(cell, seed, len(library))
        if check.approximate(cell.config):
            got = check.control_approximate(Reference, cell.config, library,
                                            picked, args.precision)
        else:
            got = {"mismatched": check.control_mismatches(
                Reference, cell.config, library, picked, args.precision)}
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "precision": args.precision, **got,
                          "of": len(picked),
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
