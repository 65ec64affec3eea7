"""The HNSW traversal's share of the HBM roofline: the least bytes of the
traced batches (``roofline_hnsw.traversal_bytes``: each scored
neighbour's print, each expanded node's adjacency row, the queries and
the answers) at the chip's peak bandwidth, over the device time of the
traversal's jitted program, found by the name the trace gives it. A
program whose service keeps no traversal counters gives no reading."""
from bench import roofline, roofline_hnsw

PROGRAM = "jit_hnsw_traverse"


def read(run):
    if run.trace is None or run.peak is None or not run.window.traced:
        return None
    evals = run.reg("service_hnsw_neighbour_evals_total")
    queries = run.reg("service_queries_total")
    if evals <= 0 or queries <= 0:
        return None
    modules = roofline_hnsw.load_modules(
        run.cell.bench_dir / "out" / "trace", run.trace.window)
    if not modules:
        return None
    t = roofline_hnsw.program_s(modules, run.trace.window, PROGRAM)
    m = int(run.cfg["engine_params"]["hnsw_m"])
    least = roofline_hnsw.traversal_bytes(
        evals, evals / (2 * m), queries, m, int(run.cfg["k"]),
        int(run.cfg["fp_bits"]))
    return roofline.hbm_share(least, t, run.peak)
