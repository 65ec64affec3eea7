"""Neighbours the HNSW traversal scored per query: the service's
neighbour-evaluation counter over its query counter. A program without
that counter gives no reading."""


def read(run):
    evals = run.reg("service_hnsw_neighbour_evals_total")
    queries = run.reg("service_queries_total")
    if evals <= 0 or queries <= 0:
        return None
    return evals / queries
