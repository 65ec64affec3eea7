"""Share of the library BitBound's stage 1 scans: rows scanned over
queries times library rows, from the service's scanned counter."""


def read(run):
    queries = run.reg("service_queries_total")
    scanned = run.reg("service_scanned_total")
    if queries <= 0 or scanned <= 0:
        return None
    return 100.0 * scanned / (queries * run.n_rows)
