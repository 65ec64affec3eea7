"""Rows the HNSW bulk build inserted per second of set-up: the service's
build gauges, rows over seconds. A program without them gives no
reading."""


def read(run):
    rows = run.reg("service_hnsw_build_rows")
    seconds = run.reg("service_hnsw_build_seconds")
    if rows <= 0 or seconds <= 0:
        return None
    return rows / seconds
