"""95th-percentile latency of every query of an open-loop window, from its
due time; a failed query counts as infinitely late."""
from bench import check, load


def read(run):
    lat = load.latencies_ms(run.window)
    return check.finite(load.quantile(lat, 0.95)) if lat.size else None
