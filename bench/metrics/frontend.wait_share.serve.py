"""Share of request time spent in the front end before the replica's
service took the request: 1 - sum(service latency) / sum(front-end
latency), from the registries' exact histogram sums."""


def read(run):
    front = run.reg("frontend_request_latency_ms", "sum")
    svc = run.reg("service_request_latency_ms", "sum")
    return 100.0 * (1.0 - svc / front) if front > 0 else None
