"""Mean time of one engine flush group in the service, from the exact
sum and count of ``service_engine_batch_ms``."""


def read(run):
    n = run.reg("service_engine_batch_ms", "count")
    return run.reg("service_engine_batch_ms", "sum") / n if n else None
