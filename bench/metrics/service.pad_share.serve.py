"""Share of the batch slots the service padded: 1 - queries / sum over
batches of the bucket size."""


def read(run):
    slots = sum(float(labels["bucket"]) * row["value"]
                for labels, row in run.children("service_batches_total"))
    queries = run.reg("service_queries_total")
    return 100.0 * (1.0 - queries / slots) if slots > 0 else None
