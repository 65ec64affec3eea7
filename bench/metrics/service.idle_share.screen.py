"""Share of the traced window in which the device ran nothing while the
service held the served call: batch formation, bucket padding and the
concatenation of results (``service.*``, ``wal.*``, ``snapshot.*`` the
innermost program spans open)."""
from bench import spanidle


def read(run):
    return spanidle.share(run.trace, "service")
