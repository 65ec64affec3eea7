"""Share of the traced window in which the device ran nothing while the
engine read the batch's results back to the host (``engine.fetch`` the
innermost program span open)."""
from bench import spanidle


def read(run):
    return spanidle.share(run.trace, "engine.fetch")
