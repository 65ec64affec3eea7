"""Share of the traced window in which the device ran nothing while the
engine's host code held the served call: the store->device refresh, the
host batch plan and the launch (``engine.*`` other than ``engine.fetch``,
``tier.*``, ``hnsw.*`` the innermost program spans open)."""
from bench import spanidle


def read(run):
    return spanidle.share(run.trace, "engine.host")
