"""Share of the traced window in which no operation ran on the device."""
from bench import tracereduce


def read(run):
    if run.trace is None:
        return None
    return tracereduce.idle_share(run.trace)
