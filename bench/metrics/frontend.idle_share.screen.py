"""Share of the traced window in which the device ran nothing while the
front end held the served call: its admission, dispatcher tick and
maintenance, and the replica's hand-off (``frontend.*``, ``replica.*``
the innermost program spans open)."""
from bench import spanidle


def read(run):
    return spanidle.share(run.trace, "frontend")
