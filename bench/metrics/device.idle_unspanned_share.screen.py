"""Share of the traced window in which the device ran nothing while a
served call was in flight (``bench.batch`` or ``bench.await_answer``
open) and no program span was: program code with no span, or the
hand-off between threads."""
from bench import spanidle


def read(run):
    return spanidle.share(run.trace, "unspanned")
