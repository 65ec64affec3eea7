"""Compounds scored per second in a closed-loop screen: queries completed
times library rows, over the time from the window's start to the last
completion (the paper's "compounds per second")."""
from bench import load


def read(run):
    if not run.window.batches:
        return None
    return load.screen_rate(run.window, run.n_rows)
