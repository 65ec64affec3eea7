"""Set-up time: from process start to the first timed operation, with
the library draw, the store build, the warm-up and any compilation."""


def read(run):
    return run.setup_s
