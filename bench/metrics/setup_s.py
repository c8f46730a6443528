"""Process start to the first timed request: loading, weights, warm-up
and any compilation (s)."""


def read(run):
    return run.setup_s
