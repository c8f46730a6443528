"""Predict rows answered inside the window, per second of the window."""

from bench.readers import rows_per_s


def read(run):
    return rows_per_s(run, "predicts")
