"""Whole step: served rows per second times the required operations per
row, over the chip's int8 peak (%)."""

from bench import work
from bench.readers import rows_per_s


def read(run):
    rate = rows_per_s(run, "predicts")
    if rate is None or run.peak is None:
        return None
    return 100.0 * rate * work.ops_per_row(run.cfg, run.nnz) \
        / run.peak["int8_ops"]
