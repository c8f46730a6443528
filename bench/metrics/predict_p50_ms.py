"""Median client latency of every predict in the window, from its
scheduled send to its answer (ms)."""

from bench.readers import latency_ms


def read(run):
    return latency_ms(run, 0.50)
