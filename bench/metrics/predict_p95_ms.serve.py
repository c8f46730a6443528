"""95th percentile of the client latency of every predict in the window,
from its scheduled send to its answer (ms).  A per-layer metric: at the
cell's load the tail is the coalescer's and stage B's queue, and it
spreads too widely from run to run to bound end to end."""

from bench.readers import latency_ms


def read(run):
    return latency_ms(run, 0.95)
