"""Engine, stage B: service time per batch on the server's device worker
(ms), from ``stats()["buckets"]``."""

from bench.readers import stage_b_ms


def read(run):
    return stage_b_ms(run)
