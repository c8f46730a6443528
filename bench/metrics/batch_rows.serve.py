"""Scheduler: mean real rows per coalesced batch over the window, from the
server's ``stats()`` counters."""

from bench.readers import batch_rows


def read(run):
    return batch_rows(run)
