"""Arithmetic shared by the metric readers under ``metrics/``.

Each reader takes a :class:`bench.harness.Run` and returns a number, or
None where it finds nothing to read (the harness then leaves the metric
out).  Percentiles are nearest-rank: the ``ceil(p * n)``-th smallest.
"""

from __future__ import annotations

import math

import numpy as np

from bench import work


def nearest_rank(values, p: float) -> float | None:
    vals = np.sort(np.asarray(values, float))
    if not len(vals):
        return None
    return float(vals[min(len(vals) - 1,
                          max(0, math.ceil(p * len(vals)) - 1))])


def latency_ms(run, p: float) -> float | None:
    """The ``p`` percentile of every answered predict's latency, from its
    scheduled send to its answer, in ms."""
    v = nearest_rank(run.window.latencies_s(), p)
    return None if v is None else v * 1e3


def rows_per_s(run, stream: str) -> float | None:
    """Rows of ``stream`` ("predicts" or "updates") answered inside the
    window, per second of the window."""
    s = getattr(run.window, stream)
    done = s.answered_in_window(run.seconds)
    if not len(done):
        return None
    return float(s.size[done].sum()) / run.seconds


def _delta(run, key: str) -> int:
    return run.stats1[key] - run.stats0[key]


def batch_rows(run) -> float | None:
    """Mean real rows per dispatched batch over the window."""
    batches = _delta(run, "batches")
    return _delta(run, "rows") / batches if batches else None


def stage_b_ms(run) -> float | None:
    """Stage-B service time (engine call and host copy, on the worker
    thread) per batch: each bucket's ring median, weighted by the batches
    that bucket served in the window."""
    before = run.stats0["buckets"]
    total = weight = 0.0
    for key, b in run.stats1["buckets"].items():
        n = b["count"] - before.get(key, {}).get("count", 0)
        total += n * b["p50_ms"]
        weight += n
    return total / weight if weight else None


def idle_pct(run) -> float | None:
    """Share of the traced window in which no operation ran on the
    device."""
    red = run.reduced
    return None if red is None else 100.0 * red.idle_share


def least_infer_s(run) -> float | None:
    """Least time of the window's inference batches, at their mean rows."""
    batches = _delta(run, "batches")
    if not batches or run.peak is None:
        return None
    rows = _delta(run, "rows") / batches
    return work.least_time(run.cfg, run.nnz, rows, run.peak)[0] * batches
