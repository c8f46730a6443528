"""The traffic generator: schedules, and latency and lateness accounting,
against a fake server at a tiny size."""

import asyncio
import gc
import time
import types

import numpy as np
import pytest

from bench import traffic
from bench.traffic import Window


def test_poisson_schedule_rate_and_order():
    rng = np.random.default_rng(1)
    t = traffic.arrivals({"rate": 2000.0}, 5.0, rng)
    assert np.all(np.diff(t) > 0) and t[0] >= 0 and t[-1] < 5.0
    assert len(t) == pytest.approx(10000, rel=0.05)


def test_onoff_schedule_keeps_the_mean_rate_in_on_phases():
    rng = np.random.default_rng(2)
    spec = {"rate": 1000.0, "on_s": 0.5, "off_s": 0.5}
    t = traffic.arrivals(spec, 20.0, rng)
    assert np.all(np.mod(t, 1.0) < 0.5)
    assert len(t) == pytest.approx(20000, rel=0.05)


def test_request_sizes_loguniform_and_fixed():
    rng = np.random.default_rng(3)
    n = traffic.request_sizes({"min": 1, "max": 64}, 200000, rng)
    assert n.min() == 1 and n.max() == 64
    assert n.mean() == pytest.approx(14.8, abs=0.3)
    # log-uniform: as many requests of 1 row as of 32..63 rows
    assert np.mean(n == 1) == pytest.approx(np.mean((n >= 32) & (n < 64)),
                                            abs=0.01)
    assert set(traffic.request_sizes({"min": 64, "max": 64}, 5, rng)) == {64}


def test_same_seed_same_schedule():
    spec = {"rate": 500.0, "rows": {"min": 1, "max": 64}}
    a = traffic.schedule(spec, 1.0, 100, np.random.default_rng(7))
    b = traffic.schedule(spec, 1.0, 100, np.random.default_rng(7))
    assert a.n == b.n > 400
    assert np.array_equal(a.t_sched, b.t_sched)
    assert all(np.array_equal(a.rows_of(i), b.rows_of(i))
               for i in range(a.n))
    assert a.size.sum() == a.n_rows == len(a.idx)
    assert np.all(a.rows_of(a.n - 1) < 100)


def test_every_seed_sends_the_same_work_in_its_own_order():
    spec = {"rate": 800.0, "rows": {"min": 1, "max": 64}}
    a = traffic.schedule(spec, 2.0, 100, np.random.default_rng(1))
    b = traffic.schedule(spec, 2.0, 100, np.random.default_rng(2**31 + 5))
    assert a.n == b.n == 1600
    assert np.array_equal(np.sort(a.size), np.sort(b.size))
    # the gaps, the one after the last arrival included, are one set
    gaps = [np.sort(np.diff(np.concatenate([[0.0], s.t_sched, [2.0]])))
            for s in (a, b)]
    assert np.allclose(gaps[0], gaps[1], rtol=1e-9, atol=1e-12)
    assert not np.array_equal(a.size, b.size)
    assert not np.array_equal(a.t_sched, b.t_sched)


def _answer(lits):
    """An answer shaped as the server's: a prediction and class sums."""
    return types.SimpleNamespace(prediction=lits.sum(axis=1),
                                 class_sums=lits)


class _Stalling:
    """A fake server whose first answer blocks the event loop."""

    def __init__(self, stall_s):
        self.stall_s, self.calls = stall_s, 0

    async def submit(self, lits):
        self.calls += 1
        if self.calls == 1:
            time.sleep(self.stall_s)        # the loop cannot send meanwhile
        await asyncio.sleep(0)
        return _answer(lits)


def test_stall_counts_as_lateness_and_latency():
    pool = np.ones((16, 4), np.int8)
    server = _Stalling(0.2)
    win = Window(pool, np.zeros(16, np.int32), submit=server.submit)
    mix = {"predict": {"loop": "open", "rate": 200.0,
                       "rows": {"min": 1, "max": 4}}}
    asyncio.run(win.run(mix, 0.5, np.random.default_rng(4)))
    late = win.lateness_s()
    lat = win.latencies_s()
    sent = win.predicts.t_sched
    assert win.predicts.n == len(lat) > 50
    # requests due during the stall were sent late, and their latency,
    # taken from the scheduled send, holds that wait
    during = (sent > sent[0]) & (sent < sent[0] + 0.15)
    assert during.any()
    assert np.all(late[during] > 0.03)
    assert np.all(lat[during] >= late[during])
    assert np.all(lat >= late - 1e-9)
    assert win.unanswered() == 0 and win.failed() == 0


def test_closed_loop_and_feeder_count_rows_in_window():
    pool = np.arange(32 * 4, dtype=np.int8).reshape(32, 4)
    labels = np.zeros(32, np.int32)
    versions = []

    async def submit(lits):
        await asyncio.sleep(0.001)
        return _answer(lits)

    async def submit_labeled(lits, y):
        await asyncio.sleep(0.01)
        versions.append(len(versions) + 1)
        return versions[-1]

    win = Window(pool, labels, submit=submit, submit_labeled=submit_labeled,
                 version=lambda: len(versions))
    mix = {"predict": {"loop": "closed", "clients": 3,
                       "rows": {"min": 8, "max": 8}},
           "learn": {"clients": 1, "rows": 4}}
    asyncio.run(win.run(mix, 0.3, np.random.default_rng(5)))
    p, u = win.predicts, win.updates
    assert p.n > 64                       # the columns grew past their start
    assert np.all(p.size[:p.n] == 8) and p.n_rows == 8 * p.n
    assert list(u.value[:u.n]) == versions
    assert np.all(p.v_lo[:p.n] <= p.v_hi[:p.n])
    inside = p.answered_in_window(0.3)
    assert 0 < len(inside) <= p.n
    assert np.all(p.t_done[inside] <= 0.3)
    # every answer landed at its own request's rows
    for i in range(p.n):
        rows = slice(p.start[i], p.start[i] + p.size[i])
        assert np.array_equal(p.class_sums[rows], pool[p.rows_of(i)])


class _Echo:
    """A fake server answering each row with its pool index."""

    def __init__(self, pool):
        self.key = {r.tobytes(): i for i, r in enumerate(pool)}

    async def submit(self, lits):
        await asyncio.sleep(0)
        pred = np.array([self.key[r.tobytes()] for r in lits], np.int32)
        return types.SimpleNamespace(prediction=pred,
                                     class_sums=pred[:, None] * [1, -1])


def test_answers_are_kept_in_columns_not_per_request_objects():
    # the generator shares the server's heap: what it keeps for the
    # check must not grow the collector's tracked objects per request
    pool = np.arange(64 * 8, dtype=np.int32).reshape(64, 8).astype(np.int8)
    pool = np.unique(pool, axis=0)
    server = _Echo(pool)
    counts = []
    for rate in (200.0, 2000.0):
        win = Window(pool, np.zeros(len(pool), np.int32),
                     submit=server.submit)
        mix = {"predict": {"loop": "open", "rate": rate,
                           "rows": {"min": 1, "max": 8}}}
        gc.collect()
        before = len(gc.get_objects())
        asyncio.run(win.run(mix, 0.3, np.random.default_rng(6)))
        gc.collect()
        counts.append((win.predicts.n, len(gc.get_objects()) - before))
        p = win.predicts
        done = p.answered()
        assert len(done) == p.n
        at = np.concatenate([p.rows_of(i) for i in done])
        assert np.array_equal(p.prediction[:p.n_rows], at)
        assert np.array_equal(p.class_sums[:p.n_rows, 1], -at)
    (n_lo, grew_lo), (n_hi, grew_hi) = counts
    assert n_hi > 5 * n_lo
    assert grew_hi - grew_lo < 0.05 * (n_hi - n_lo)
