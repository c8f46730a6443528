"""The one traffic generator.  A mix is a data file under ``traffic/``.

A mix names:

- ``pool_rows``, ``noise``: the pool of inputs requests draw their rows
  from (see :mod:`bench.reference`);
- ``predict``: the predict stream.  ``"loop": "open"`` sends Poisson
  arrivals at a mean ``rate`` (requests/s) whatever the server does, the
  same set of gaps and request sizes for every seed, in the seed's order;
  ``on_s``/``off_s`` switch it on and off, at ``rate * (on_s + off_s) /
  on_s`` while on, so the mean stays ``rate``.  ``"loop": "closed"`` runs
  ``clients`` callers that each send their next request when the last one
  is answered.  ``rows`` gives the rows per request: ``min``..``max``,
  log-uniform (or fixed where they are equal);
- ``learn`` (optional): ``clients`` closed-loop feeders of labelled
  batches of ``rows`` rows;
- ``check_rows``: how many served rows the check compares, at most.

Open-loop latency is taken from each request's scheduled arrival, so a
generator that runs late adds its lateness to the latency rather than
hiding it; the lateness itself is recorded too.

What the window sends and gets back is kept in columns (:class:`Stream`),
not in an object per request: the generator shares the server's process
and heap, and records that outlive their request would make the garbage
collector's full collections due inside the window on the generator's
account, not the server's.
"""

from __future__ import annotations

import asyncio
import contextlib
import time

import numpy as np


def request_sizes(rows: dict, n: int, rng) -> np.ndarray:
    """``n`` rows-per-request draws: log-uniform over min..max."""
    lo, hi = int(rows["min"]), int(rows["max"])
    if lo == hi:
        return np.full(n, lo, np.int64)
    u = rng.uniform(np.log(lo), np.log(hi + 1), n)
    return np.minimum(np.floor(np.exp(u)).astype(np.int64), hi)


WORK_SEED = 0
"""Seed of the set of gaps and request sizes an open loop sends: the same
set for every run seed, which only orders it, so every seed asks the
server for the same work."""


def _on_time(seconds: float, on: float, off: float) -> float:
    """Seconds of ``[0, seconds)`` in which an on-off stream is on."""
    if on <= 0:
        return seconds
    full, rest = divmod(seconds, on + off)
    return full * on + min(rest, on)


def arrivals(predict: dict, seconds: float, rng) -> np.ndarray:
    """Scheduled send offsets in ``[0, seconds)`` of an open loop:
    ``round(rate * seconds)`` arrivals with exponential gaps (a Poisson
    stream given its count).  The gaps are one set drawn from
    :data:`WORK_SEED`; ``rng`` only shuffles them."""
    rate = float(predict["rate"])
    on, off = float(predict.get("on_s", 0)), float(predict.get("off_s", 0))
    span = _on_time(seconds, on, off)
    n = int(round(rate * seconds))
    gaps = np.random.default_rng([WORK_SEED, 0]).exponential(1.0, n + 1)
    gaps = np.maximum(gaps, 1e-9)
    t = np.cumsum(rng.permutation(gaps))
    t = t[:n] * (span / t[-1])
    if on > 0:
        # on-time to wall time: each on-phase is followed by an off-phase
        t = np.floor(t / on) * (on + off) + np.mod(t, on)
    return t


def fixed_sizes(rows: dict, n: int, rng) -> np.ndarray:
    """``n`` rows-per-request: one set of :func:`request_sizes` draws from
    :data:`WORK_SEED`, in an order drawn from ``rng``."""
    return rng.permutation(request_sizes(
        rows, n, np.random.default_rng([WORK_SEED, 1])))


class Stream:
    """The requests of one stream, one column entry each, in send order.

    ``rows_of(i)`` are request ``i``'s pool rows, a slice of one index
    column; its answer lands in ``prediction``/``class_sums`` at the same
    rows (a predict), or in ``value`` (a labelled batch: the version it
    published).  ``t_done`` is NaN until answered; ``error`` marks a
    request whose call raised.  Columns grow by doubling where the count
    is not known ahead (closed loops).
    """

    _REQ = {"t_sched": np.float64, "t_sent": np.float64,
            "t_done": np.float64, "v_lo": np.int64, "v_hi": np.int64,
            "size": np.int64, "start": np.int64, "value": np.int64,
            "error": bool}

    def __init__(self, requests: int = 64, rows: int = 1024):
        self.n = 0                    # requests sent
        self.n_rows = 0               # rows of those requests
        self._alloc(max(1, requests), max(1, rows))
        self.prediction = self.class_sums = None
        self.errors: list[BaseException] = []   # the first few raised

    def _alloc(self, requests: int, rows: int) -> None:
        for name, dtype in self._REQ.items():
            col = np.zeros(requests, dtype)
            old = getattr(self, name, None)
            if old is not None:
                col[:self.n] = old[:self.n]
            setattr(self, name, col)
        self.t_done[self.n:] = np.nan
        self.value[self.n:] = -1
        idx = np.zeros(rows, np.int64)
        if getattr(self, "idx", None) is not None:
            idx[:self.n_rows] = self.idx[:self.n_rows]
        self.idx = idx
        for name in ("prediction", "class_sums"):
            old = getattr(self, name, None)
            if old is not None:
                col = np.zeros((rows,) + old.shape[1:], old.dtype)
                col[:self.n_rows] = old[:self.n_rows]
                setattr(self, name, col)

    @classmethod
    def scheduled(cls, t_sched: np.ndarray, sizes: np.ndarray,
                  idx: np.ndarray) -> Stream:
        """A stream whose every request is known ahead (an open loop)."""
        s = cls(len(t_sched), len(idx))
        s.n, s.n_rows = len(t_sched), len(idx)
        s.t_sched[:] = t_sched
        s.size[:] = sizes
        s.start[:] = np.cumsum(sizes) - sizes
        s.idx[:] = idx
        return s

    def add(self, t_sched: float, rows: np.ndarray) -> int:
        """Append one request (a closed loop) → its number."""
        k = len(rows)
        if self.n == len(self.t_sched) or self.n_rows + k > len(self.idx):
            self._alloc(2 * len(self.t_sched),
                        2 * max(len(self.idx), self.n_rows + k))
        i = self.n
        self.t_sched[i], self.size[i], self.start[i] = \
            t_sched, k, self.n_rows
        self.idx[self.n_rows:self.n_rows + k] = rows
        self.n, self.n_rows = i + 1, self.n_rows + k
        return i

    def rows_of(self, i: int) -> np.ndarray:
        return self.idx[self.start[i]:self.start[i] + self.size[i]]

    def keep(self, i: int, result) -> None:
        """Store predict ``i``'s answer (its prediction and class sums)."""
        if self.prediction is None:
            rows = len(self.idx)
            sums = np.asarray(result.class_sums)
            self.prediction = np.zeros(rows, np.asarray(
                result.prediction).dtype)
            self.class_sums = np.zeros((rows,) + sums.shape[1:], sums.dtype)
        lo, hi = self.start[i], self.start[i] + self.size[i]
        self.prediction[lo:hi] = np.asarray(result.prediction)
        self.class_sums[lo:hi] = np.asarray(result.class_sums)

    def fail(self, i: int, exc: BaseException) -> None:
        self.error[i] = True
        if len(self.errors) < 8:
            self.errors.append(exc)

    # -- what the stream produced --------------------------------------

    def answered(self) -> np.ndarray:
        """Numbers of the requests answered without error."""
        n = self.n
        return np.flatnonzero(~np.isnan(self.t_done[:n])
                              & ~self.error[:n])

    def answered_in_window(self, seconds: float) -> np.ndarray:
        ok = self.answered()
        return ok[self.t_done[ok] <= seconds]

    def latencies_s(self) -> np.ndarray:
        """Per answered request: answer minus scheduled send."""
        ok = self.answered()
        return self.t_done[ok] - self.t_sched[ok]

    def lateness_s(self) -> np.ndarray:
        """Per request sent: actual send minus scheduled send."""
        return self.t_sent[:self.n] - self.t_sched[:self.n]

    def unanswered(self) -> int:
        return int(np.sum(np.isnan(self.t_done[:self.n])
                          & ~self.error[:self.n]))

    def failed(self) -> int:
        return int(np.sum(self.error[:self.n]))


def schedule(predict: dict, seconds: float, pool_rows: int,
             rng) -> Stream:
    """The whole open-loop schedule, drawn before the window opens."""
    t = arrivals(predict, seconds, rng)
    sizes = fixed_sizes(predict["rows"], len(t), rng)
    idx = rng.integers(0, pool_rows, int(sizes.sum()))
    return Stream.scheduled(t, sizes, idx)


class Window:
    """Drives one measured window against ``submit`` / ``submit_labeled``
    coroutine functions and keeps every request it sent, in the streams
    ``predicts`` and ``updates``.

    ``version()`` reads the served state version (0 where nothing
    learns); ``span(name)`` gives a context manager around each call (a
    profiler annotation in a traced run).
    """

    def __init__(self, pool: np.ndarray, labels: np.ndarray, *, submit,
                 submit_labeled=None, version=lambda: 0,
                 span=lambda name: contextlib.nullcontext(),
                 clock=time.perf_counter):
        self.pool, self.labels = pool, labels
        self.submit, self.submit_labeled = submit, submit_labeled
        self.version, self.span, self.clock = version, span, clock
        self.predicts = Stream()
        self.updates = Stream()
        self.t0 = 0.0
        self.seconds = 0.0

    def now(self) -> float:
        return self.clock() - self.t0

    async def _predict(self, s: Stream, i: int) -> None:
        s.t_sent[i] = self.now()
        s.v_lo[i] = self.version()
        try:
            with self.span("submit"):
                result = await self.submit(self.pool[s.rows_of(i)])
            s.keep(i, result)
        except Exception as exc:            # counted as failed, not fatal
            s.fail(i, exc)
        s.t_done[i] = self.now()
        s.v_hi[i] = self.version()

    async def _update(self, s: Stream, i: int) -> None:
        s.t_sent[i] = self.now()
        rows = s.rows_of(i)
        try:
            with self.span("submit_labeled"):
                s.value[i] = int(await self.submit_labeled(
                    self.pool[rows], self.labels[rows]))
        except Exception as exc:
            s.fail(i, exc)
        s.t_done[i] = self.now()

    async def _open(self, s: Stream, live: set) -> None:
        for i in range(s.n):
            delay = s.t_sched[i] - self.now()
            if delay > 0:
                await asyncio.sleep(delay)
            task = asyncio.ensure_future(self._predict(s, i))
            live.add(task)
            task.add_done_callback(live.discard)

    async def _closed(self, rows: dict, rng, s: Stream, call) -> None:
        while self.now() < self.seconds:
            n = int(request_sizes(rows, 1, rng)[0])
            await call(s, s.add(self.now(),
                                rng.integers(0, len(self.pool), n)))

    async def run(self, traffic: dict, seconds: float, rng,
                  drain_s: float = 60.0, on_open=None,
                  span_window: bool = False) -> None:
        """Send the mix for ``seconds``, then wait up to ``drain_s`` for
        every answer still due.  ``on_open()`` runs as the window opens;
        with ``span_window`` a ``span("window")`` covers the window."""
        predict = traffic["predict"]
        learn = traffic.get("learn")
        self.seconds = float(seconds)
        if predict["loop"] == "open":
            self.predicts = schedule(predict, seconds, len(self.pool), rng)
        streams = [np.random.default_rng(rng.integers(2**63))
                   for _ in range(int(predict.get("clients", 0))
                                  + int((learn or {}).get("clients", 0)))]
        if on_open is not None:
            on_open()
        self.t0 = self.clock()
        jobs, live = [], set()
        if span_window:
            window_span = self.span("window")
            window_span.__enter__()

            async def close():
                await asyncio.sleep(max(0.0, self.seconds - self.now()))
                window_span.__exit__(None, None, None)

            jobs.append(asyncio.ensure_future(close()))
        if predict["loop"] == "open":
            jobs.append(asyncio.ensure_future(self._open(self.predicts,
                                                         live)))
        else:
            for _ in range(int(predict["clients"])):
                jobs.append(asyncio.ensure_future(self._closed(
                    predict["rows"], streams.pop(), self.predicts,
                    self._predict)))
        if learn:
            fixed = {"min": learn["rows"], "max": learn["rows"]}
            for _ in range(int(learn["clients"])):
                jobs.append(asyncio.ensure_future(self._closed(
                    fixed, streams.pop(), self.updates, self._update)))
        done, pending = await asyncio.wait(jobs, timeout=seconds + drain_s)
        for job in pending:
            job.cancel()
        if live:
            left = max(1.0, seconds + drain_s - self.now())
            await asyncio.wait(set(live), timeout=left)
        for job in done:
            job.result()

    # -- what the window produced ---------------------------------------

    def latencies_s(self) -> np.ndarray:
        """Per answered predict: answer minus scheduled send."""
        return self.predicts.latencies_s()

    def lateness_s(self) -> np.ndarray:
        """Per predict sent: actual send minus scheduled send."""
        return self.predicts.lateness_s()

    def unanswered(self) -> int:
        return self.predicts.unanswered() + self.updates.unanswered()

    def failed(self) -> int:
        return self.predicts.failed() + self.updates.failed()

    def attempted(self) -> int:
        return self.predicts.n + self.updates.n
