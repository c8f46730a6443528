"""The server's own spans in a profiler trace: where each batch's host time
goes, and what the server was doing while the device sat idle.

    python3 bench/spans.py --workload <name> --seed <n> --seconds <s>

From the root of a checkout.  It runs one window of the cell as
``bench/run.py --trace 1`` does, keeps the profiler trace, and prints one
JSON line:

- ``spans``: for each of the program's spans (``PROGRAM_SPANS``) inside
  the window, its count and its mean, median and total;
- ``clock``: the shift of the device's ops onto the host's clock that puts
  the most device time inside some batch's stage B (the start of its
  ``tm.stageB.dispatch`` to the end of its ``tm.stageB.sync``), and the
  share so contained before and after that shift.  The profiler places
  the two clocks up to a couple of milliseconds apart, from run to run;
- ``idle_in_server``: the share of the window in which the device runs no
  op while a server stage (a span not in ``AWAIT_SPANS``) is open on some
  host thread, after the shift; null where less than 95% of the device's
  time lies inside stage B even so, as the alignment is then not known;
- ``gaps``: the longest idle gaps of the device, each named by the harness
  spans and then the program spans open at its middle;
- the window's ``predict_p50_ms``, ``served_rows_per_s`` and
  ``stage_b_ms`` as the benchmark's readers compute them, with the
  profiler on, and ``queue_wait_ms``, the mean wait from arrival to
  dispatch by the server's ``stats()["queue_wait"]``.

It makes no correctness check; ``bench/run.py`` does.  It exits 2 when
JAX finds no TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import dataclasses                                            # noqa: E402
import json                                                   # noqa: E402
import sys                                                    # noqa: E402
import warnings                                               # noqa: E402
from pathlib import Path                                      # noqa: E402

import numpy as np                                            # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace                                       # noqa: E402

# The server's spans and those of them that cover its awaits:
# ``repro.serve.tm_server.SPANS`` and ``AWAIT_SPANS`` (a test keeps them
# equal).  Copies, so that a trace of a program without them reads empty.
PROGRAM_SPANS = ("tm.idle", "tm.coalesce", "tm.pipeline_wait", "tm.assemble",
                 "tm.stageB.dispatch", "tm.stageB.sync", "tm.stageB.copy",
                 "tm.fanout", "tm.publish", "tm.train_step")
AWAIT_SPANS = ("tm.idle", "tm.coalesce", "tm.pipeline_wait")
STAGES = tuple(n for n in PROGRAM_SPANS if n not in AWAIT_SPANS)

MAX_SHIFT_NS = 2e6          # how far apart the profiler may set the clocks
MIN_CONTAINED = 0.95        # below this, idle_in_server is not known


@dataclasses.dataclass(frozen=True)
class Span:
    """One program span: its host thread (plane, line index), its name
    without the ``#key=value#`` metadata, and that metadata."""
    thread: tuple[str, int]
    name: str
    args: dict
    start_ns: float
    end_ns: float


def load(log_dir: str | Path) -> tuple[list[trace.Event], list[Span]]:
    """(every event, as :func:`bench.trace.load` gives them; the program's
    spans with their threads and metadata) of the ``.xplane.pb`` under
    ``log_dir``."""
    import jax
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(str(found[-1]))
    events, spans = [], []
    with warnings.catch_warnings():
        # the first read of a metadata list builds its type, which warns
        # (and with warnings made errors, aborts the process)
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    start, dur = float(ev.start_ns), float(ev.duration_ns)
                    events.append(trace.Event(plane.name, line.name,
                                              ev.name, start, dur))
                    name = ev.name.split("#", 1)[0]
                    if name in PROGRAM_SPANS:
                        # reading metadata costs microseconds an event:
                        # the program's spans alone
                        spans.append(Span((plane.name, i), name,
                                          dict(ev.stats), start,
                                          start + dur))
    return events, spans


def durations(spans: list[Span], lo: float, hi: float
              ) -> dict[str, list[float]]:
    """Each of the ``PROGRAM_SPANS`` -> the seconds of each of its spans
    that lie inside ``lo..hi``; a span never recorded maps to []."""
    out: dict[str, list[float]] = {n: [] for n in PROGRAM_SPANS}
    for s in spans:
        if s.start_ns >= lo and s.end_ns <= hi:
            out[s.name].append((s.end_ns - s.start_ns) * 1e-9)
    return out


def device_busy(events: list[trace.Event], lo: float, hi: float,
                device_plane: str = "/device:TPU", op_line: str = "XLA Ops"
                ) -> list[tuple[float, float]]:
    """The busy intervals of the first device plane within ``lo..hi``
    (the plane :func:`bench.trace.reduce` finds its gaps on)."""
    planes: dict[str, list[tuple[float, float]]] = {}
    for e in events:
        if (e.plane.startswith(device_plane) and e.line.startswith(op_line)
                and e.dur_ns > 0):
            planes.setdefault(e.plane, []).append((e.start_ns, e.end_ns))
    if not planes:
        return []
    return trace.union(trace.clip(planes[sorted(planes)[0]], lo, hi))


def stage_b(spans: list[Span]) -> list[tuple[float, float]]:
    """Each batch's stage B on the device's account, from the start of its
    ``tm.stageB.dispatch`` to the end of its ``tm.stageB.sync``, merged."""
    start = {s.args.get("batch"): s.start_ns for s in spans
             if s.name == "tm.stageB.dispatch"}
    return trace.union([(start[s.args.get("batch")], s.end_ns)
                        for s in spans if s.name == "tm.stageB.sync"
                        and start.get(s.args.get("batch"), s.end_ns)
                        < s.end_ns])


def _covered(cover: list[tuple[float, float]], busy: np.ndarray,
             shifts: np.ndarray) -> np.ndarray:
    """For each shift, the time of the ``busy`` intervals (an (n, 2)
    array), moved by it, that lies inside the disjoint sorted ``cover``."""
    knots = np.asarray(cover, float).ravel()
    origin = knots[0]          # profiler times are since the epoch: keep
    knots = knots - origin     # the sums' precision to well under 1 ns
    busy = busy - origin
    # cumulative covered time at each knot: flat across gaps, rising
    # one for one inside an interval
    lengths = np.diff(knots, prepend=knots[0])
    lengths[0::2] = 0.0
    acc = np.cumsum(lengths)
    out = np.empty(len(shifts))
    for i, shift in enumerate(shifts):
        f = np.interp(busy + shift, knots, acc)
        out[i] = (f[:, 1] - f[:, 0]).sum()
    return out


def align(busy: list[tuple[float, float]], cover: list[tuple[float, float]],
          max_shift: float = MAX_SHIFT_NS) -> tuple[float, float, float]:
    """(shift in ns, share of the busy time inside ``cover`` unshifted,
    that share after the shift): the shift within ``±max_shift``, on a
    grid of 10 µs and then 0.25 µs, that puts the most busy time inside
    ``cover``; of equal ones the smallest.  The stage-B windows recur, so
    a shift by one period may contain as much: it is the share contained
    that counts, not the shift."""
    if not busy or not cover:
        return 0.0, 0.0, 0.0
    b = np.asarray(busy, float)
    total = float((b[:, 1] - b[:, 0]).sum())
    best = 0.0
    for step, half in ((1e4, max_shift), (250.0, 1e4)):
        grid = best + np.arange(-half, half + step / 2, step)
        grid = grid[np.abs(grid) <= max_shift]
        got = _covered(cover, b, grid)
        top = np.flatnonzero(got >= got.max() - 1e-6 * total)
        best = float(grid[top[np.argmin(np.abs(grid[top]))]])
    before, after = _covered(cover, b, np.array([0.0, best])) / total
    return best, float(before), float(after)


def idle_in_server(spans: list[Span], busy: list[tuple[float, float]],
                   lo: float, hi: float, shift: float = 0.0) -> float | None:
    """Share of ``lo..hi`` in which the device, its intervals moved by
    ``shift``, runs nothing while one of the ``STAGES`` is open on some
    host thread; None where no stage is open in it."""
    open_ = trace.union(trace.clip([(s.start_ns, s.end_ns) for s in spans
                                    if s.name in STAGES
                                    and s.end_ns > s.start_ns], lo, hi))
    if not open_:
        return None
    open_ns = sum(e - s for s, e in open_)
    moved = np.asarray(busy, float).reshape(-1, 2)
    inside = _covered(open_, moved, np.array([shift]))[0] if busy else 0.0
    return float((open_ns - inside) / (hi - lo))


def gaps(events: list[trace.Event], spans: list[Span],
         busy: list[tuple[float, float]], lo: float, hi: float,
         shift: float = 0.0, top: int = 10) -> list[tuple[str, float]]:
    """The ``top`` longest gaps between the device's busy intervals (moved
    by ``shift``) in ``lo..hi``, longest first, each named as in
    :func:`bench.trace.reduce` by the harness spans open at its middle
    and then by the program spans open there: ``submit+tm.idle at
    11.7835s``."""
    edges = [lo] + [x + shift for iv in busy for x in iv] + [hi]
    found = sorted(((s, e) for s, e in zip(edges[0::2], edges[1::2])
                    if e > s), key=lambda g: g[1] - g[0], reverse=True)
    found = found[:top]
    by_name: dict[str, list[tuple[float, float]]] = {}
    for ev in events:
        if ev.name in trace.HARNESS_SPANS:
            by_name.setdefault(ev.name, []).append((ev.start_ns, ev.end_ns))
    for sp in spans:
        by_name.setdefault(sp.name, []).append((sp.start_ns, sp.end_ns))
    by_name = {n: np.asarray(iv, float) for n, iv in by_name.items()}

    def open_at(names, t):
        return [n for n in names if n in by_name and np.any(
            (by_name[n][:, 0] <= t) & (t < by_name[n][:, 1]))]

    out = []
    for s, e in found:
        mid = (s + e) / 2
        label = "+".join(["+".join(open_at(trace.HARNESS_SPANS, mid))
                          or "no harness call"]
                         + open_at(PROGRAM_SPANS, mid))
        out.append((f"{label} at {(s - lo) * 1e-9:.4f}s", (e - s) * 1e-9))
    return out


def report(events: list[trace.Event], spans: list[Span],
           device_plane: str = "/device:TPU", op_line: str = "XLA Ops"
           ) -> dict:
    """What the module's docstring lists, for the ``window`` span."""
    lo, hi = trace.window_of(events)
    busy = device_busy(events, lo, hi, device_plane, op_line)
    shift, before, after = align(busy, stage_b(spans))
    idle = idle_in_server(spans, busy, lo, hi, shift)
    per = {}
    for name, d in durations(spans, lo, hi).items():
        if d:
            per[name] = {"n": len(d), "mean_ms": float(np.mean(d)) * 1e3,
                         "p50_ms": float(np.median(d)) * 1e3,
                         "total_s": float(np.sum(d))}
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(e - s for s, e in busy) * 1e-9,
        "spans": per,
        "clock": {"shift_us": shift * 1e-3, "in_stage_b": before,
                  "in_stage_b_shifted": after},
        "idle_in_server": idle if after >= MIN_CONTAINED else None,
        "gaps": gaps(events, spans, busy, lo, hi, shift),
    }


def measure(cfg: dict, traffic: dict, *, seed: int, seconds: float,
            device_lines: tuple[str, str], t_start: float) -> dict:
    """One traced window of the cell → :func:`report` of its trace."""
    import asyncio
    import tempfile

    import jax

    from bench import harness, readers
    seeds_ = harness.seeds(seed)
    ta, pool, labels, _ = harness.prepare(cfg, traffic, seeds_)
    with tempfile.TemporaryDirectory() as trace_dir:
        got = asyncio.run(harness.serve(
            cfg, traffic, ta, pool, labels, seeds_=seeds_, seconds=seconds,
            traced=True, t_start=t_start, trace_dir=trace_dir,
            cache_events={"hits": 0, "misses": 0}))
        jax.profiler.stop_trace()
        events, spans = load(trace_dir)
    out = report(events, spans, *device_lines)
    run = harness.Run(cfg=cfg, window=got["window"], seconds=seconds,
                      setup_s=got["setup_s"], nnz=0, peak=None,
                      stats0=got["stats0"], stats1=got["stats1"])
    wait0, wait1 = (s.get("queue_wait") for s in (run.stats0, run.stats1))
    waited = wait1["requests"] - wait0["requests"] if wait1 else 0
    out.update(
        requests=run.window.predicts.n,
        # the window's own end-to-end numbers, with the profiler on
        predict_p50_ms=readers.latency_ms(run, 0.50),
        served_rows_per_s=readers.rows_per_s(run, "predicts"),
        stage_b_ms=readers.stage_b_ms(run),
        queue_wait_ms=((wait1["total_ms"] - wait0["total_ms"]) / waited
                       if waited else None))
    return out


def main(argv: list[str] | None = None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    spec = harness.load_spec()
    cell, cfg, traffic = harness.resolve(spec, args.workload)
    try:
        devices = harness.require_devices(int(cell["chips"]))
    except harness.NoChip as exc:
        print(f"spans: {exc}", file=sys.stderr)
        return 2
    harness.enable_cache()
    out = measure(cfg, traffic, seed=args.seed, seconds=args.seconds,
                  device_lines=trace.DEVICE_LINES[devices[0].platform],
                  t_start=T_START)
    print(json.dumps(dict(workload=args.workload, seed=args.seed, **out)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
