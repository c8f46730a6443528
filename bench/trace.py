"""Reduction of a profiler trace to device busy time, op times and idle
gaps.

A traced run writes the JAX profiler's ``.xplane.pb``; :func:`load` reads
it with ``jax.profiler.ProfileData`` into plain events ``(plane, line,
name, start_ns, dur_ns)``.  Device operations are the events on the lines
named ``op_line`` of the planes whose names start with ``device_plane``
("XLA Ops" on "/device:TPU:<n>").  The harness's own spans are host events
with the names it annotates (``window``, ``submit``, ``submit_labeled``,
``check``); the ``window`` span bounds the measured window.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

HARNESS_SPANS = ("submit", "submit_labeled", "check")

# platform -> (device plane prefix, op line prefix) of the device's ops
DEVICE_LINES = {"tpu": ("/device:TPU", "XLA Ops")}


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(log_dir: str | Path) -> list[Event]:
    """Every event of the one ``.xplane.pb`` under ``log_dir``."""
    import jax
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(str(found[-1]))
    return [Event(plane.name, line.name, ev.name, float(ev.start_ns),
                  float(ev.duration_ns))
            for plane in data.planes for line in plane.lines
            for ev in line.events]


_HLO = re.compile(r"^(%[\w.\-]+) = (\(?\w+\[[\d,]*\])")


def op_name(name: str) -> str:
    """A device op's short name: ``%fusion.12 u32[64000,2]`` for an HLO
    line, else the name's first 80 characters."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over device planes
    devices: int
    op_s: dict[str, float]            # device op name -> seconds
    gaps: list[tuple[str, float]]     # longest idle gaps, longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_of(events: list[Event], name: str = "window"
              ) -> tuple[float, float]:
    spans = [e for e in events if e.name == name and e.dur_ns > 0]
    if not spans:
        raise ValueError(f"trace holds no {name!r} span")
    w = max(spans, key=lambda e: e.dur_ns)
    return w.start_ns, w.end_ns


def _covering(spans: list[Event], t: np.ndarray) -> np.ndarray:
    """How many of ``spans`` are open at each time in ``t``."""
    if not spans:
        return np.zeros(len(t), np.int64)
    starts = np.sort([e.start_ns for e in spans])
    ends = np.sort([e.end_ns for e in spans])
    return (np.searchsorted(starts, t, side="right")
            - np.searchsorted(ends, t, side="right"))


def reduce(events: list[Event], device_plane: str = "/device:TPU",
           op_line: str = "XLA Ops", *, top: int = 10,
           span_names=HARNESS_SPANS) -> Reduced:
    """Busy time, op times and the ``top`` longest idle gaps inside the
    ``window`` span.  Each gap is named by the harness spans open at its
    middle ("submit", "submit_labeled+submit", or "no harness call")."""
    lo, hi = window_of(events)
    planes: dict[str, list[tuple[float, float]]] = {}
    op_s: dict[str, float] = {}
    for e in events:
        if not (e.plane.startswith(device_plane)
                and e.line.startswith(op_line)) or e.dur_ns <= 0:
            continue
        part = clip([(e.start_ns, e.end_ns)], lo, hi)
        if not part:
            continue
        planes.setdefault(e.plane, []).append(part[0])
        op_s[op_name(e.name)] = op_s.get(op_name(e.name), 0.0) + \
            (part[0][1] - part[0][0]) * 1e-9
    if not planes:
        raise ValueError(f"no device op on {device_plane!r}/{op_line!r} "
                         f"inside the window")
    busy = {p: union(iv) for p, iv in planes.items()}
    busy_s = float(np.mean([sum(e - s for s, e in iv) * 1e-9
                            for iv in busy.values()]))
    first = busy[sorted(busy)[0]]
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    gaps = gaps[:top]
    mids = np.array([(s + e) / 2 for s, e in gaps])
    open_by = {n: _covering([e for e in events if e.name == n
                             and not e.line.startswith(op_line)],
                            mids) for n in span_names}
    named = []
    for i, (s, e) in enumerate(gaps):
        label = "+".join(n for n in span_names if open_by[n][i] > 0)
        named.append((f"{label or 'no harness call'} at "
                      f"{(s - lo) * 1e-9:.4f}s", (e - s) * 1e-9))
    ranked = dict(sorted(op_s.items(), key=lambda kv: kv[1], reverse=True))
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_s,
                   devices=len(busy), op_s=ranked, gaps=named)

