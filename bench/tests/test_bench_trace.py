"""Trace reduction: interval arithmetic by hand, and a small trace recorded
on CPU with ``jax.profiler`` (``data/cpu_trace.xplane.pb``).

The recording ran, inside a ``window`` span: a jitted matmul, a 30 ms sleep
inside a ``submit`` span, the matmul, a 20 ms sleep under no span, the
matmul, a 10 ms sleep inside a ``check`` span, and the matmul.  On CPU the
"device" ops are the XLA CPU client's thread.
"""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data"
CPU = ("/host:CPU", "tf_XLAPjRtCpuClient")


def test_union_and_clip_by_hand():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def _ev(line, name, start, dur, plane="/device:TPU:0"):
    return trace.Event(plane, line, name, start, dur)


def test_reduce_synthetic_by_hand():
    events = [
        _ev("python", "window", 0, 100, plane="/host:CPU"),
        _ev("python", "submit", 10, 40, plane="/host:CPU"),
        _ev("XLA Ops", "fusion", 5, 10),          # busy 5..15
        _ev("XLA Ops", "fusion", 12, 8),          # overlaps: busy 5..20
        _ev("XLA Ops", "copy", 60, 20),           # busy 60..80
        _ev("XLA Ops", "copy", 95, 10),           # clipped to 95..100
    ]
    red = trace.reduce(events)
    assert red.window_s == pytest.approx(100e-9)
    assert red.busy_s == pytest.approx(40e-9)
    assert red.idle_share == pytest.approx(0.6)
    assert red.op_s == pytest.approx({"copy": 25e-9, "fusion": 18e-9})
    # gaps: 20..60 (submit open at 40), 80..95, 0..5
    assert [round(s * 1e9) for _, s in red.gaps] == [40, 15, 5]
    assert red.gaps[0][0].startswith("submit at")
    assert red.gaps[1][0].startswith("no harness call at")


def test_reduce_recorded_cpu_trace():
    events = trace.load(DATA)
    red = trace.reduce(events, *CPU)
    assert red.devices == 1
    assert 0 < red.busy_s < red.window_s
    assert red.window_s == pytest.approx(0.0626, abs=0.005)
    # every instant of the window is busy or in a gap
    assert red.busy_s + sum(s for _, s in trace.reduce(
        events, *CPU, top=10**6).gaps) == pytest.approx(red.window_s)
    assert "dot_general.1" in red.op_s
    longest = [(name.split(" at ")[0], s) for name, s in red.gaps[:3]]
    assert [n for n, _ in longest] == ["submit", "no harness call", "check"]
    assert [s for _, s in longest] == pytest.approx([0.03, 0.02, 0.01],
                                                    abs=0.004)


def test_window_span_is_required():
    with pytest.raises(ValueError, match="no 'window' span"):
        trace.reduce([_ev("XLA Ops", "fusion", 0, 1)])
