"""The program's spans in a trace: reductions worked by hand on synthetic
spans, and traces of a small ``TMServer`` recorded on CPU into the test's
directory."""

import asyncio
import os
import subprocess
import sys
import time
from collections import defaultdict
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness, spans, trace
from bench.tests.conftest import tiny

ROOT = Path(__file__).resolve().parents[2]
CPU = ("/host:CPU", "tf_XLAPjRtCpuClient")


def test_program_spans_are_the_servers():
    from repro.serve import tm_server
    assert spans.PROGRAM_SPANS == tm_server.SPANS
    assert spans.AWAIT_SPANS == tm_server.AWAIT_SPANS
    assert set(spans.STAGES) == set(tm_server.SPANS) \
        - set(tm_server.AWAIT_SPANS)


def _sp(name, start, end, batch=None, thread=1):
    return spans.Span(("/host:CPU", thread), name,
                      {} if batch is None else {"batch": batch},
                      float(start), float(end))


def _ev(line, name, start, dur, plane="/device:TPU:0"):
    return trace.Event(plane, line, name, float(start), float(dur))


def test_durations_by_hand():
    got = spans.durations([
        _sp("tm.stageB.dispatch", 10, 20, batch=1),
        _sp("tm.stageB.dispatch", 30, 34, batch=2),
        _sp("tm.stageB.dispatch", 95, 110, batch=3),    # ends outside
        _sp("tm.fanout", 40, 42, thread=0),
    ], 0, 100)
    assert set(got) == set(spans.PROGRAM_SPANS)
    assert got["tm.stageB.dispatch"] == pytest.approx([10e-9, 4e-9])
    assert got["tm.fanout"] == pytest.approx([2e-9])
    assert got["tm.publish"] == []


def test_stage_b_pairs_a_batchs_dispatch_with_its_sync():
    got = spans.stage_b([
        _sp("tm.stageB.dispatch", 0, 10, batch=1),
        _sp("tm.stageB.sync", 10, 30, batch=1),
        _sp("tm.stageB.copy", 30, 35, batch=1),
        _sp("tm.stageB.dispatch", 40, 45, batch=2),
        _sp("tm.stageB.sync", 45, 50, batch=2),
        _sp("tm.stageB.sync", 60, 70, batch=9),          # no dispatch
    ])
    assert got == [(0, 30), (40, 50)]


def test_align_finds_the_shift_that_puts_the_device_inside_stage_b():
    # stage B every 2 ms for 1 ms; the device works 100 us at the end of
    # each, but its clock reads 337.5 us late
    cover = [(t, t + 1e6) for t in np.arange(0, 2e7, 2e6)]
    busy = [(t + 0.9e6 + 337.5e3, t + 1e6 + 337.5e3) for t, _ in cover]
    shift, before, after = spans.align(busy, cover)
    assert before == pytest.approx(0.0)
    assert after == pytest.approx(1.0)
    # every shift from -1237.5 to -337.5 us contains it all: the least
    assert shift == pytest.approx(-337.5e3)
    assert spans.align([], cover) == (0.0, 0.0, 0.0)
    # already inside: no shift
    assert spans.align([(0.5e6, 0.6e6)], cover) == (0.0, 1.0, 1.0)


def test_idle_in_server_by_hand():
    sp = [
        _sp("tm.stageB.dispatch", 0, 20, batch=4),
        _sp("tm.stageB.sync", 15, 20, batch=4),         # inside dispatch
        _sp("tm.fanout", 25, 40, thread=0),
        _sp("tm.idle", 40, 100, thread=0),              # an await: no
        _sp("tm.publish", 65, 80, thread=0),
    ]
    busy = [(10, 30), (60, 70)]
    # stages open 0..20, 25..40, 65..80 (50); the device runs in 10..20,
    # 25..30 and 65..70 of them (20): idle under a stage 30 of 100
    assert spans.idle_in_server(sp, busy, 0, 100) == pytest.approx(0.30)
    # moved 5 later it runs in 15..20, 25..35 and 65..75 of them (25)
    assert spans.idle_in_server(sp, busy, 0, 100, shift=5) \
        == pytest.approx(0.25)
    # moved 10 earlier: 0..20 and 50..60: only 20 of the stages' 50
    assert spans.idle_in_server(sp, busy, 0, 100, shift=-10) \
        == pytest.approx(0.30)
    # moved 40 earlier only 25..30 lies under a stage (fanout's)
    assert spans.idle_in_server(sp, busy, 0, 100, shift=-40) \
        == pytest.approx(0.45)
    assert spans.idle_in_server(sp, [], 0, 100) == pytest.approx(0.50)
    assert spans.idle_in_server(
        [s for s in sp if s.name == "tm.idle"], busy, 0, 100) is None


def test_gap_labels_add_the_open_program_spans():
    events = [_ev("python", "window", 0, 100, plane="/host:CPU"),
              _ev("python", "submit", 10, 40, plane="/host:CPU")]
    busy = [(5, 20), (60, 80), (97, 100)]
    sp = [_sp("tm.idle", 20, 60, thread=0),
          _sp("tm.coalesce", 80, 96, batch=2, thread=0),
          _sp("tm.fanout", 85, 90, batch=1, thread=0)]
    got = spans.gaps(events, sp, busy, 0, 100)
    assert [(name.split(" at ")[0], round(s * 1e9)) for name, s in got] \
        == [("submit+tm.idle", 40),
            ("no harness call+tm.coalesce+tm.fanout", 17),
            ("no harness call", 5)]
    assert got[0][0] == "submit+tm.idle at 0.0000s"
    # without program spans the labels are those of bench.trace.reduce
    bare = spans.gaps(events, [], busy, 0, 100)
    red = trace.reduce(events + [_ev("XLA Ops", "op", s, e - s)
                                 for s, e in busy])
    assert bare == red.gaps


def _traced_server(tmp_path, learn: bool):
    """Trace a small TMServer answering a few bursts of predicts (and,
    with ``learn``, one labelled batch) inside a ``window`` span."""
    from repro.core.tm import TMConfig, TMState
    from repro.serve import ServePolicy, TMServer
    cfg = TMConfig(n_classes=3, n_clauses=8, n_features=12)
    rng = np.random.default_rng(5)
    ta = np.where(rng.random((3, 8, cfg.n_literals)) < 0.3,
                  cfg.n_states + 1, cfg.n_states)
    lits = rng.integers(0, 2, (24, cfg.n_literals), dtype=np.int8)

    async def go():
        async with TMServer(cfg, TMState(ta=jax.numpy.asarray(ta)),
                            ServePolicy(max_batch=8, max_wait_us=1000),
                            train_backend="fused" if learn else None
                            ) as server:
            await server.warmup(train_batches=(8,) if learn else ())
            jax.profiler.start_trace(
                str(tmp_path), profiler_options=harness._profile_options())
            try:
                with jax.profiler.TraceAnnotation("window"):
                    for burst in range(4):
                        await asyncio.gather(*[
                            server.submit(lits[i:i + 1 + burst % 3])
                            for i in range(0, 24, 4)])
                        await asyncio.sleep(0.005)
                    if learn:
                        await server.submit_labeled(
                            lits[:8], rng.integers(0, 3, 8))
            finally:
                jax.profiler.stop_trace()

    asyncio.run(go())
    return spans.load(tmp_path)


def test_server_spans_in_a_cpu_trace(tmp_path):
    events, ours = _traced_server(tmp_path, learn=False)
    lo, hi = trace.window_of(events)
    got = spans.durations(ours, lo, hi)
    serving = [n for n in spans.PROGRAM_SPANS
               if n not in ("tm.publish", "tm.train_step")]
    assert all(got[n] for n in serving), {n: len(got[n]) for n in serving}
    threads = defaultdict(set)
    for s in ours:
        threads[s.name].add(s.thread)
    stage_b = threads["tm.stageB.dispatch"] | threads["tm.stageB.sync"] \
        | threads["tm.stageB.copy"]
    assert len(stage_b) == 1
    assert threads["tm.coalesce"] == threads["tm.fanout"] \
        == threads["tm.assemble"]
    assert not stage_b & threads["tm.coalesce"]    # not the event loop's
    # one batch's spans share its number, and run in pipeline order
    by_batch: dict[int, dict[str, spans.Span]] = defaultdict(dict)
    for s in ours:
        if "batch" in s.args:
            by_batch[s.args["batch"]][s.name] = s
    whole = [b for b in by_batch.values()
             if "tm.fanout" in b and "tm.coalesce" in b]
    assert len(whole) >= 4
    order = ("tm.coalesce", "tm.pipeline_wait", "tm.assemble",
             "tm.stageB.dispatch", "tm.stageB.sync", "tm.stageB.copy",
             "tm.fanout")
    for b in whole:
        assert set(b) == set(order)
        for first, then in zip(order, order[1:]):
            assert b[first].end_ns <= b[then].start_ns, (first, then)
    assert not any(s.args for s in ours if s.name == "tm.idle")


def test_learning_spans_in_a_cpu_trace(tmp_path):
    events, ours = _traced_server(tmp_path, learn=True)
    lo, hi = trace.window_of(events)
    got = spans.durations(ours, lo, hi)
    assert len(got["tm.train_step"]) == 1
    assert len(got["tm.publish"]) == 1
    publish = next(s for s in ours if s.name == "tm.publish")
    step = next(s for s in ours if s.name == "tm.train_step")
    assert publish.args == {"version": 1}
    assert step.end_ns <= publish.start_ns


def test_measure_a_tiny_cell_on_cpu():
    _, cfg, traffic = tiny("mnist50-bulk")
    out = spans.measure(cfg, traffic, seed=2**40 + 5, seconds=1.0,
                        device_lines=CPU, t_start=time.perf_counter())
    assert out["requests"] > 20
    assert out["served_rows_per_s"] > 0 and out["predict_p50_ms"] > 0
    assert out["stage_b_ms"] > 0
    assert out["queue_wait_ms"] > 0
    assert 0 < out["busy_s"] < out["window_s"]
    parts = [out["spans"][f"tm.stageB.{p}"] for p in
             ("dispatch", "sync", "copy")]
    assert all(p["n"] > 0 and p["mean_ms"] > 0 for p in parts)
    assert 0 <= out["clock"]["in_stage_b"] <= \
        out["clock"]["in_stage_b_shifted"] <= 1 + 1e-9
    assert abs(out["clock"]["shift_us"]) <= spans.MAX_SHIFT_NS * 1e-3
    assert out["idle_in_server"] is None or \
        0 <= out["idle_in_server"] <= 1 - out["busy_s"] / out["window_s"]
    assert 0 < len(out["gaps"]) <= 10
    assert any("tm." in name for name, _ in out["gaps"])


def test_spans_py_refuses_cpu():
    out = subprocess.run(
        [sys.executable, "bench/spans.py", "--workload", "mnist50-bulk",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()
