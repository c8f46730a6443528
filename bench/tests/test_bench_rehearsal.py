"""A CPU rehearsal of whole runs: each drives a cell's traffic for about a
second against a tiny TM through the harness (the device check alone is
skipped), and the benchmark's command refuses to run without a TPU."""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from bench import harness, work
from bench.tests.conftest import CPU_DEVICE, tiny

ROOT = Path(__file__).resolve().parents[2]


def run(workload, traced=False, seconds=1.0, learn=False):
    spec, cfg, traffic = tiny(workload, learn=learn)
    return harness.run_cell(
        spec, workload, cfg, traffic, seed=2**40 + 3, seconds=seconds,
        traced=traced, t_start=time.perf_counter(), device=CPU_DEVICE,
        cache_events={"hits": 0, "misses": 0},
        peak=work.peaks("TPU v5 lite"))


def test_rehearsal_poisson_end_to_end():
    line, checks, notes = run("mnist100-poisson")
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 50
    assert set(line["metrics"]) == {"predict_p50_ms", "setup_s"}
    assert list(line)[-1] == "checks"
    assert checks["wrong_rows"] == (0, 0) and checks["unanswered"] == (0, 0)
    assert checks["checked_rows"][0] > 0
    assert line["device"]["platform"] == "cpu"
    assert any(n.startswith("generator_lateness_ms") for n in notes)


def test_rehearsal_learn_traced(cpu_trace_lines):
    line, checks, _ = run("mnist100-poisson", traced=True, learn=True)
    assert line["correct"] is True
    assert checks["state_mismatch"] == (0, 0)
    assert checks["version_gaps"] == (0, 0)
    # the cell's per-layer metrics only
    assert set(line["metrics"]) == {"predict_p95_ms.serve",
                                    "batch_rows.serve", "stageB_ms.serve",
                                    "device_idle.serve"}
    assert not {"predict_p50_ms", "setup_s"} & set(line["metrics"])
    assert 0 < line["device"]["busy_s"] < line["device"]["window_s"]
    assert line["breakdown"]["device_ops"]
    assert len(line["breakdown"]["idle_gaps"]) <= 10


def test_rehearsal_bulk_closed_loop():
    line, _, _ = run("mnist50-bulk")
    assert line["correct"] is True
    assert set(line["metrics"]) == {"served_rows_per_s", "setup_s"}
    assert line["metrics"]["served_rows_per_s"]["unit"] == "rows/s"


def test_run_py_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mnist100-poisson",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert "no TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
