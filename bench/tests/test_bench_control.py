"""The check can fail: its control (the reference at the next lower
precision in the program's place) and faults planted in the timed path
each come out not correct, at a CPU test's size."""

import time

import numpy as np
import pytest

from bench import control, harness, work
from bench.tests.conftest import CPU_DEVICE, tiny

SEED = 2**33 + 11


@pytest.mark.parametrize("learn", [False, True])
def test_control_fails_where_program_passes(learn):
    _, cfg, traffic = tiny("mnist100-poisson", learn=learn)
    got = control.readings(cfg, traffic, seed=SEED, seconds=1.0)
    program, ctrl = got["program"], got["control"]
    assert all(v <= lim for v, lim in program.values() if lim is not None)
    # the control has to fail one of the cell's numbers, not each
    assert any(v > lim for v, lim in ctrl.values() if lim is not None)


def _flip_one_sum(orig):
    def infer_padded(engine, lits, bucket):
        from repro.engine import EngineResult
        res = orig(engine, lits, bucket)
        sums = np.array(res.class_sums)
        sums[0, 0] += 1
        return EngineResult(res.prediction, sums, res.aux)
    return infer_padded


def _drop_half(orig):
    def infer_padded(engine, lits, bucket):
        kept = np.array(lits)
        kept[(len(kept) + 1) // 2:] = 0      # the second half never counted
        return orig(engine, kept, bucket)
    return infer_padded


def _plant(monkeypatch, fault):
    import repro.serve.tm_server as tm_server
    from repro.engine.train import FusedTrainEngine
    if fault == "answer_altered":
        monkeypatch.setattr(tm_server, "infer_padded",
                            _flip_one_sum(tm_server.infer_padded))
    elif fault == "half_batch_left_out":
        monkeypatch.setattr(tm_server, "infer_padded",
                            _drop_half(tm_server.infer_padded))
    elif fault == "state_unchanged":
        monkeypatch.setattr(FusedTrainEngine, "step",
                            lambda self, state, key, x, y: state)


@pytest.mark.parametrize("workload,fault,number", [
    ("mnist100-poisson", "answer_altered", "wrong_rows"),
    ("mnist100-poisson", "half_batch_left_out", "wrong_rows"),
    ("mnist50-bulk", "half_batch_left_out", "wrong_rows"),
    ("learn", "state_unchanged", "state_mismatch"),
])
def test_fault_in_timed_path_is_not_correct(monkeypatch, workload, fault,
                                            number):
    _plant(monkeypatch, fault)
    learn = workload == "learn"
    workload = "mnist100-poisson" if learn else workload
    spec, cfg, traffic = tiny(workload, learn=learn)
    line, checks, _ = harness.run_cell(
        spec, workload, cfg, traffic, seed=SEED, seconds=1.0, traced=False,
        t_start=time.perf_counter(), device=CPU_DEVICE,
        cache_events={"hits": 0, "misses": 0},
        peak=work.peaks("TPU v5 lite"))
    assert line["correct"] is False
    value, limit = checks[number]
    assert value > limit
    assert line["checks"][number] == {"value": value, "limit": limit}
