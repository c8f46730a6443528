"""Tiny versions of the benchmark's cells for CPU tests."""

import sys

import pytest

from bench import harness, trace

if str(harness.SRC) not in sys.path:      # the system under test
    sys.path.insert(0, str(harness.SRC))

CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def tiny(workload: str, rate: float = 150.0, learn: bool = False):
    """(spec, cfg, traffic) of ``workload`` cut to a CPU test's size: 4
    classes of 32 clauses over 32 features, batches of at most 8 rows.
    ``learn`` adds a closed-loop feeder of 8-row labelled batches."""
    spec = harness.load_spec()
    _, cfg, traffic = harness.resolve(spec, workload)
    cfg = dict(cfg, n_classes=4, n_clauses=32, n_features=32,
               serve_policy=dict(cfg["serve_policy"], max_batch=8))
    predict = dict(traffic["predict"])
    predict["rows"] = {"min": min(predict["rows"]["min"], 8), "max": 8}
    if predict["loop"] == "open":
        predict["rate"] = rate
    else:
        predict["clients"] = 2
    traffic = dict(traffic, pool_rows=256, predict=predict, check_rows=2048)
    if learn:
        traffic["learn"] = {"clients": 1, "rows": 8}
    return spec, cfg, traffic


@pytest.fixture
def cpu_trace_lines(monkeypatch):
    """Read the XLA CPU client's thread as the device in a traced run."""
    monkeypatch.setitem(trace.DEVICE_LINES, "cpu",
                        ("/host:CPU", "tf_XLAPjRtCpuClient"))
