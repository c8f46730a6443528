"""Required work and the peak table, against values worked by hand."""

import json
from pathlib import Path

import pytest

from bench import work

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
V5E = work.PEAKS["TPU v5 lite"]


def _cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


# nnz fixed at 5% of C*M*2F included literals
@pytest.mark.parametrize("name,nnz,ops,bytes64", [
    # 2*78400 + 10*100 ; 64*1568/8 + 2*78400 + 64*10*4
    ("tm-mnist-100", 78400, 157800, 12544 + 156800 + 2560),
    # 2*39200 + 10*50 ; the index form 2*39200 = 78400 is smaller than
    # the 500*1568/8 = 98000-byte bitmap, so it counts
    ("tm-mnist-50", 39200, 78900, 12544 + 78400 + 2560),
])
def test_required_work_by_hand(name, nnz, ops, bytes64):
    cfg = _cfg(name)
    assert work.ops_per_row(cfg, nnz) == ops
    assert work.batch_bytes(cfg, nnz, 64) == bytes64
    t, bound = work.least_time(cfg, nnz, 64, V5E)
    assert bound == "bytes"
    assert t == pytest.approx(bytes64 / 819e9)


def test_bitmap_form_when_smaller():
    cfg = _cfg("tm-mnist-50")
    # a dense machine: 2 bytes an index would exceed the 98,000-byte bitmap
    assert work.batch_bytes(cfg, 60000, 1) == 196 + 98000 + 40


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v9 imaginary")
    assert work.peaks("TPU v5 lite")["int8_ops"] == 393e12


@pytest.mark.parametrize("name", ["tm-mnist-100", "tm-mnist-50"])
@pytest.mark.parametrize("rows", [1, 7, 64])
@pytest.mark.parametrize("factor", [1.0, 1.0000001, 3.0, 1e6])
def test_roofline_never_above_100(name, rows, factor):
    cfg = _cfg(name)
    least, _ = work.least_time(cfg, 78400, rows, V5E)
    share = work.roofline_pct(least, least * factor)
    assert 0 < share <= 100.0
    assert work.roofline_pct(least, 0.0) is None
