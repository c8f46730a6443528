"""The knee rule of the open-loop sweep."""

from bench import sweep


def _row(rate, holds, stalled=False):
    return {"rate": rate, "holds": holds, "stalled": stalled}


def test_knee_is_the_highest_rate_held_in_every_pass():
    rows = [_row(700, True), _row(800, True), _row(900, False),
            _row(700, True), _row(800, False), _row(900, True)]
    assert sweep.knee_of(rows) == 700


def test_stalled_windows_are_neither_held_nor_failed():
    rows = [_row(700, False, stalled=True), _row(800, True),
            _row(900, False), _row(700, True), _row(800, True),
            _row(900, True)]
    assert sweep.knee_of(rows) == 800
    assert sweep.knee_of([_row(700, False)]) is None
