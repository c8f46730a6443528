"""``bench/control_wide.py``'s closed form reads what the reference's
per-vote control loop reads, on a machine small enough for that loop."""

import numpy as np
import pytest

from bench import control_wide, harness, reference

SMALL = {"n_classes": 3, "n_clauses": 48, "n_features": 40,
         "n_states": 16, "include_density": 0.05}


@pytest.mark.parametrize("seed", [7, 2**31 + 5])
@pytest.mark.parametrize("vote_bits", [0, 4])
def test_wrapping_once_equals_wrapping_each_vote(seed, vote_bits):
    seeds = harness.seeds(seed)
    ta, proto = reference.make_machine(SMALL, seeds["machine"])
    lits, _ = reference.make_pool(proto, seeds["pool"], 256, 0.02)
    n = SMALL["n_states"]
    want = reference._infer(ta, lits, n_states=n, vote_bits=vote_bits)
    got = control_wide.infer(ta, lits, n_states=n, vote_bits=vote_bits)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # not vacuous: some exact sums leave the 4-bit register's range
    _, exact = reference._infer(ta, lits, n_states=n, vote_bits=0)
    assert np.abs(np.asarray(exact)).max() > 8


def test_main_puts_the_reference_back(monkeypatch):
    monkeypatch.setattr(control_wide.control, "main",
                        lambda argv: reference._infer is control_wide.infer)
    assert control_wide.main([]) is True
    assert reference._infer is control_wide._EXACT
