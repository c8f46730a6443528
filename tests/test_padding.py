"""Padding neutrality: the serving seam can't change any real row.

The micro-batcher pads coalesced batches to bucket shapes with all-zero
rows (``repro.engine.pad_batch``) before ``infer`` and slices them off
after (``infer_padded``).  The registry invariant that makes this safe is
batch-axis data parallelism: for *every* registered backend, the padded
call must match the unpadded call row-for-row — predictions, class sums,
and aux extras — including lowest-index tie-break behaviour on
non-power-of-two shapes.  Runs under real hypothesis or the seeded
fallback shim.
"""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.tm import TMConfig, TMState
from repro.engine import (available_backends, get_engine, infer_padded,
                          pad_batch)

ALL_BACKENDS = available_backends()

# non-power-of-two everything: odd clause count (unequal ± halves), odd
# literal count words, so bucket padding crosses word boundaries
C, M, F = 3, 7, 9


def _random_tm(*, density=0.2, seed=0):
    cfg = TMConfig(n_classes=C, n_clauses=M, n_features=F)
    rng = np.random.default_rng(seed)
    ta = np.where(rng.random((C, M, cfg.n_literals)) < density,
                  cfg.n_states + 1, cfg.n_states)
    return cfg, TMState(ta=jnp.asarray(ta, jnp.int32))


def _literals(b, n_literals, seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 2, (b, n_literals), dtype=np.int8)


def _assert_rows_equal(res_padded, res_ref, b):
    np.testing.assert_array_equal(np.asarray(res_padded.prediction),
                                  np.asarray(res_ref.prediction)[:b])
    np.testing.assert_array_equal(np.asarray(res_padded.class_sums),
                                  np.asarray(res_ref.class_sums)[:b])
    assert set(res_padded.aux) == set(res_ref.aux)
    for k in res_ref.aux:
        np.testing.assert_array_equal(np.asarray(res_padded.aux[k]),
                                      np.asarray(res_ref.aux[k])[:b])


def test_pad_batch_semantics():
    lits = _literals(5, 2 * F, seed=0)
    assert pad_batch(lits, 5) is lits                   # exact fit: no copy
    padded = pad_batch(lits, 8)
    assert isinstance(padded, np.ndarray)               # numpy in → numpy out
    assert padded.shape == (8, 2 * F) and padded.dtype == lits.dtype
    np.testing.assert_array_equal(padded[:5], lits)
    assert not padded[5:].any()                         # neutral zero rows
    jpadded = pad_batch(jnp.asarray(lits), 8)           # jax in → jax out
    assert not isinstance(jpadded, np.ndarray)
    np.testing.assert_array_equal(np.asarray(jpadded), padded)
    with pytest.raises(ValueError, match="does not fit bucket"):
        pad_batch(lits, 4)


@settings(max_examples=10, deadline=None)
@given(b=st.integers(min_value=1, max_value=12),
       bucket=st.sampled_from((4, 12, 16)),
       seed=st.integers(min_value=0, max_value=2**16))
def test_padding_neutral_every_backend(b, bucket, seed):
    """infer on a padded bucket == infer on the unpadded batch,
    row-for-row — checked against *every* registered backend per draw
    (backends loop in the body: the hypothesis-fallback shim can't
    combine ``@given`` with ``parametrize``)."""
    if b > bucket:
        b = bucket      # keep the draw, fold into the valid region
    cfg, state = _random_tm(seed=7)
    lits = _literals(b, cfg.n_literals, seed)
    for backend in ALL_BACKENDS:
        engine = get_engine(backend, cfg, state)
        ref = engine.infer(jnp.asarray(lits))
        padded = infer_padded(engine, lits, bucket)
        assert np.asarray(padded.prediction).shape == (b,)
        _assert_rows_equal(padded, ref, b)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_padding_preserves_tie_break(backend):
    """Exact ties (duplicate class blocks) must still resolve to the
    lowest index through the padded path — the padded rows create their
    own (discarded) ties and must not disturb the arbiter elsewhere."""
    cfg, state = _random_tm(seed=3)
    ta = np.array(state.ta)
    ta[1] = ta[0]                       # classes 0 and 1 exactly tied
    state = TMState(ta=jnp.asarray(ta))
    lits = _literals(5, cfg.n_literals, seed=11)
    engine = get_engine(backend, cfg, state)
    ref = engine.infer(jnp.asarray(lits))
    padded = infer_padded(engine, lits, 16)
    sums = np.asarray(padded.class_sums)
    np.testing.assert_array_equal(sums[:, 0], sums[:, 1])
    assert not (np.asarray(padded.prediction) == 1).any()   # never index 1
    _assert_rows_equal(padded, ref, 5)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_padding_neutral_at_density_extremes(backend):
    """All-empty and all-include clause layouts are the boundary cases of
    the sparse/packed layouts; padding must stay invisible there too."""
    for density in (0.0, 1.0):
        cfg, state = _random_tm(density=density, seed=17)
        lits = _literals(3, cfg.n_literals, seed=19)
        engine = get_engine(backend, cfg, state)
        ref = engine.infer(jnp.asarray(lits))
        _assert_rows_equal(infer_padded(engine, lits, 4), ref, 3)


def test_infer_padded_exact_fit_returns_backend_result():
    cfg, state = _random_tm(seed=23)
    lits = _literals(4, cfg.n_literals, seed=23)
    engine = get_engine("oracle", cfg, state)
    res = infer_padded(engine, jnp.asarray(lits), 4)
    ref = engine.infer(jnp.asarray(lits))
    np.testing.assert_array_equal(np.asarray(res.prediction),
                                  np.asarray(ref.prediction))


@pytest.mark.parametrize("bucket", (4, 8), ids=("exact_fit", "padded"))
def test_host_literals_come_back_in_one_copy(bucket):
    """Numpy literals on an engine with ``infer_packed``: the result's
    leaves are numpy views of one host copy of the packed array, equal
    to ``infer`` row for row; ``np.array`` of a leaf is a copy of its own."""
    cfg, state = _random_tm(seed=29)
    lits = _literals(4, cfg.n_literals, seed=29)
    engine = get_engine("sparse_csr", cfg, state)
    res = infer_padded(engine, lits, bucket)
    assert res.aux == {}
    assert res.prediction.shape == (4,) and len(res.prediction) == 4
    assert res.class_sums.shape == (4, C)
    pred, sums = np.asarray(res.prediction), np.asarray(res.class_sums)
    assert pred.dtype == sums.dtype == np.int32
    # columns 0 and 1.. of one (B, 1 + C) array: sums start one int later
    addr = lambda a: a.__array_interface__["data"][0]      # noqa: E731
    assert addr(sums) == addr(pred) + pred.itemsize
    assert pred.strides[0] == sums.strides[0] == (1 + C) * pred.itemsize
    _assert_rows_equal(res, engine.infer(jnp.asarray(lits)), 4)
    own = np.array(res.class_sums)
    own[0, 0] += 1
    assert np.asarray(res.class_sums)[0, 0] == own[0, 0] - 1
    assert res.class_sums[0, 0] == sums[0, 0]
