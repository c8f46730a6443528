"""TrainEngine: every backend delta-exact with the reference step.

The registry's contract: for any (cfg, state), any labeled batch, and any
fixed PRNG key, all training backends return bitwise-identical new states
— across clause/literal/polarity edge cases (odd clause counts and their
unequal ±polarity halves, all-exclude and all-include machines, all-zero
and all-one literal rows, two-class machines where the sampled negative
class is forced) and under both PRNG implementations (the contract is
"same key ⇒ same draws", not a specific bit generator).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.tm import TMConfig, TMState, init_tm
from repro.core.tm_train import train_epoch, train_step
from repro.engine import (DEFAULT_TRAIN_BACKEND, available_train_backends,
                          clear_train_engine_cache, get_train_engine,
                          train_engine_cache_info)

ALL_TRAIN_BACKENDS = available_train_backends()

# (C, M, F): odd M (unequal +/− polarity halves), C=2 (forced negative
# class), tiny and wide feature spaces
SHAPES = [(2, 6, 9), (3, 10, 12), (5, 7, 33), (4, 12, 5), (10, 25, 49)]


def _random_tm(c, m, f, *, density=0.15, seed=0, batch=17):
    cfg = TMConfig(n_classes=c, n_clauses=m, n_features=f, T=5, s=3.9)
    rng = np.random.default_rng(seed)
    ta = np.where(rng.random((c, m, 2 * f)) < density,
                  cfg.n_states + 1, cfg.n_states)
    lits = rng.integers(0, 2, (batch, 2 * f), dtype=np.int8)
    lits[0] = 0                 # all-zero literal row (every clause fires
    lits[-1] = 1                # iff it has no positive-literal include)
    y = rng.integers(0, c, (batch,), dtype=np.int32)
    k = min(c, batch)
    y[:k] = np.arange(k)        # address as many distinct classes as fit
    return (cfg, TMState(ta=jnp.asarray(ta, jnp.int32)),
            jnp.asarray(lits), jnp.asarray(y))


def _assert_state_equal(a: TMState, b: TMState):
    np.testing.assert_array_equal(np.asarray(a.ta), np.asarray(b.ta))


def test_registry_has_all_backends():
    assert {"reference", "packed", "fused"} <= set(ALL_TRAIN_BACKENDS)
    assert DEFAULT_TRAIN_BACKEND in ALL_TRAIN_BACKENDS


def test_unknown_backend_raises():
    cfg = TMConfig(n_classes=2, n_clauses=4, n_features=3)
    with pytest.raises(KeyError, match="unknown TrainEngine backend"):
        get_train_engine("sgd", cfg)


@pytest.mark.parametrize("shape", SHAPES,
                         ids=lambda s: f"C{s[0]}M{s[1]}F{s[2]}")
@pytest.mark.parametrize("backend", ALL_TRAIN_BACKENDS)
def test_backend_delta_parity_randomized(backend, shape):
    cfg, st, lits, y = _random_tm(*shape, seed=sum(shape))
    key = jax.random.key(sum(shape) + 1)
    ref = train_step(cfg, st, key, lits, y)
    got = get_train_engine(backend, cfg).step(st, key, lits, y)
    _assert_state_equal(got, ref)


@pytest.mark.parametrize("density", [0.0, 1.0],
                         ids=["all_exclude", "all_include"])
@pytest.mark.parametrize("backend", ALL_TRAIN_BACKENDS)
def test_backend_parity_density_extremes(backend, density):
    """All-exclude machines (every clause empty, fires everywhere) and
    all-include machines are the clause-eval boundary cases."""
    cfg, st, lits, y = _random_tm(3, 8, 11, density=density, seed=21)
    key = jax.random.key(2)
    _assert_state_equal(get_train_engine(backend, cfg).step(st, key, lits, y),
                        train_step(cfg, st, key, lits, y))


@pytest.mark.parametrize("backend", ALL_TRAIN_BACKENDS)
def test_backend_parity_no_boost(backend):
    """boost_tpf=False exercises the (s−1)/s Type I include probability."""
    cfg, st, lits, y = _random_tm(4, 9, 13, seed=5)
    key = jax.random.key(3)
    ref = train_step(cfg, st, key, lits, y, boost_tpf=False)
    eng = get_train_engine(backend, cfg, boost_tpf=False)
    _assert_state_equal(eng.step(st, key, lits, y), ref)


@pytest.mark.parametrize("backend", ALL_TRAIN_BACKENDS)
def test_backend_parity_rbg_prng(backend):
    """The PRNG contract is impl-agnostic: rbg keys must agree too."""
    cfg, st, lits, y = _random_tm(3, 10, 12, seed=7)
    key = jax.random.key(11, impl="rbg")
    _assert_state_equal(get_train_engine(backend, cfg).step(st, key, lits, y),
                        train_step(cfg, st, key, lits, y))


@pytest.mark.parametrize("backend", ALL_TRAIN_BACKENDS)
def test_states_stay_in_bounds(backend):
    """Repeated saturating updates keep every TA inside [1, 2N]."""
    cfg, st, lits, y = _random_tm(2, 6, 7, seed=9, batch=32)
    eng = get_train_engine(backend, cfg)
    key = jax.random.key(4)
    for _ in range(5):
        key, k = jax.random.split(key)
        st = eng.step(st, k, lits, y)
    ta = np.asarray(st.ta)
    assert ta.min() >= 1 and ta.max() <= 2 * cfg.n_states


@settings(max_examples=12, deadline=None)
@given(c=st.integers(min_value=2, max_value=6),
       m=st.integers(min_value=2, max_value=14),
       f=st.integers(min_value=1, max_value=24),
       batch=st.integers(min_value=1, max_value=24),
       density=st.sampled_from((0.0, 0.05, 0.3, 1.0)),
       seed=st.integers(min_value=0, max_value=2**16))
def test_delta_parity_property(c, m, f, batch, density, seed):
    """Property: packed and fused match the reference bit-for-bit on
    arbitrary shapes, batch sizes, densities, and keys."""
    cfg, stt, lits, y = _random_tm(c, m, f, density=density, seed=seed,
                                   batch=batch)
    key = jax.random.key(seed)
    ref = train_step(cfg, stt, key, lits, y)
    for backend in ("packed", "fused"):
        got = get_train_engine(backend, cfg).step(stt, key, lits, y)
        _assert_state_equal(got, ref)


def test_uniform_threshold_equivalence():
    """(bits >> 9) < uniform_threshold(p)  ⟺  uniform(bits) < p, exactly."""
    from repro.kernels.train_fused import uniform_threshold
    key = jax.random.key(17)
    u = jax.random.uniform(key, (4096,))
    bits = jax.random.bits(key, (4096,), jnp.uint32)
    for p in (1.0, 0.5, 1 / 3.9, 2.9 / 3.9, 1e-4, 0.999999):
        want = np.asarray(u < p)
        got = np.asarray((bits >> 9) < jnp.uint32(uniform_threshold(p)))
        np.testing.assert_array_equal(got, want, err_msg=f"p={p}")


def test_train_epoch_backend_knob():
    """train_epoch(backend=...) is bit-exact with the in-module scan."""
    cfg, st, lits, y = _random_tm(3, 10, 12, seed=23, batch=40)
    key = jax.random.key(5)
    ref = train_epoch(cfg, st, key, lits, y, batch_size=8)
    for backend in ALL_TRAIN_BACKENDS:
        got = train_epoch(cfg, st, key, lits, y, batch_size=8,
                          backend=backend)
        _assert_state_equal(got, ref)


def test_train_engine_cache():
    """Same (backend, cfg, opts) → same engine object; distinct opts or
    cache=False build fresh."""
    clear_train_engine_cache()
    cfg = TMConfig(n_classes=3, n_clauses=8, n_features=10)
    e1 = get_train_engine("packed", cfg)
    assert get_train_engine("packed", cfg) is e1
    assert train_engine_cache_info()["hits"] >= 1
    assert get_train_engine("packed", cfg, boost_tpf=False) is not e1
    assert get_train_engine("packed", cfg, cache=False) is not e1
    # a distinct-but-equal cfg hashes equal (frozen dataclass) and shares
    cfg2 = TMConfig(n_classes=3, n_clauses=8, n_features=10)
    assert get_train_engine("packed", cfg2) is e1


def test_train_autotune_lookup_applied(tmp_path, monkeypatch):
    """Trainers take no tile opts: an autotune cache that still holds
    ``train:fused`` tile entries from older sweeps is ignored, and the
    engine builds, steps and persists only its real options."""
    import json
    from repro.engine import autotune
    from repro.engine.train import train_engine_opts
    clear_train_engine_cache()
    cfg, st, lits, y = _random_tm(3, 10, 12, seed=29)
    path = tmp_path / "autotune.json"
    path.write_text(json.dumps({"best": {
        autotune.shape_key(f"train:{b}", cfg): {"block_b": 32,
                                                "block_m": 32}
        for b in ("fused", "sparse", "sharded")}}))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    key = jax.random.key(6)
    for backend in ("fused", "sparse", "sharded"):
        assert autotune.lookup(f"train:{backend}", cfg) == {}
        eng = get_train_engine(backend, cfg, cache=False)
        assert "block_b" not in train_engine_opts(eng)
        _assert_state_equal(eng.step(st, key, lits, y),
                            train_step(cfg, st, key, lits, y))
    with pytest.raises(TypeError):
        get_train_engine("fused", cfg, cache=False, block_b=64)


def test_training_converges_through_engines():
    """End-to-end: the engine path actually learns (not just matches) —
    a few epochs on a separable toy problem beat chance markedly."""
    from repro.core.tm_train import evaluate
    cfg = TMConfig(n_classes=2, n_clauses=10, n_features=8, T=5, s=3.9)
    rng = np.random.default_rng(0)
    # class 1 iff feature 0 is set: trivially separable
    x = rng.integers(0, 2, (200, 8), dtype=np.int8)
    y = x[:, 0].astype(np.int32)
    lits = jnp.asarray(np.concatenate([x, 1 - x], -1))
    yj = jnp.asarray(y)
    st = init_tm(cfg, jax.random.key(0))
    key = jax.random.key(1)
    for _ in range(10):
        key, k = jax.random.split(key)
        st = train_epoch(cfg, st, k, lits, yj, batch_size=25,
                         backend="fused")
    assert evaluate(cfg, st, lits, yj) >= 0.9
