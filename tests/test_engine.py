"""Unified VoteEngine: every backend bit-exact with the oracle.

The registry's contract: for any (cfg, state) and any literal batch, all
backends return identical ``prediction`` *and* ``class_sums`` — across
non-power-of-two clause/class counts and tie cases, where the paper's
arbiter (and ``jnp.argmax``) resolve to the lowest index.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.time_domain import PDLConfig, make_device
from repro.core.tm import TMConfig, TMState, init_tm, predict
from repro.engine import (DEFAULT_BACKEND, EngineResult, available_backends,
                          engine_from_model_config, get_engine, pack_result)
from repro.engine.base import _REGISTRY

ALL_BACKENDS = available_backends()

# (C, M, F): non-power-of-two classes and clause counts, odd M (unequal
# +/− polarity halves), tiny and wide feature spaces
SHAPES = [(2, 6, 9), (3, 10, 12), (5, 7, 33), (4, 12, 5), (10, 25, 49)]


def _random_tm(c, m, f, *, density=0.15, seed=0):
    cfg = TMConfig(n_classes=c, n_clauses=m, n_features=f)
    rng = np.random.default_rng(seed)
    ta = np.where(rng.random((c, m, 2 * f)) < density,
                  cfg.n_states + 1, cfg.n_states)
    lits = rng.integers(0, 2, (17, 2 * f), dtype=np.int8)
    return cfg, TMState(ta=jnp.asarray(ta, jnp.int32)), jnp.asarray(lits)


def test_registry_has_all_paper_backends():
    assert {"oracle", "adder_tree", "swar_packed", "swar_fused",
            "sparse_csr", "mxu_fused", "time_domain"} <= set(ALL_BACKENDS)


def test_unknown_backend_raises():
    cfg, st, _ = _random_tm(2, 4, 3)
    with pytest.raises(KeyError, match="unknown VoteEngine backend"):
        get_engine("fpga", cfg, st)


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"C{s[0]}M{s[1]}F{s[2]}")
@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backend_parity_randomized(backend, shape):
    cfg, st, lits = _random_tm(*shape, seed=sum(shape))
    ref = get_engine("oracle", cfg, st).infer(lits)
    res = get_engine(backend, cfg, st).infer(lits)
    assert isinstance(res, EngineResult)
    np.testing.assert_array_equal(np.asarray(res.prediction),
                                  np.asarray(ref.prediction))
    np.testing.assert_array_equal(np.asarray(res.class_sums),
                                  np.asarray(ref.class_sums))


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backend_tie_break_lowest_index(backend):
    """Duplicate class blocks ⇒ exactly tied sums ⇒ winner is lowest index."""
    cfg, st, lits = _random_tm(4, 8, 11, seed=3)
    ta = np.array(st.ta)          # mutable copy
    ta[2] = ta[1] = ta[0]         # classes 0,1,2 identical: 3-way ties
    st = TMState(ta=jnp.asarray(ta))
    res = get_engine(backend, cfg, st).infer(lits)
    sums = np.asarray(res.class_sums)
    np.testing.assert_array_equal(sums[:, 0], sums[:, 1])
    np.testing.assert_array_equal(np.asarray(res.prediction),
                                  np.argmax(sums, -1))
    # the tied block always beats-or-ties class 3, so winner ∈ {0, 3}
    assert set(np.asarray(res.prediction).tolist()) <= {0, 3}


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_backend_matches_tm_predict_on_seeded_tm(backend):
    """Acceptance check: get_engine(name).infer == tm.predict, seeded TM."""
    cfg = TMConfig(n_classes=3, n_clauses=10, n_features=12)
    st = init_tm(cfg, jax.random.key(42))
    rng = np.random.default_rng(7)
    lits = jnp.asarray(rng.integers(0, 2, (29, 24), dtype=np.int8))
    expected = np.asarray(predict(cfg, st, lits))
    got = np.asarray(get_engine(backend, cfg, st).infer(lits).prediction)
    np.testing.assert_array_equal(got, expected)


def test_predict_backend_kwarg():
    cfg, st, lits = _random_tm(3, 9, 8, seed=5)
    base = np.asarray(predict(cfg, st, lits))
    for backend in ALL_BACKENDS:
        np.testing.assert_array_equal(
            np.asarray(predict(cfg, st, lits, backend=backend)), base)
    assert DEFAULT_BACKEND in ALL_BACKENDS


def test_time_domain_aux_and_physical_device():
    cfg, st, lits = _random_tm(4, 10, 16, seed=9)
    res = get_engine("time_domain", cfg, st).infer(lits)
    assert res.aux["latency_ps"].shape == (lits.shape[0],)
    assert res.aux["metastable"].dtype == bool
    # stronger winners finish earlier: latency anticorrelates with max sum
    best = np.asarray(res.class_sums).max(-1)
    lat = np.asarray(res.aux["latency_ps"])
    assert np.corrcoef(best, lat)[0, 1] < 0
    # a physical device (variation, no skew) agrees with the ideal
    # arbiter.  Its variation (sigma 2 ps per element) is far below one
    # vote's delay step (d_high - d_low = 233 ps), so on every row it
    # picks a class with the maximal sum; on exactly tied sums the race
    # is decided by the device's own element offsets, not by the ideal
    # arbiter's lowest index, so a tie won by any tied class agrees
    pdl = PDLConfig(sigma_elem=2.0, sigma_noise=0.0)
    dev = make_device(pdl, cfg.n_classes, cfg.n_clauses, jax.random.key(1))
    phys = get_engine("time_domain", cfg, st, pdl=pdl, device=dev).infer(lits)
    sums = np.asarray(res.class_sums)
    won = sums[np.arange(len(sums)), np.asarray(phys.prediction)]
    np.testing.assert_array_equal(won, sums.max(-1))
    unique = (sums == sums.max(-1, keepdims=True)).sum(-1) == 1
    assert unique.any()
    np.testing.assert_array_equal(np.asarray(phys.prediction)[unique],
                                  np.asarray(res.prediction)[unique])


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_shard_batch_parity(backend):
    """shard_map wrapper returns identical results, ragged batch included."""
    cfg, st, lits = _random_tm(3, 8, 10, seed=11)  # B=17: ragged on >1 dev
    ref = get_engine(backend, cfg, st).infer(lits)
    res = get_engine(backend, cfg, st, shard_batch=True).infer(lits)
    np.testing.assert_array_equal(np.asarray(res.prediction),
                                  np.asarray(ref.prediction))
    np.testing.assert_array_equal(np.asarray(res.class_sums),
                                  np.asarray(ref.class_sums))
    for k in ref.aux:
        np.testing.assert_allclose(np.asarray(res.aux[k]),
                                   np.asarray(ref.aux[k]), rtol=1e-6)


def test_shard_batch_rejects_noise_key():
    """Sharding would replicate the same jitter draw on every device."""
    cfg, st, _ = _random_tm(3, 8, 10, seed=13)
    with pytest.raises(ValueError, match="noise_key"):
        get_engine("time_domain", cfg, st, noise_key=jax.random.key(0),
                   shard_batch=True)


def test_engines_share_jit_cache():
    """Building a fresh engine per call (as tm.predict does) must hit the
    module-level jit cache, not recompile per instance."""
    import time
    cfg, st, lits = _random_tm(3, 10, 12, seed=17)
    jax.block_until_ready(get_engine("oracle", cfg, st).infer(lits))  # warm
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(get_engine("oracle", cfg, st).infer(lits))
    assert time.perf_counter() - t0 < 1.0   # recompiling would take seconds


@pytest.mark.parametrize("backend", ["sparse_csr", "swar_fused"])
@pytest.mark.parametrize("density", [0.0, 1.0],
                         ids=["all_empty_clauses", "all_include"])
def test_sparsity_backends_density_extremes(backend, density):
    """Empty clauses (fire unconditionally, oracle convention) and fully
    dense clauses are the sparse layout's boundary cases."""
    cfg, st, lits = _random_tm(3, 8, 11, density=density, seed=21)
    ref = get_engine("oracle", cfg, st).infer(lits)
    res = get_engine(backend, cfg, st).infer(lits)
    np.testing.assert_array_equal(np.asarray(res.prediction),
                                  np.asarray(ref.prediction))
    np.testing.assert_array_equal(np.asarray(res.class_sums),
                                  np.asarray(ref.class_sums))


def test_sparse_ell_layout():
    from repro.engine.sparse import ell_from_include
    inc = jnp.asarray([[1, 0, 1, 0, 0],
                       [0, 0, 0, 0, 0],
                       [1, 1, 1, 1, 1]], jnp.int8)
    ell = ell_from_include(inc)
    assert ell.k_max == 5 and ell.n_literals == 5
    assert np.asarray(ell.nnz).tolist() == [2, 0, 5]
    idx = np.asarray(ell.indices)
    assert idx[0].tolist() == [0, 2, 5, 5, 5]   # padding → sentinel L
    assert idx[1].tolist() == [5] * 5
    assert idx[2].tolist() == [0, 1, 2, 3, 4]
    assert 0.0 < ell.density <= 1.0


def test_engine_cache_hit_is_free():
    """Acceptance: the second get_engine with identical (cfg, state,
    backend) returns the cached engine — build cost ≈ 0, same object."""
    import time
    from repro.engine import clear_engine_cache, engine_cache_info
    clear_engine_cache()
    cfg, st, lits = _random_tm(3, 10, 12, seed=23)
    e1 = get_engine("sparse_csr", cfg, st)
    t0 = time.perf_counter()
    e2 = get_engine("sparse_csr", cfg, st)
    build_ms = (time.perf_counter() - t0) * 1e3
    assert e2 is e1
    assert build_ms < 5.0, build_ms          # dict lookup, not a rebuild
    assert engine_cache_info()["hits"] >= 1
    # a state with identical values but different arrays must NOT hit
    st2 = type(st)(ta=jnp.asarray(np.asarray(st.ta)))
    assert get_engine("sparse_csr", cfg, st2) is not e1
    # cache=False always builds fresh
    assert get_engine("sparse_csr", cfg, st, cache=False) is not e1
    # unhashable opts (arrays) silently bypass the cache
    eng = get_engine("time_domain", cfg, st,
                     noise_key=jax.random.key(0))
    assert eng.infer(lits).prediction.shape == (lits.shape[0],)


def test_engine_cache_evicts_dead_states():
    """Entries hold weakrefs: dropping a state frees its cache slot (no
    retention of retired states in training-eval loops)."""
    import gc
    from repro.engine import clear_engine_cache, engine_cache_info
    clear_engine_cache()
    cfg, st, _ = _random_tm(2, 4, 3, seed=200)
    get_engine("oracle", cfg, st)
    assert engine_cache_info()["size"] == 1
    del st
    gc.collect()
    assert engine_cache_info()["size"] == 0


def test_engine_cache_lru_bounded():
    from repro.engine import clear_engine_cache, engine_cache_info
    from repro.engine.base import ENGINE_CACHE_SIZE
    clear_engine_cache()
    for seed in range(ENGINE_CACHE_SIZE + 4):
        cfg, st, _ = _random_tm(2, 4, 3, seed=100 + seed)
        get_engine("oracle", cfg, st)
    assert engine_cache_info()["size"] <= ENGINE_CACHE_SIZE


def test_autotune_lookup_applied(tmp_path, monkeypatch):
    """get_engine picks tuned tiles from the JSON cache; explicit opts win."""
    import json
    from repro.engine import autotune, clear_engine_cache
    clear_engine_cache()
    cfg, st, lits = _random_tm(3, 10, 12, seed=29)
    key = autotune.shape_key("swar_fused", cfg)
    cache = {"best": {key: {"block_b": 16, "block_cm": 64,
                            "stale_opt": 1}}}
    path = tmp_path / "autotune.json"
    path.write_text(json.dumps(cache))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    assert autotune.lookup("swar_fused", cfg) == {"block_b": 16,
                                                 "block_cm": 64}
    eng = get_engine("swar_fused", cfg, st, cache=False)
    assert eng._blocks == (16, 64)
    eng = get_engine("swar_fused", cfg, st, cache=False, block_b=8)
    assert eng._blocks == (8, 64)
    # untuned backend / missing file → defaults, no error
    assert autotune.lookup("oracle", cfg) == {}
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "none.json"))
    assert autotune.lookup("swar_fused", cfg) == {}
    ref = get_engine("oracle", cfg, st).infer(lits)
    res = eng.infer(lits)
    np.testing.assert_array_equal(np.asarray(res.prediction),
                                  np.asarray(ref.prediction))


def test_donate_literals_wrapper():
    cfg, st, _ = _random_tm(3, 9, 8, seed=31)
    rng = np.random.default_rng(0)
    lits_np = rng.integers(0, 2, (12, 16), dtype=np.int8)
    ref = get_engine("oracle", cfg, st).infer(jnp.asarray(lits_np))
    eng = get_engine("oracle", cfg, st, donate_literals=True)
    assert eng.name == "oracle+donate"
    # fresh device buffer per call: donation must not need caller reuse
    res = eng.infer(jnp.asarray(lits_np))
    np.testing.assert_array_equal(np.asarray(res.prediction),
                                  np.asarray(ref.prediction))


def test_engine_from_model_config():
    from repro.configs import get_config
    mcfg = get_config("tm-iris-10")
    cfg = TMConfig(n_classes=3, n_clauses=10, n_features=12, T=5, s=1.5)
    st = init_tm(cfg, jax.random.key(0))
    eng = engine_from_model_config(mcfg, st)
    assert eng.name == mcfg.backend
    rng = np.random.default_rng(2)
    lits = jnp.asarray(rng.integers(0, 2, (8, 24), dtype=np.int8))
    np.testing.assert_array_equal(np.asarray(eng.infer(lits).prediction),
                                  np.asarray(predict(cfg, st, lits)))


# -- packed form: the whole result as one (B, 1 + C) int32 array ---------

PACKED_BACKENDS = [b for b in ALL_BACKENDS
                   if hasattr(_REGISTRY[b], "infer_packed")]
SERVED_BUCKETS = (1, 2, 4, 8, 16, 32, 64)     # default_buckets(64)


def _prototype_tm(c, m, f, *, density=0.05, noise=0.02, rows=64, seed=0):
    """A trained-looking machine: every clause includes the same number of
    features, with its class prototype's polarity (even clauses) or
    another class's (odd clauses); inputs are prototypes with ``noise``
    of their bits flipped."""
    cfg = TMConfig(n_classes=c, n_clauses=m, n_features=f)
    rng = np.random.default_rng(seed)
    proto = rng.random((c, f)) < 0.5
    k = max(1, round(2 * density * f))
    feat = np.argsort(rng.random((c, m, f)), axis=-1).argsort(axis=-1) < k
    clause = np.arange(m)
    other = (np.arange(c)[:, None] + 1 + (clause[None, :] // 2)
             % max(c - 1, 1)) % c
    target = np.where(clause[None, :] % 2 == 0, np.arange(c)[:, None], other)
    bits = proto[target]                                    # (C, M, F)
    inc = np.concatenate([feat & bits, feat & ~bits], axis=-1)
    ta = np.where(inc, cfg.n_states + 1, cfg.n_states)
    x = proto[rng.integers(0, c, rows)] ^ (rng.random((rows, f)) < noise)
    lits = np.concatenate([x, ~x], axis=-1).astype(np.int8)
    return cfg, TMState(ta=jnp.asarray(ta, jnp.int32)), lits


def _assert_packed_matches_infer(eng, lits):
    for bucket in SERVED_BUCKETS:
        x = jnp.asarray(lits[:bucket])
        ref = eng.infer(x)
        buf = eng.infer_packed(x)
        c = ref.class_sums.shape[1]
        assert buf.shape == (bucket, 1 + c) and buf.dtype == jnp.int32
        host = np.asarray(buf)
        for have, want in ((host[:, 0], ref.prediction),
                           (host[:, 1:], ref.class_sums)):
            want = np.asarray(want)
            assert have.dtype == want.dtype, bucket
            np.testing.assert_array_equal(have, want, err_msg=f"{bucket}")


def test_packed_backends_are_the_int32_ones():
    assert {"oracle", "adder_tree", "swar_packed", "swar_fused",
            "sparse_csr", "mxu_fused"} <= set(PACKED_BACKENDS)
    assert "time_domain" not in PACKED_BACKENDS


@pytest.mark.parametrize("backend", PACKED_BACKENDS)
def test_packed_form_equals_infer_at_every_served_bucket(backend):
    """Bit for bit: column 0 is ``infer``'s prediction, columns 1..C its
    class sums, at each bucket a server compiles (random machine)."""
    cfg, st, _ = _random_tm(5, 7, 33, seed=23)
    rng = np.random.default_rng(23)
    lits = rng.integers(0, 2, (64, cfg.n_literals), dtype=np.int8)
    _assert_packed_matches_infer(get_engine(backend, cfg, st), lits)


def test_sparse_csr_packed_form_on_the_prototype_machine():
    """A machine shaped as the bulk cell's (C=10, M=50, F=784, every
    clause including 78 literals): every bucket bit-exact."""
    cfg, st, lits = _prototype_tm(10, 50, 784, seed=31)
    eng = get_engine("sparse_csr", cfg, st)
    assert eng.ell.k_max == round(2 * 0.05 * 784)
    _assert_packed_matches_infer(eng, lits)


def test_pack_result_refuses_aux_and_mixed_dtypes():
    cfg, st, lits = _random_tm(3, 6, 5, seed=29)
    res = get_engine("time_domain", cfg, st).infer(lits)
    with pytest.raises(ValueError, match="aux"):
        pack_result(res)
    with pytest.raises(TypeError, match="one array"):
        pack_result(EngineResult(res.prediction,
                                 res.class_sums.astype(jnp.float32), {}))
    packed = pack_result(EngineResult(res.prediction, res.class_sums, {}))
    np.testing.assert_array_equal(np.asarray(packed[:, 0]),
                                  np.asarray(res.prediction))
