"""Clause-indexed sparse training + incremental ELL refresh.

Three contracts from this layer:

1. layout — the vectorized ``ell_from_include`` matches the per-row-loop
   oracle exactly, and a delta-patched layout (``ell_apply_deltas`` /
   ``IncrementalEll.refresh``) is bitwise identical to a from-scratch
   build at the same K, across overflow and drift-rebuild boundaries;
2. training — the ``sparse`` TrainEngine is delta-exact against
   ``reference`` over multi-step online chains (the single-step parity
   and density/polarity edge cases run in ``test_train_engine.py``,
   where ``sparse`` auto-joins ``ALL_TRAIN_BACKENDS``), including under
   a ``lax.scan`` trace (the packed fallback);
3. serving — ``TMServer`` re-resolves density-heuristic routes on every
   state publish (the stale-routing regression: on the pre-fix server
   the route table froze at the initial state's density), keeps its
   incremental serving layout equal to a from-scratch build after N
   publishes, and evicts the superseded state's engines from the keyed
   cache.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.tm import TMConfig, TMState
from repro.core.tm_train import train_epoch
from repro.engine import (available_train_backends, clear_engine_cache,
                          engine_cache_info, get_engine, get_train_engine)
from repro.engine.base import KeyedEngineCache
from repro.engine.sparse import (IncrementalEll, ell_apply_deltas,
                                 ell_from_include)
from repro.engine.train import train_engine_opts
from repro.serve.tm_server import ServePolicy, TMServer


def _loop_ell(inc: np.ndarray, k: int) -> tuple[np.ndarray, np.ndarray]:
    """The per-row-loop oracle the vectorized build replaced."""
    r, l = inc.shape
    idx = np.full((r, k), l, np.int32)
    for i in range(r):
        nz = np.nonzero(inc[i])[0]
        idx[i, :len(nz)] = nz
    return idx, inc.sum(axis=1).astype(np.int32)


def _drifting_tm(c=3, m=8, f=12, *, density=0.15, seed=0, batch=16):
    cfg = TMConfig(n_classes=c, n_clauses=m, n_features=f, T=5, s=3.9)
    rng = np.random.default_rng(seed)
    # included TAs sit just above N and excluded just below 1+N margin,
    # so feedback flips include bits readily — maximal layout churn
    ta = np.where(rng.random((c, m, 2 * f)) < density,
                  cfg.n_states + 1, cfg.n_states)
    st = TMState(ta=jnp.asarray(ta, jnp.int32))
    lits = jnp.asarray(rng.integers(0, 2, (batch, 2 * f), dtype=np.int8))
    y = jnp.asarray(rng.integers(0, c, (batch,), dtype=np.int32))
    return cfg, st, lits, y


# -- layout: vectorized build == loop oracle --------------------------


def test_ell_from_include_matches_loop_on_random_masks():
    rng = np.random.default_rng(0)
    for trial in range(50):
        r = int(rng.integers(1, 40))
        l = int(rng.integers(1, 64))
        inc = rng.random((r, l)) < rng.random()
        lay = ell_from_include(inc)
        idx, nnz = _loop_ell(inc, lay.k_max)
        np.testing.assert_array_equal(np.asarray(lay.indices), idx,
                                      err_msg=f"trial {trial}")
        np.testing.assert_array_equal(np.asarray(lay.nnz), nnz)
        assert lay.n_literals == l


def test_ell_from_include_k_override_and_validation():
    inc = np.array([[1, 0, 1, 0], [0, 0, 0, 0]], bool)
    lay = ell_from_include(inc, k=3)
    np.testing.assert_array_equal(np.asarray(lay.indices),
                                  [[0, 2, 4], [4, 4, 4]])
    # k above L pads pure sentinel columns
    wide = ell_from_include(inc, k=6)
    assert np.asarray(wide.indices).shape == (2, 6)
    assert (np.asarray(wide.indices)[:, 4:] == 4).all()
    with pytest.raises(ValueError, match="below the max"):
        ell_from_include(inc, k=1)


def test_ell_from_include_empty_rows_and_zero_k():
    lay = ell_from_include(np.zeros((5, 7), bool))
    assert lay.k_max == 0 and lay.density == 0.0
    np.testing.assert_array_equal(np.asarray(lay.nnz), np.zeros(5))


# -- layout: delta patch == from-scratch ------------------------------


def test_ell_apply_deltas_matches_fresh_build():
    rng = np.random.default_rng(1)
    inc = rng.random((24, 32)) < 0.2
    lay = ell_from_include(inc, k=12)
    idx = np.asarray(lay.indices).copy()
    nnz = np.asarray(lay.nnz).copy()
    new = inc.copy()
    rows = np.array([0, 3, 17])
    new[rows] = rng.random((3, 32)) < 0.2
    assert ell_apply_deltas(idx, nnz, new, rows)
    fresh = ell_from_include(new, k=12)
    np.testing.assert_array_equal(idx, np.asarray(fresh.indices))
    np.testing.assert_array_equal(nnz, np.asarray(fresh.nnz))


def test_ell_apply_deltas_overflow_refuses_without_writing():
    inc = np.zeros((4, 16), bool)
    inc[1, :3] = True
    lay = ell_from_include(inc)                  # K = 3
    idx = np.asarray(lay.indices).copy()
    nnz = np.asarray(lay.nnz).copy()
    before = idx.copy()
    new = inc.copy()
    new[2, :5] = True                            # nnz 5 > K 3
    assert not ell_apply_deltas(idx, nnz, new, np.array([2]))
    np.testing.assert_array_equal(idx, before)   # nothing written


def test_incremental_refresh_equals_from_scratch_soak():
    rng = np.random.default_rng(2)
    inc = rng.random((48, 40)) < 0.1
    ell = IncrementalEll(inc, k_slack=8)
    for t in range(60):
        flip = rng.random(inc.shape) < rng.choice([0.001, 0.01, 0.08])
        inc = inc ^ flip
        lay = ell.refresh(inc)
        fresh = ell_from_include(inc, k=lay.k_max)
        np.testing.assert_array_equal(np.asarray(lay.indices),
                                      np.asarray(fresh.indices),
                                      err_msg=f"step {t}")
        np.testing.assert_array_equal(np.asarray(lay.nnz),
                                      np.asarray(fresh.nnz))
    stats = ell.stats()
    assert stats["patches"] > 0 and stats["rebuilds"] >= 1
    assert stats["rows"] == 48


def test_incremental_k_overflow_triggers_rebuild():
    inc = np.zeros((16, 64), bool)
    inc[:, 0] = True
    ell = IncrementalEll(inc, k_slack=0)
    k0 = ell.layout.k_max                        # quantized alloc (8)
    assert k0 == 8
    new = inc.copy()
    new[3, :k0 + 1] = True                       # overflows the alloc
    lay = ell.refresh(new)
    assert ell.rebuilds == 2                     # initial + overflow
    assert lay.k_max >= k0 + 1
    fresh = ell_from_include(new, k=lay.k_max)
    np.testing.assert_array_equal(np.asarray(lay.indices),
                                  np.asarray(fresh.indices))


def test_incremental_drift_threshold_triggers_rebuild():
    rng = np.random.default_rng(3)
    inc = rng.random((40, 24)) < 0.3
    ell = IncrementalEll(inc, rebuild_threshold=0.25)
    new = inc.copy()
    new[:15] = rng.random((15, 24)) < 0.3        # 37% of rows drift
    ell.refresh(new)
    assert ell.rebuilds == 2


def test_incremental_noop_and_shape_change():
    inc = np.eye(6, 10, dtype=bool)
    ell = IncrementalEll(inc)
    lay0 = ell.refresh(inc)                      # nothing flipped
    assert lay0 is ell.layout and ell.patches == 0
    lay1 = ell.refresh(np.eye(8, 10, dtype=bool))
    assert lay1.indices.shape[0] == 8 and ell.rebuilds == 2


def test_incremental_validation():
    with pytest.raises(ValueError, match="k_slack"):
        IncrementalEll(np.zeros((2, 4), bool), k_slack=-1)
    with pytest.raises(ValueError, match="rebuild_threshold"):
        IncrementalEll(np.zeros((2, 4), bool), rebuild_threshold=1.5)


# -- training: sparse backend ----------------------------------------


def test_sparse_backend_registered_with_opts():
    assert "sparse" in available_train_backends()
    cfg = TMConfig(n_classes=2, n_clauses=4, n_features=6)
    eng = get_train_engine("sparse", cfg, cache=False, k_slack=16,
                           rebuild_threshold=0.5)
    opts = train_engine_opts(eng)
    assert opts["k_slack"] == 16 and opts["rebuild_threshold"] == 0.5
    assert eng.layout_stats() is None            # no concrete step yet


def test_sparse_online_chain_exact_vs_reference():
    """Multi-step chain: the engine's incremental layout must track the
    drifting state exactly or votes (and hence deltas) diverge."""
    cfg, st, lits, y = _drifting_tm(seed=7)
    ref = get_train_engine("reference", cfg, cache=False)
    sp = get_train_engine("sparse", cfg, cache=False, k_slack=0)
    s_ref, s_sp = st, st
    for i in range(20):
        k = jax.random.fold_in(jax.random.key(5), i)
        s_ref = ref.step(s_ref, k, lits, y)
        s_sp = sp.step(s_sp, k, lits, y)
        np.testing.assert_array_equal(np.asarray(s_ref.ta),
                                      np.asarray(s_sp.ta),
                                      err_msg=f"diverged at step {i}")
    stats = sp.layout_stats()
    assert stats is not None and stats["rebuilds"] >= 1
    # after syncing to the final state (the layout tracks each step's
    # *input*), the incremental layout equals a from-scratch build
    sp._refresh(s_sp)
    inc = (np.asarray(s_sp.ta) > cfg.n_states).reshape(
        cfg.n_classes * cfg.n_clauses, cfg.n_literals)
    fresh = ell_from_include(inc, k=sp._ell.layout.k_max)
    np.testing.assert_array_equal(np.asarray(sp._ell.layout.indices),
                                  np.asarray(fresh.indices))


def test_sparse_exact_across_kslack_and_thresholds():
    """Refresh policy knobs change *when* rebuilds happen, never the
    layout contents — so the trained state is invariant to them."""
    cfg, st, lits, y = _drifting_tm(seed=11)
    key = jax.random.key(3)
    ref = get_train_engine("reference", cfg, cache=False)
    s_ref = st
    for i in range(6):
        s_ref = ref.step(s_ref, jax.random.fold_in(key, i), lits, y)
    for k_slack, thr in [(0, 0.0), (8, 0.25), (32, 1.0)]:
        sp = get_train_engine("sparse", cfg, cache=False, k_slack=k_slack,
                              rebuild_threshold=thr)
        s_sp = st
        for i in range(6):
            s_sp = sp.step(s_sp, jax.random.fold_in(key, i), lits, y)
        np.testing.assert_array_equal(np.asarray(s_ref.ta),
                                      np.asarray(s_sp.ta),
                                      err_msg=f"k_slack={k_slack} thr={thr}")


def test_sparse_under_scan_tracer_fallback():
    """``train_epoch`` scans the step under a trace where the host-side
    layout refresh is impossible — the fallback must stay delta-exact."""
    cfg, st, lits, y = _drifting_tm(batch=48, seed=13)
    key = jax.random.key(9)
    ref = train_epoch(cfg, st, key, lits, y, batch_size=16)
    got = train_epoch(cfg, st, key, lits, y, batch_size=16,
                      backend="sparse")
    np.testing.assert_array_equal(np.asarray(ref.ta), np.asarray(got.ta))


# -- engine cache: superseded-state eviction --------------------------


def test_keyed_cache_evict_state():
    cache = KeyedEngineCache(maxsize=4)
    a = np.arange(3.0)
    b = np.arange(4.0)
    cache.insert(("ka",), (a,), "engine-a")
    cache.insert(("kb",), (b,), "engine-b")
    assert cache.evict_state((a,)) == 1
    assert cache.get(("ka",)) is None
    assert cache.get(("kb",)) == "engine-b"
    info = cache.info()
    assert info["superseded"] == 1 and info["evictions"] == 0
    assert cache.evict_state((a,)) == 0          # already gone


def test_server_publish_evicts_superseded_engines():
    cfg, st, lits, y = _drifting_tm(seed=17)

    async def go():
        clear_engine_cache()
        srv = TMServer(cfg, st, ServePolicy(max_batch=16, max_wait_us=0),
                       train_backend="packed")
        async with srv:
            await srv.submit(np.asarray(lits))   # caches v0's engine
            before = engine_cache_info()["superseded"]
            await srv.submit_labeled(np.asarray(lits), np.asarray(y))
            return before, engine_cache_info()["superseded"]

    before, after = asyncio.run(go())
    assert after > before


# -- serving: the stale-routing regression ----------------------------


def _density_drift_server(train_backend="sparse"):
    """A server whose density starts above the 0.10 heuristic boundary
    (routes dense) and whose include TAs sit one decrement from
    exclusion, so all-zero-literal feedback drives density down fast."""
    rng = np.random.default_rng(23)
    cfg = TMConfig(n_classes=4, n_clauses=8, n_features=16)
    inc = rng.random((cfg.n_classes, cfg.n_clauses, cfg.n_literals)) < 0.2
    ta = np.where(inc, cfg.n_states + 1, 1).astype(np.int32)
    state = TMState(ta=jnp.asarray(ta))
    srv = TMServer(cfg, state, ServePolicy(max_batch=16, max_wait_us=0),
                   train_backend=train_backend)
    return cfg, srv


def test_routes_flip_when_density_crosses_heuristic_boundary():
    """The headline regression: before the fix, ``TMServer`` resolved
    density-heuristic routes once from the initial state, so a model
    drifting across the 0.10 boundary kept serving the dense backend
    forever.  Now each publish re-resolves — and predictions stay
    bit-exact against the oracle on the post-drift state."""
    cfg, srv = _density_drift_server()
    rng = np.random.default_rng(29)
    x = rng.integers(0, 2, (8, cfg.n_literals)).astype(np.int8)
    zeros = np.zeros((16, cfg.n_literals), np.int8)

    async def go():
        async with srv:
            assert set(srv.routing.values()) == {"swar_packed"}
            for i in range(50):
                await srv.submit_labeled(
                    zeros, np.full(16, i % cfg.n_classes, np.int32))
                if set(srv.routing.values()) == {"sparse_csr"}:
                    break
            else:
                pytest.fail("density crossed the boundary but routes "
                            "never re-resolved (stale-routing bug)")
            density = float(np.asarray(
                srv.state.ta > cfg.n_states).mean())
            assert density <= 0.10               # the flip was *earned*
            res = await srv.submit(x)
            oracle = get_engine("oracle", cfg, srv.state, cache=False)
            np.testing.assert_array_equal(
                np.asarray(res.prediction),
                np.asarray(oracle.infer(jnp.asarray(x)).prediction))
            st = srv.stats()
            assert st["routing_updates"] >= 1
            assert st["sparse_layout"] is not None

    asyncio.run(go())


def test_explicit_routing_and_backend_stay_pinned():
    """Explicit route tables and ``policy.backend`` must NOT re-resolve
    — operators pinned them on purpose."""
    rng = np.random.default_rng(31)
    cfg = TMConfig(n_classes=3, n_clauses=6, n_features=10)
    inc = rng.random((cfg.n_classes, cfg.n_clauses, cfg.n_literals)) < 0.2
    ta = np.where(inc, cfg.n_states + 1, 1).astype(np.int32)
    state = TMState(ta=jnp.asarray(ta))
    zeros = np.zeros((8, cfg.n_literals), np.int8)

    async def go(policy, **kw):
        srv = TMServer(cfg, state, policy, train_backend="packed", **kw)
        async with srv:
            routes0 = dict(srv.routing)
            for i in range(30):
                await srv.submit_labeled(zeros, np.full(8, i % 3, np.int32))
            assert srv.routing == routes0
            assert srv.stats()["routing_updates"] == 0

    quick = ServePolicy(max_batch=8, max_wait_us=0)
    asyncio.run(go(quick, routing={b: "oracle"
                                   for b in quick.resolved_buckets()}))
    asyncio.run(go(ServePolicy(max_batch=8, max_wait_us=0,
                               backend="swar_packed")))


def test_serving_layout_matches_from_scratch_after_publishes():
    """Online-learning soak: after N publishes the server's incremental
    serving layout is bitwise identical to ``ell_from_include`` of the
    live state — refresh never accumulates drift — and the prebuilt
    engine it feeds still predicts bit-exactly.  The ``sparse_csr``
    route is pinned so the layout is maintained on every publish
    regardless of where density drifts."""
    rng = np.random.default_rng(37)
    cfg = TMConfig(n_classes=4, n_clauses=8, n_features=16)
    inc0 = rng.random((cfg.n_classes, cfg.n_clauses,
                       cfg.n_literals)) < 0.08
    ta = np.where(inc0, cfg.n_states + 1, cfg.n_states).astype(np.int32)
    srv = TMServer(cfg, TMState(ta=jnp.asarray(ta)),
                   ServePolicy(max_batch=16, max_wait_us=0,
                               backend="sparse_csr"),
                   train_backend="sparse")
    x = rng.integers(0, 2, (8, cfg.n_literals)).astype(np.int8)

    async def go():
        async with srv:
            for _ in range(25):
                lits = rng.integers(0, 2, (16, cfg.n_literals)).astype(
                    np.int8)
                await srv.submit_labeled(
                    lits, rng.integers(0, cfg.n_classes, 16).astype(
                        np.int32))
            ell = srv._serve_ell
            assert ell is not None
            inc = np.asarray(srv.state.ta > cfg.n_states).reshape(
                cfg.n_classes * cfg.n_clauses, cfg.n_literals)
            fresh = ell_from_include(inc, k=ell.layout.k_max)
            np.testing.assert_array_equal(np.asarray(ell.layout.indices),
                                          np.asarray(fresh.indices))
            np.testing.assert_array_equal(np.asarray(ell.layout.nnz),
                                          np.asarray(fresh.nnz))
            stats = srv.stats()["sparse_layout"]
            assert stats["rebuilds"] + stats["patches"] >= 1
            res = await srv.submit(x)
            oracle = get_engine("oracle", cfg, srv.state, cache=False)
            np.testing.assert_array_equal(
                np.asarray(res.prediction),
                np.asarray(oracle.infer(jnp.asarray(x)).prediction))

    asyncio.run(go())
