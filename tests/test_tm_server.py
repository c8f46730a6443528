"""End-to-end property tests for the TM micro-batching scheduler.

The serving contract, under randomized arrival orders, request sizes,
batching policies, and bucket configurations:

- **exactly once** — every submitted request resolves exactly one future;
- **in order per client** — a client that awaits its requests
  sequentially observes completions in its submission order;
- **bit-exact** — each response equals a direct, unbatched oracle
  ``infer`` on that request's own literals (predictions *and* class
  sums), no matter how the scheduler coalesced, padded, or routed it.

Degenerate configurations are covered explicitly: ``max_batch=1`` (every
request its own batch), a single bucket, and oversized requests that
exceed the largest bucket.  Runs under real hypothesis or the seeded
fallback shim.
"""

import asyncio

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.tm import TMConfig, TMState
from repro.engine import get_engine
from repro.serve import (ServePolicy, TMServer, bucket_for, default_buckets,
                         route_buckets)

C, M, F = 3, 7, 9       # non-power-of-two shape, cheap enough per example
N_CLIENTS = 3


def _tm(seed=0, density=0.2):
    cfg = TMConfig(n_classes=C, n_clauses=M, n_features=F)
    rng = np.random.default_rng(seed)
    ta = np.where(rng.random((C, M, cfg.n_literals)) < density,
                  cfg.n_states + 1, cfg.n_states)
    return cfg, TMState(ta=jnp.asarray(ta, jnp.int32))


def _requests(cfg, sizes, seed):
    """Round-robin the request stream over N_CLIENTS clients.
    → list of (client, seq_within_client, literals)."""
    rng = np.random.default_rng(seed)
    reqs, seqs = [], [0] * N_CLIENTS
    for i, n in enumerate(sizes):
        client = i % N_CLIENTS
        lits = rng.integers(0, 2, (n, cfg.n_literals), dtype=np.int8)
        reqs.append((client, seqs[client], lits))
        seqs[client] += 1
    return reqs


def _serve_all(cfg, state, policy, reqs):
    """Submit every request concurrently; → (results, completion order)."""
    completions = []

    async def go():
        async with TMServer(cfg, state, policy) as server:
            async def one(client, seq, lits):
                res = await server.submit(lits, client=client)
                completions.append((client, seq))
                return res

            results = await asyncio.gather(
                *[one(c, s, l) for c, s, l in reqs])
            stats = server.stats()
        return results, stats

    results, stats = asyncio.run(go())
    return results, completions, stats


def _check_contract(cfg, state, reqs, results, completions):
    oracle = get_engine("oracle", cfg, state)
    # exactly once: one result per request, one completion per request
    assert len(results) == len(reqs)
    assert len(completions) == len(set(completions)) == len(reqs)
    # in order per client
    for client in range(N_CLIENTS):
        seqs = [s for c, s in completions if c == client]
        assert seqs == sorted(seqs), f"client {client} reordered: {seqs}"
    # bit-exact vs direct unbatched oracle infer per request
    for (client, seq, lits), res in zip(reqs, results):
        ref = oracle.infer(jnp.asarray(lits))
        assert np.asarray(res.prediction).shape == (len(lits),)
        np.testing.assert_array_equal(np.asarray(res.prediction),
                                      np.asarray(ref.prediction))
        np.testing.assert_array_equal(np.asarray(res.class_sums),
                                      np.asarray(ref.class_sums))


@settings(max_examples=8, deadline=None)
@given(sizes=st.lists(st.integers(min_value=1, max_value=5),
                      min_size=1, max_size=20),
       max_batch=st.sampled_from((1, 2, 4, 8, 16)),
       max_wait_us=st.sampled_from((0, 200, 2000)),
       buckets=st.sampled_from((None, (8,), (1, 4, 16))),
       backend=st.sampled_from(("oracle", "swar_packed")),
       seed=st.integers(min_value=0, max_value=2**16))
def test_scheduler_contract_randomized(sizes, max_batch, max_wait_us,
                                       buckets, backend, seed):
    cfg, state = _tm(seed=5)
    policy = ServePolicy(max_batch=max_batch, max_wait_us=max_wait_us,
                         buckets=buckets, backend=backend)
    reqs = _requests(cfg, sizes, seed)
    results, completions, stats = _serve_all(cfg, state, policy, reqs)
    _check_contract(cfg, state, reqs, results, completions)
    assert stats["requests"] == len(reqs)
    assert stats["rows"] == sum(sizes)


def test_max_batch_one_degenerates_to_sequential():
    """max_batch=1: every request is its own batch, contract still holds."""
    cfg, state = _tm(seed=1)
    reqs = _requests(cfg, [1, 2, 1, 3, 1, 1, 2], seed=2)
    results, completions, stats = _serve_all(
        cfg, state, ServePolicy(max_batch=1, backend="oracle"), reqs)
    _check_contract(cfg, state, reqs, results, completions)
    # single-sample requests can't coalesce past a 1-row budget: the
    # 1-row requests each formed their own batch
    assert stats["batches"] >= len(reqs)


def test_single_bucket_and_oversized_requests():
    """One configured bucket: everything pads to it; requests larger than
    the bucket round up to a multiple of it instead of failing."""
    cfg, state = _tm(seed=3)
    sizes = [1, 3, 8, 2, 10, 1]          # 10 > the only bucket (8)
    reqs = _requests(cfg, sizes, seed=4)
    policy = ServePolicy(max_batch=16, max_wait_us=500, buckets=(8,),
                         backend="oracle")
    results, completions, stats = _serve_all(cfg, state, policy, reqs)
    _check_contract(cfg, state, reqs, results, completions)
    assert stats["rows"] == sum(sizes)


def test_bucket_for_rounding():
    buckets = (1, 4, 16)
    assert bucket_for(1, buckets) == 1
    assert bucket_for(3, buckets) == 4
    assert bucket_for(16, buckets) == 16
    assert bucket_for(17, buckets) == 32        # multiple of the largest
    assert bucket_for(33, buckets) == 48
    assert default_buckets(64) == (1, 2, 4, 8, 16, 32, 64)
    assert default_buckets(48) == (1, 2, 4, 8, 16, 32, 48)
    assert default_buckets(1) == (1,)


def test_routing_explicit_and_heuristic():
    cfg, state = _tm(seed=6, density=0.05)      # trained-like: sparse
    buckets = (1, 8)
    assert route_buckets(cfg, state, buckets, backend="mxu_fused") == \
        {1: "mxu_fused", 8: "mxu_fused"}
    sparse = route_buckets(cfg, state, buckets)
    assert set(sparse.values()) <= {"sparse_csr"}
    cfg2, dense = _tm(seed=6, density=0.5)
    assert set(route_buckets(cfg2, dense, buckets).values()) <= \
        {"swar_packed"}


def test_measured_routing_overrides_heuristic(tmp_path, monkeypatch):
    """serve_bench --update-routing style entries win over the density
    heuristic, per bucket, keyed to this device kind."""
    from repro.engine import autotune
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    cfg, state = _tm(seed=8, density=0.05)
    autotune.record_serve_routing(cfg, {8: "adder_tree",
                                        1: "renamed_backend"})
    routes = route_buckets(cfg, state, (1, 8))
    assert routes[8] == "adder_tree"            # measured
    # stale entry naming an unregistered backend → heuristic fallback
    assert routes[1] == "sparse_csr"


def test_submit_validation_and_lifecycle():
    cfg, state = _tm(seed=7)

    async def go():
        server = TMServer(cfg, state, ServePolicy(max_batch=4,
                                                  backend="oracle"))
        with pytest.raises(RuntimeError, match="already started"):
            async with server:
                await server.start()
        # after stop: reject new work
        with pytest.raises(RuntimeError, match="stopped"):
            await server.submit(np.zeros(cfg.n_literals, np.int8))
        # second stop is a no-op
        await server.stop()

        async with TMServer(cfg, state,
                            ServePolicy(max_batch=4,
                                        backend="oracle")) as srv:
            with pytest.raises(ValueError, match="expected"):
                await srv.submit(np.zeros((2, 5), np.int8))
            # 1-D input promotes to a single-sample request
            res = await srv.submit(np.zeros(cfg.n_literals, np.int8))
            assert np.asarray(res.prediction).shape == (1,)

    asyncio.run(go())


def test_failing_batch_fails_only_its_requests():
    """An engine error (here: a bucket routed to a nonexistent backend)
    surfaces on that batch's futures; the scheduler survives and keeps
    serving buckets whose engines work."""
    cfg, state = _tm(seed=12)
    policy = ServePolicy(max_batch=4, max_wait_us=0, buckets=(1, 4))
    routing = {1: "bogus_backend", 4: "oracle"}

    async def go():
        async with TMServer(cfg, state, policy, routing=routing) as server:
            with pytest.raises(KeyError, match="unknown VoteEngine"):
                await server.submit(np.zeros((1, cfg.n_literals), np.int8))
            res = await server.submit(
                np.zeros((4, cfg.n_literals), np.int8))
            assert np.asarray(res.prediction).shape == (4,)
            assert server.stats()["errors"] == 1

    asyncio.run(go())


def test_warmup_and_stats_shape():
    cfg, state = _tm(seed=9)

    async def go():
        async with TMServer(cfg, state,
                            ServePolicy(max_batch=8,
                                        backend="oracle")) as server:
            await server.warmup()
            await server.submit(np.zeros((3, cfg.n_literals), np.int8))
            s = server.stats()
            for key in ("requests", "rows", "batches", "qdepth",
                        "mean_batch_rows", "batch_fill", "p50_ms",
                        "p99_ms", "routing"):
                assert key in s, key
            assert s["requests"] == 1 and s["rows"] == 3
            assert 0 < s["batch_fill"] <= 1
            assert s["qdepth"] == 0

    asyncio.run(go())


def test_queue_wait_counts_each_dispatched_request():
    """``stats()["queue_wait"]`` counts every dispatched request once,
    and a request's wait to dispatch is part of its latency."""
    cfg, state = _tm(seed=13)
    policy = ServePolicy(max_batch=4, max_wait_us=2000, backend="oracle")
    rng = np.random.default_rng(13)
    sizes = [1, 3, 2, 1, 4, 2, 1, 1, 3, 2, 1, 2]

    async def go():
        async with TMServer(cfg, state, policy) as server:
            assert server.stats()["queue_wait"] == {
                "requests": 0, "total_ms": 0.0, "mean_ms": 0.0}
            await asyncio.gather(*[
                server.submit(rng.integers(0, 2, (n, cfg.n_literals),
                                           dtype=np.int8))
                for n in sizes])
            return server.stats()

    s = asyncio.run(go())
    wait = s["queue_wait"]
    assert wait["requests"] == s["requests"] == len(sizes)
    assert s["batches"] < len(sizes)           # requests were coalesced
    assert wait["mean_ms"] == pytest.approx(wait["total_ms"] / len(sizes))
    # every request waited, and less than its own latency; with at most
    # 100 latencies the server's p99 is the longest (rounded to 1 us)
    assert 0 < wait["mean_ms"] <= s["p99_ms"] + 1e-3



@pytest.mark.parametrize("backend", [None, "oracle"])
def test_engine_build_counts_each_publish(backend):
    """``stats()["engine_build"]`` counts the constructor's publish and
    each later one, and sums positive seconds, whether the route builds
    a ``sparse_csr`` engine (density route) or not (pinned ``oracle``)."""
    cfg, state = _tm(seed=14, density=0.05)
    server = TMServer(cfg, state, ServePolicy(max_batch=4, backend=backend))
    build = server.stats()["engine_build"]
    assert build["count"] == 1 and build["seconds"] > 0
    seconds = [build["seconds"]]
    for i in range(2):
        ta = state.ta.at[0, 0, i].set(cfg.n_states + 1)
        server.publish(TMState(ta=ta))
        build = server.stats()["engine_build"]
        assert build["count"] == 2 + i
        seconds.append(build["seconds"])
    assert seconds[0] < seconds[1] < seconds[2]
    assert (server.stats()["sparse_layout"] is None) == (backend is not None)


@pytest.mark.parametrize("backend,copies", [
    (None, 1), ("sparse_csr", 1), ("swar_fused", 0), ("oracle", 0)])
def test_publish_copies_the_mask_only_where_a_route_reads_it(
        monkeypatch, backend, copies):
    """A publish brings the include mask to the host for the density
    route or the ELL; a route pinned to another backend reads neither."""
    import repro.serve.tm_server as tm_server
    cfg, state = _tm(seed=15, density=0.05)
    server = TMServer(cfg, state, ServePolicy(max_batch=4, backend=backend))
    calls = []
    real = tm_server.include_mask
    monkeypatch.setattr(tm_server, "include_mask",
                        lambda *a: calls.append(1) or real(*a))
    server.publish(TMState(ta=state.ta.at[0, 0, 0].set(cfg.n_states + 1)))
    assert len(calls) == copies
    assert server.stats()["engine_build"]["count"] == 2
    lits = jnp.asarray(np.random.default_rng(0).integers(
        0, 2, (4, cfg.n_literals)), jnp.int8)
    want = get_engine("oracle", cfg, server.state).infer(lits)
    got = server.engine_for(4).infer(lits)
    np.testing.assert_array_equal(np.asarray(got.class_sums),
                                  np.asarray(want.class_sums))



def test_engine_build_span_nests_in_publish(tmp_path):
    """A profiler trace of a publish holds one ``tm.engine_build`` span
    inside its ``tm.publish`` span."""
    import jax
    cfg, state = _tm(seed=16, density=0.05)
    server = TMServer(cfg, state, ServePolicy(max_batch=4))
    jax.profiler.start_trace(str(tmp_path))
    try:
        server.publish(TMState(ta=state.ta.at[0, 0, 0].set(cfg.n_states)))
    finally:
        jax.profiler.stop_trace()
    [path] = tmp_path.rglob("*.xplane.pb")
    events = [ev for plane in jax.profiler.ProfileData.from_file(
        str(path)).planes for line in plane.lines for ev in line.events]
    [publish] = [e for e in events if e.name == "tm.publish"]
    [build] = [e for e in events if e.name == "tm.engine_build"]
    assert publish.start_ns <= build.start_ns
    assert build.start_ns + build.duration_ns \
        <= publish.start_ns + publish.duration_ns


# -- stage B's result fetch: one packed array, or a copy per leaf --------

def _server_for(kind, cfg, state):
    """A server whose stage B serves ``kind``'s engine, and whether
    ``_compute`` should take the shed tier."""
    if kind == "cascade_shed":
        policy = ServePolicy(max_batch=8, backend="oracle",
                             shed_backend="cascade")
        return TMServer(cfg, state, policy), True
    backend = "time_domain" if kind == "time_domain" else "sparse_csr"
    mesh = 2 if kind == "sharded" else None
    return TMServer(cfg, state, ServePolicy(max_batch=8, backend=backend),
                    mesh=mesh), False


@pytest.mark.parametrize("kind, packed, aux", [
    pytest.param("sparse_csr", True, {}, id="sparse_csr"),
    pytest.param("time_domain", False, {"latency_ps": np.float32,
                                        "metastable": np.bool_},
                 id="time_domain"),
    pytest.param("cascade_shed", False, {"escalated": np.bool_},
                 id="cascade_shed"),
    pytest.param("sharded", False, {}, id="sharded"),
])
def test_compute_returns_numpy_results(kind, packed, aux):
    """``_compute`` hands stage C numpy leaves of the bucket's shape and
    the engine's dtypes, equal to the engine's own ``infer``, whichever
    way the result came back."""
    cfg, state = _tm(seed=14, density=0.05)
    rng = np.random.default_rng(14)

    async def go():
        server, shed = _server_for(kind, cfg, state)
        async with server:
            for seq, bucket in enumerate(server.buckets):
                eng = (server.shed_engine_for(bucket) if shed
                       else server.engine_for(bucket))
                assert hasattr(eng, "infer_packed") == packed
                lits = rng.integers(0, 2, (bucket, cfg.n_literals),
                                    dtype=np.int8)
                res = server._compute(lits, bucket, server.state, shed, seq)
                ref = eng.infer(jnp.asarray(lits))
                assert isinstance(res.prediction, np.ndarray)
                assert res.prediction.shape == (bucket,)
                assert res.prediction.dtype == np.int32
                assert isinstance(res.class_sums, np.ndarray)
                assert res.class_sums.shape == (bucket, C)
                assert res.class_sums.dtype == np.int32
                assert {k: v.dtype.type for k, v in res.aux.items()} == aux
                for k, v in res.aux.items():
                    assert isinstance(v, np.ndarray) and v.shape[0] == bucket
                    np.testing.assert_array_equal(v, np.asarray(ref.aux[k]))
                np.testing.assert_array_equal(res.prediction,
                                              np.asarray(ref.prediction))
                np.testing.assert_array_equal(res.class_sums,
                                              np.asarray(ref.class_sums))
            n = len(server.buckets)
            assert server.stats()["result_fetch"] == {
                "packed": n if packed else 0, "per_leaf": 0 if packed else n}

    asyncio.run(go())


@pytest.mark.parametrize("backend, path", [("sparse_csr", "packed"),
                                           ("time_domain", "per_leaf")])
def test_result_fetch_counts_each_batch_by_its_path(backend, path):
    cfg, state = _tm(seed=15, density=0.05)
    reqs = _requests(cfg, [1, 3, 2, 5, 1, 8, 2], seed=15)
    policy = ServePolicy(max_batch=8, max_wait_us=500, backend=backend)
    results, completions, stats = _serve_all(cfg, state, policy, reqs)
    _check_contract(cfg, state, reqs, results, completions)
    other = "per_leaf" if path == "packed" else "packed"
    assert stats["result_fetch"][path] == stats["batches"] > 0
    assert stats["result_fetch"][other] == 0


def test_warmup_compiles_the_served_program():
    """The warm-up compiles the packed program once per bucket, and
    serving every bucket afterwards adds nothing to its jit cache."""
    from repro.engine.backends import _sparse_csr_infer_packed
    # a shape no other test serves, so only this server fills the cache
    cfg = TMConfig(n_classes=4, n_clauses=11, n_features=13)
    rng = np.random.default_rng(16)
    ta = np.where(rng.random((4, 11, cfg.n_literals)) < 0.05,
                  cfg.n_states + 1, cfg.n_states)
    state = TMState(ta=jnp.asarray(ta, jnp.int32))

    async def go():
        policy = ServePolicy(max_batch=8, max_wait_us=0, backend="sparse_csr")
        async with TMServer(cfg, state, policy) as server:
            before = _sparse_csr_infer_packed._cache_size()
            await server.warmup()
            warm = _sparse_csr_infer_packed._cache_size()
            assert warm - before == len(server.buckets)
            for b in server.buckets:
                await server.submit(np.zeros((b, cfg.n_literals), np.int8))
            assert _sparse_csr_infer_packed._cache_size() == warm
            s = server.stats()
            assert s["batches"] == len(server.buckets)
            assert s["result_fetch"] == {"packed": len(server.buckets),
                                         "per_leaf": 0}

    asyncio.run(go())


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(sizes=st.lists(st.integers(min_value=1, max_value=9),
                      min_size=5, max_size=40),
       max_batch=st.sampled_from((1, 3, 8, 32)),
       seed=st.integers(min_value=0, max_value=2**16))
def test_scheduler_contract_heavy(sizes, max_batch, seed):
    """Wider sweep of the same contract (more examples, bigger streams,
    default bucket/backends routing) — the slow-tier companion of
    test_scheduler_contract_randomized."""
    cfg, state = _tm(seed=10, density=0.05)
    policy = ServePolicy(max_batch=max_batch, max_wait_us=1000)
    reqs = _requests(cfg, sizes, seed)
    results, completions, stats = _serve_all(cfg, state, policy, reqs)
    _check_contract(cfg, state, reqs, results, completions)


@pytest.mark.slow
def test_backpressure_bounded_queue():
    """queue_depth bounds the backlog: with a tiny queue and a flood of
    concurrent submits, qdepth never exceeds the bound and every request
    still completes exactly once."""
    cfg, state = _tm(seed=11)
    policy = ServePolicy(max_batch=2, max_wait_us=0, queue_depth=4,
                         backend="oracle")
    seen_depths = []

    async def go():
        async with TMServer(cfg, state, policy) as server:
            async def one(i):
                res = await server.submit(
                    np.zeros((1, cfg.n_literals), np.int8), client=i)
                seen_depths.append(server.stats()["qdepth"])
                return res

            results = await asyncio.gather(*[one(i) for i in range(50)])
        return results

    results = asyncio.run(go())
    assert len(results) == 50
    assert max(seen_depths) <= policy.queue_depth
