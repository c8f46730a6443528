"""Pipelined-dispatch invariants: the serving contract under overlap.

PR 7 split the scheduler's serial loop into stages (assemble ‖ compute ‖
fan-out, bounded at ``pipeline_depth`` batches in flight) and made
dispatch deadline-aware (EDF ordering, admission control, slack
shedding).  These tests pin down what the pipeline must NOT change:

- **exactly once / in order per client** — across pipeline depths,
  including depth 1 (the legacy serial semantics);
- **arrival-version pinning** — predicts overlapping labeled updates
  still resolve bit-exactly against a *committed* version (their own
  arrival version), under pipelined update/predict interleavings;
- **drain on stop** — ``stop()`` mid-pipeline retires every in-flight
  stage and resolves every accepted future;

plus the new policy surface: EDF ordering keys, admission-control
rejects (:class:`DeadlineExceeded`), slack-exhausted shedding into the
tier backend, and the deadline/pipeline ``stats()`` blocks.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.tm import TMConfig, TMState, init_tm
from repro.engine import get_engine, get_train_engine
from repro.serve import DeadlineExceeded, ServePolicy, TMServer
from repro.serve.tm_server import _Request

C, M, F = 3, 7, 9
N_CLIENTS = 3


def _tm(seed=0, density=0.2):
    cfg = TMConfig(n_classes=C, n_clauses=M, n_features=F)
    rng = np.random.default_rng(seed)
    ta = np.where(rng.random((C, M, cfg.n_literals)) < density,
                  cfg.n_states + 1, cfg.n_states)
    return cfg, TMState(ta=jnp.asarray(ta, jnp.int32))


def _learn_tm(seed=0):
    cfg = TMConfig(n_classes=C, n_clauses=8, n_features=F, T=5, s=3.9)
    return cfg, init_tm(cfg, jax.random.key(seed))


def _stream(cfg, n, seed):
    rng = np.random.default_rng(seed)
    lits = rng.integers(0, 2, (n, cfg.n_literals), dtype=np.int8)
    labels = rng.integers(0, cfg.n_classes, (n,), dtype=np.int32)
    return lits, labels


def _expected_chain(cfg, state, batches, *, backend, seed):
    eng = get_train_engine(backend, cfg)
    chain = jax.random.key(seed)
    states = [state]
    for lits, labels in batches:
        chain, k = jax.random.split(chain)
        state = eng.step(state, k, jnp.asarray(lits), jnp.asarray(labels))
        states.append(state)
    return states


# -- contract across pipeline depths --------------------------------------

@settings(max_examples=8, deadline=None)
@given(sizes=st.lists(st.integers(min_value=1, max_value=5),
                      min_size=1, max_size=16),
       depth=st.sampled_from((1, 2, 3)),
       max_batch=st.sampled_from((2, 4, 16)),
       max_wait_us=st.sampled_from((0, 500)),
       seed=st.integers(min_value=0, max_value=2**16))
def test_pipelined_contract_exactly_once_in_order(sizes, depth, max_batch,
                                                  max_wait_us, seed):
    """The depth-parametrized version of the scheduler contract: every
    request resolves exactly once, per-client completion order is
    submission order, and every response is bit-exact vs an unbatched
    oracle — no matter how many batches overlap in flight."""
    cfg, state = _tm(seed=5)
    policy = ServePolicy(max_batch=max_batch, max_wait_us=max_wait_us,
                         backend="oracle", pipeline_depth=depth)
    rng = np.random.default_rng(seed)
    reqs = []
    seqs = [0] * N_CLIENTS
    for i, n in enumerate(sizes):
        client = i % N_CLIENTS
        lits = rng.integers(0, 2, (n, cfg.n_literals), dtype=np.int8)
        reqs.append((client, seqs[client], lits))
        seqs[client] += 1
    completions = []

    async def go():
        async with TMServer(cfg, state, policy) as server:
            async def one(client, seq, lits):
                res = await server.submit(lits, client=client)
                completions.append((client, seq))
                return res
            results = await asyncio.gather(
                *[one(c, s, l) for c, s, l in reqs])
            return results, server.stats()

    results, stats = asyncio.run(go())
    assert len(results) == len(reqs)
    assert len(completions) == len(set(completions)) == len(reqs)
    for client in range(N_CLIENTS):
        got = [s for c, s in completions if c == client]
        assert got == sorted(got), f"client {client} reordered: {got}"
    oracle = get_engine("oracle", cfg, state)
    for (client, seq, lits), res in zip(reqs, results):
        ref = oracle.infer(jnp.asarray(lits))
        np.testing.assert_array_equal(np.asarray(res.prediction),
                                      np.asarray(ref.prediction))
        np.testing.assert_array_equal(np.asarray(res.class_sums),
                                      np.asarray(ref.class_sums))
    assert stats["requests"] == len(reqs)
    assert stats["pipeline"]["depth"] == depth
    assert stats["pipeline"]["inflight"] == 0           # all retired


# -- update barriers under pipelined interleavings ------------------------

@settings(max_examples=6, deadline=None)
@given(n_updates=st.integers(min_value=1, max_value=3),
       n_predicts=st.integers(min_value=2, max_value=10),
       depth=st.sampled_from((1, 2, 3)),
       seed=st.integers(min_value=0, max_value=2**16))
def test_version_pinning_survives_pipelined_updates(n_updates, n_predicts,
                                                    depth, seed):
    """Updates overlap predict batches on the pipelined path (separate
    training thread, no global barrier) — yet every predict response
    still equals a full oracle result under one *committed* version, the
    update chain replays bit-exactly, and versions stay dense."""
    cfg, state = _learn_tm(seed=7)
    lits, labels = _stream(cfg, 48, seed)
    batches = [(lits[8 * i:8 * i + 8], labels[8 * i:8 * i + 8])
               for i in range(n_updates)]
    expected = _expected_chain(cfg, state, batches, backend="packed",
                               seed=seed)
    rng = np.random.default_rng(seed + 1)
    queries = [lits[rng.integers(0, 48, rng.integers(1, 4))]
               for _ in range(n_predicts)]

    async def go():
        async with TMServer(cfg, state,
                            ServePolicy(max_batch=8, max_wait_us=200,
                                        backend="oracle",
                                        pipeline_depth=depth),
                            train_backend="packed", train_seed=seed) as srv:
            await srv.warmup(train_batches=(8,))
            tasks = [srv.submit(q) for q in queries] + \
                    [srv.submit_labeled(*b) for b in batches]
            out = await asyncio.gather(*tasks)
            return out, srv.state

    results, final_state = asyncio.run(go())
    predict_res = results[:n_predicts]
    versions = results[n_predicts:]
    assert sorted(versions) == list(range(1, n_updates + 1))
    np.testing.assert_array_equal(np.asarray(final_state.ta),
                                  np.asarray(expected[-1].ta))
    for q, res in zip(queries, predict_res):
        qj = jnp.asarray(q)
        matched = any(
            (np.asarray(res.prediction)
             == np.asarray(get_engine("oracle", cfg, st_v).infer(qj)
                           .prediction)).all()
            and (np.asarray(res.class_sums)
                 == np.asarray(get_engine("oracle", cfg, st_v).infer(qj)
                               .class_sums)).all()
            for st_v in expected)
        assert matched, "response matches no committed state version"


@pytest.mark.parametrize("depth", [1, 2])
def test_queued_updates_all_apply_without_more_arrivals(depth):
    """Labeled batches that queue up together each get applied with no
    later arrival to wake the scheduler.  At depth 1 an update runs
    inline, so a second queued one used to sit until the next request
    arrived — forever, when the client was awaiting the first."""
    cfg, state = _learn_tm(seed=7)
    lits, labels = _stream(cfg, 24, seed=3)
    batches = [(lits[8 * i:8 * i + 8], labels[8 * i:8 * i + 8])
               for i in range(3)]
    expected = _expected_chain(cfg, state, batches, backend="packed",
                               seed=5)

    async def go():
        srv = TMServer(cfg, state,
                       ServePolicy(max_batch=8, backend="oracle",
                                   pipeline_depth=depth),
                       train_backend="packed", train_seed=5)
        await srv.start()
        await srv.warmup(train_batches=(8,))
        updates = asyncio.gather(*[srv.submit_labeled(*b) for b in batches])
        done, _ = await asyncio.wait({updates}, timeout=60)
        if not done:
            # stop() would wait on the stuck update too: cancel instead
            updates.cancel()
            srv._task.cancel()
            return None, None
        await srv.stop()
        return updates.result(), srv.state

    versions, final = asyncio.run(go())
    assert versions is not None, "queued labeled updates never applied"
    assert sorted(versions) == [1, 2, 3]
    np.testing.assert_array_equal(np.asarray(final.ta),
                                  np.asarray(expected[-1].ta))


def test_stop_mid_pipeline_drains_inflight():
    """stop() while batches are queued and in flight: every accepted
    request resolves (exactly once), nothing hangs, and the pipeline
    scoreboard is empty afterwards."""
    cfg, state = _tm(seed=11)
    policy = ServePolicy(max_batch=2, max_wait_us=0, backend="oracle",
                         pipeline_depth=3)

    async def go():
        server = await TMServer(cfg, state, policy).start()
        tasks = [asyncio.ensure_future(
            server.submit(np.zeros((1, cfg.n_literals), np.int8), client=i))
            for i in range(24)]
        await asyncio.sleep(0)      # let every submit reach the queue
        # stop immediately: the burst is still queued / mid-pipeline
        await server.stop()
        results = await asyncio.gather(*tasks)
        return results, server.stats()

    results, stats = asyncio.run(go())
    assert len(results) == 24
    assert stats["requests"] == 24 and stats["errors"] == 0
    assert stats["pipeline"]["inflight"] == 0
    assert stats["qdepth"] == 0


# -- deadline policy ------------------------------------------------------

def test_edf_orders_by_priority_then_slack():
    """The reorder heap serves (priority, deadline, seq): tighter slack
    first within a tier, FIFO for deadline-free traffic."""
    cfg, state = _tm(seed=3)
    srv = TMServer(cfg, state, ServePolicy(backend="oracle"))
    lits = np.zeros((1, cfg.n_literals), np.int8)
    t0 = 1000.0
    mk = (lambda seq, deadline=None, priority=0:
          _Request(lits, None, None, 0, state, deadline=deadline,
                   priority=priority, seq=seq))
    reqs = [mk(1, deadline=t0 + 9), mk(2), mk(3, deadline=t0 + 1),
            mk(4, priority=1), mk(5, deadline=t0 + 5, priority=1), mk(6)]
    for r in reqs:
        srv._ingest(r)
    order = []
    while True:
        r = srv._pop_head()
        if r is None:
            break
        order.append(r.seq)
    # tier 0: deadlines 1 then 9, then FIFO no-deadline (2, 6);
    # tier 1: deadline 5, then no-deadline (4)
    assert order == [3, 1, 2, 6, 5, 4]


def test_expired_requests_reaped_at_dispatch():
    """A queued request whose deadline passed while it waited is failed
    with DeadlineExceeded at dispatch (no compute) and counted as an
    expired drop; live requests and admission_control=False are
    untouched."""
    import time

    cfg, state = _tm(seed=5)
    lits = np.zeros((1, cfg.n_literals), np.int8)

    def seed_heap(srv):
        loop = asyncio.new_event_loop()
        try:
            dead = loop.create_future()
            live = loop.create_future()
        finally:
            loop.close()
        now = time.monotonic()
        srv._ingest(_Request(lits, dead, None, 0, state,
                             deadline=now - 1.0, seq=1))
        srv._ingest(_Request(lits, live, None, 0, state,
                             deadline=now + 60.0, seq=2))
        return dead, live

    srv = TMServer(cfg, state, ServePolicy(backend="oracle"))
    dead, live = seed_heap(srv)
    srv._reap_expired()
    assert dead.done() and isinstance(dead.exception(), DeadlineExceeded)
    assert not live.done()
    assert [e[-1].seq for e in srv._pending] == [2]
    assert srv.stats()["deadline"]["expired_drops"] == 1

    srv = TMServer(cfg, state, ServePolicy(backend="oracle",
                                           admission_control=False))
    dead, live = seed_heap(srv)
    srv._reap_expired()                      # no-op with admission off
    assert not dead.done() and not live.done()
    assert len(srv._pending) == 2
    assert srv.stats()["deadline"]["expired_drops"] == 0
    dead.cancel(), live.cancel()


def test_admission_control_rejects_provably_late():
    """A deadline below the bucket's fastest observed service time is
    rejected at submit (DeadlineExceeded) and counted; switching
    admission_control off serves (and records the miss) instead."""
    cfg, state = _tm(seed=4)

    async def go(admission):
        policy = ServePolicy(max_batch=4, max_wait_us=0, backend="oracle",
                             admission_control=admission)
        async with TMServer(cfg, state, policy) as srv:
            # seed the service ring: this bucket "always" takes 50ms
            srv._svc.observe(bucket_for_one := 1, 0.050)
            assert bucket_for_one == 1
            rejected = False
            try:
                # 1us: a real dispatch can never make this, so with
                # admission off it must be served-and-missed instead
                await srv.submit(np.zeros((1, cfg.n_literals), np.int8),
                                 deadline_us=1)
            except DeadlineExceeded:
                rejected = True
            # a generous deadline is always admitted
            await srv.submit(np.zeros((1, cfg.n_literals), np.int8),
                             deadline_us=60_000_000)
            return rejected, srv.stats()

    rejected, stats = asyncio.run(go(admission=True))
    assert rejected
    assert stats["deadline"]["admission_rejects"] == 1
    assert stats["deadline"]["requests"] == 1       # only the served one
    rejected, stats = asyncio.run(go(admission=False))
    assert not rejected
    assert stats["deadline"]["admission_rejects"] == 0
    assert stats["deadline"]["requests"] == 2
    assert stats["deadline"]["misses"] >= 1         # the 1us deadline


def test_deadline_validation_and_miss_accounting():
    cfg, state = _tm(seed=6)

    async def go():
        async with TMServer(cfg, state,
                            ServePolicy(max_batch=4, max_wait_us=0,
                                        backend="oracle")) as srv:
            with pytest.raises(ValueError, match="deadline_us"):
                await srv.submit(np.zeros(cfg.n_literals, np.int8),
                                 deadline_us=0)
            await srv.submit(np.zeros(cfg.n_literals, np.int8),
                             deadline_us=60_000_000, priority=2)
            return srv.stats()

    stats = asyncio.run(go())
    assert stats["deadline"]["requests"] == 1
    assert stats["deadline"]["misses"] == 0
    assert stats["deadline"]["miss_rate"] == 0.0


def test_slack_exhaustion_sheds_to_tier():
    """With a shed tier configured and the bucket's EWMA above a batch's
    remaining slack, dispatch routes the batch to the tier even though
    the queue-depth trigger never fires — and counts it."""
    cfg, state = _tm(seed=8)
    policy = ServePolicy(max_batch=4, max_wait_us=0, backend="oracle",
                         shed_backend="oracle", shed_qdepth=10**9,
                         admission_control=False)

    async def go():
        async with TMServer(cfg, state, policy) as srv:
            srv._svc.observe(1, 10.0)       # EWMA: 10s per 1-row bucket
            res = await srv.submit(np.zeros((1, cfg.n_literals), np.int8),
                                   deadline_us=50_000)
            return res, srv.stats()

    res, stats = asyncio.run(go())
    # exact tier: the answer is still bit-exact
    ref = get_engine("oracle", cfg, state).infer(
        jnp.zeros((1, cfg.n_literals), jnp.int8))
    np.testing.assert_array_equal(np.asarray(res.prediction),
                                  np.asarray(ref.prediction))
    assert stats["tiers"]["shed_batches"] == 1
    assert stats["deadline"]["slack_shed_batches"] == 1
    # per-bucket ring is surfaced for the operator
    assert stats["buckets"]["1"]["count"] >= 1


def test_pipeline_depth_validation():
    with pytest.raises(ValueError, match="pipeline_depth"):
        ServePolicy(pipeline_depth=0)


def test_service_stats_ring():
    """ServiceStats: EWMA converges toward observations, floor is the
    provable min, snapshot carries the percentile fields."""
    from repro.engine import ServiceStats
    svc = ServiceStats(alpha=0.5, window=8)
    assert svc.ewma(4) is None and svc.floor(4) is None
    for t in (0.010, 0.020, 0.030):
        svc.observe(4, t)
    assert svc.floor(4) == pytest.approx(0.010)
    assert 0.010 < svc.ewma(4) < 0.030
    snap = svc.snapshot()[4]
    assert snap["count"] == 3
    for k in ("ewma_ms", "min_ms", "p50_ms", "p90_ms", "p99_ms"):
        assert k in snap
    assert snap["min_ms"] == pytest.approx(10.0)
