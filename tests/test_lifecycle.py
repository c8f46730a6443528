"""TMServer state lifecycle: checkpoint/restore, bounded history, drift.

The acceptance contract of the lifecycle seam (docs/operations.md):

- **kill/restart** — a server restored mid-learning from a checkpoint
  produces bit-identical predictions and state versions to an
  uninterrupted run fed the same labeled stream, per train backend (the
  restored key-chain cursor resumes the deterministic chain exactly);
- **bounded history** — the version ring never exceeds its configured
  capacity while in-flight predicts pinned to retained (or even
  evicted) versions still resolve against their arrival state;
- **rollback** — re-publishes a historical (ring) or checkpointed
  (disk) state under a new, monotonically increasing version;
- **drift** — the held-out probe stream is scored every N updates and
  surfaced in ``stats()`` with best/latest/regression deltas.
"""

import asyncio
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core.tm import TMConfig, TMState, init_tm
from repro.engine import get_engine, get_train_engine
from repro.serve import ServePolicy, TMServer

C, M, F = 3, 8, 9


def _tm(seed=0):
    cfg = TMConfig(n_classes=C, n_clauses=M, n_features=F, T=5, s=3.9)
    return cfg, init_tm(cfg, jax.random.key(seed))


def _stream(cfg, n, seed):
    rng = np.random.default_rng(seed)
    lits = rng.integers(0, 2, (n, cfg.n_literals), dtype=np.int8)
    labels = rng.integers(0, cfg.n_classes, (n,), dtype=np.int32)
    return lits, labels


def _batches(cfg, n_batches, rows, seed):
    lits, labels = _stream(cfg, n_batches * rows, seed)
    return [(lits[i * rows:(i + 1) * rows], labels[i * rows:(i + 1) * rows])
            for i in range(n_batches)]


# -- kill/restart bit-exact continuation (the acceptance test) ---------


@pytest.mark.parametrize("backend", ["reference", "packed", "fused"])
def test_kill_restart_replays_bit_exact(backend, tmp_path):
    """Restored-from-checkpoint continuation == uninterrupted run: same
    states, same versions, same predictions, for every train backend."""
    cfg, state = _tm(seed=3)
    batches = _batches(cfg, 6, 8, seed=4)
    probe = batches[0][0][:5]
    d = str(tmp_path / "ck")

    async def uninterrupted():
        preds = []
        async with TMServer(cfg, state, ServePolicy(max_batch=8,
                                                    backend="oracle"),
                            train_backend=backend, train_seed=11) as srv:
            for b in batches:
                await srv.submit_labeled(*b)
                preds.append(np.asarray((await srv.submit(probe)).prediction))
            return np.asarray(srv.state.ta), srv.state_version, preds

    async def killed_and_restored():
        preds = []
        async with TMServer(cfg, state, ServePolicy(max_batch=8,
                                                    backend="oracle"),
                            train_backend=backend, train_seed=11,
                            checkpoint_dir=d,
                            checkpoint_every_updates=3) as srv:
            for b in batches[:3]:
                await srv.submit_labeled(*b)
                preds.append(np.asarray((await srv.submit(probe)).prediction))
        # fresh server, wrong train_seed on purpose: the restored
        # cursor (not the constructor seed) must drive the chain
        srv2 = TMServer(cfg, state, ServePolicy(max_batch=8,
                                                backend="oracle"),
                        train_backend=backend, train_seed=999,
                        checkpoint_dir=d)
        assert srv2.restore() == 3
        assert srv2.stats()["checkpoint"]["restored_from"] == 3
        async with srv2:
            for b in batches[3:]:
                await srv2.submit_labeled(*b)
                preds.append(
                    np.asarray((await srv2.submit(probe)).prediction))
            return np.asarray(srv2.state.ta), srv2.state_version, preds

    ta_a, v_a, preds_a = asyncio.run(uninterrupted())
    ta_b, v_b, preds_b = asyncio.run(killed_and_restored())
    assert v_a == v_b == 6
    np.testing.assert_array_equal(ta_a, ta_b)
    for a, b in zip(preds_a, preds_b):
        np.testing.assert_array_equal(a, b)


def test_restore_adopts_checkpoint_backend_and_enables_training(tmp_path):
    """A checkpoint taken under one train backend restores onto a server
    constructed with another (or none): the snapshot's backend + opts
    win, so the resumed run is the same run."""
    cfg, state = _tm(seed=5)
    batches = _batches(cfg, 4, 8, seed=6)
    d = str(tmp_path / "ck")

    async def phase1():
        async with TMServer(cfg, state, ServePolicy(max_batch=8),
                            train_backend="packed", train_seed=7,
                            checkpoint_dir=d) as srv:
            for b in batches[:2]:
                await srv.submit_labeled(*b)
            # graceful stop checkpoints the final version automatically

    asyncio.run(phase1())
    assert ckpt.latest_step(d) == 2
    extra = ckpt.read_manifest_extra(d, 2)
    assert extra["train_backend"] == "packed" and extra["has_cursor"]
    assert extra["cfg"] == dataclasses.asdict(cfg)

    async def phase2():
        srv = TMServer(cfg, state, ServePolicy(max_batch=8),
                       checkpoint_dir=d)      # no train_backend at all
        assert srv.restore() == 2
        async with srv:
            for b in batches[2:]:
                await srv.submit_labeled(*b)  # training is now enabled
            return np.asarray(srv.state.ta), srv.state_version

    ta_b, v_b = asyncio.run(phase2())
    assert v_b == 4

    # offline replay of the whole chain says the same thing
    eng = get_train_engine("packed", cfg)
    chain, s = jax.random.key(7), state
    for lits, labels in batches:
        chain, k = jax.random.split(chain)
        s = eng.step(s, k, jnp.asarray(lits), jnp.asarray(labels))
    np.testing.assert_array_equal(ta_b, np.asarray(s.ta))


@pytest.mark.parametrize("backend", ["fused", "sparse", "sharded"])
def test_restore_ignores_retired_tile_opts(backend, tmp_path):
    """Checkpoints written while the fused/sparse/sharded trainers took
    ``block_b``/``block_m`` still carry them in ``train_opts``: restore
    drops the two keys and resumes the same chain bit-exactly."""
    cfg, state = _tm(seed=8)
    batches = _batches(cfg, 4, 8, seed=9)
    old, d = str(tmp_path / "written"), str(tmp_path / "older")

    async def phase1():
        async with TMServer(cfg, state, ServePolicy(max_batch=8),
                            train_backend=backend, train_seed=13) as srv:
            for b in batches[:2]:
                await srv.submit_labeled(*b)
        srv.checkpoint(old)

    asyncio.run(phase1())
    step, tree, extra = ckpt.restore_tm_lifecycle(old)
    extra["train_opts"].update(block_b=64, block_m=128)
    ckpt.save(d, step, tree, extra=extra)

    async def phase2():
        srv = TMServer(cfg, state, ServePolicy(max_batch=8))
        assert srv.restore(d) == 2
        async with srv:
            for b in batches[2:]:
                await srv.submit_labeled(*b)
            return np.asarray(srv.state.ta), srv.state_version

    ta_b, v_b = asyncio.run(phase2())
    assert v_b == 4
    eng = get_train_engine("fused", cfg)
    chain, s = jax.random.key(13), state
    for lits, labels in batches:
        chain, k = jax.random.split(chain)
        s = eng.step(s, k, jnp.asarray(lits), jnp.asarray(labels))
    np.testing.assert_array_equal(ta_b, np.asarray(s.ta))


def test_restore_validation(tmp_path):
    cfg, state = _tm()
    d = str(tmp_path / "ck")
    srv = TMServer(cfg, state, ServePolicy(max_batch=4))
    with pytest.raises(ValueError, match="no checkpoint directory"):
        srv.checkpoint()
    with pytest.raises(ValueError, match="no checkpoint directory"):
        srv.restore()
    srv.checkpoint(d)
    other_cfg = TMConfig(n_classes=C, n_clauses=M, n_features=F + 1)
    other = TMServer(other_cfg, init_tm(other_cfg, jax.random.key(0)),
                     ServePolicy(max_batch=4))
    with pytest.raises(ValueError, match="was written for"):
        other.restore(d)

    async def mid_run():
        async with TMServer(cfg, state, ServePolicy(max_batch=4)) as live:
            with pytest.raises(RuntimeError, match="before start"):
                live.restore(d)

    asyncio.run(mid_run())
    with pytest.raises(ValueError, match="checkpoint_every_updates"):
        TMServer(cfg, state, checkpoint_every_updates=2)
    with pytest.raises(ValueError, match="probe_every_updates"):
        TMServer(cfg, state, probe_every_updates=2)


# -- bounded version history + rollback --------------------------------


def test_history_ring_is_bounded_and_pinned_predicts_resolve():
    """The ring holds at most ``history_size`` pairs while a predict
    pinned to a version long since evicted from the ring still resolves
    against its arrival state (requests own their pin)."""
    cfg, state = _tm(seed=7)
    lits, labels = _stream(cfg, 64, 8)
    expected0 = get_engine("oracle", cfg, state).infer(jnp.asarray(lits[:4]))

    async def go():
        async with TMServer(cfg, state,
                            ServePolicy(max_batch=64, max_wait_us=200_000,
                                        backend="oracle"),
                            train_backend="reference", history_size=3) as srv:
            await srv.warmup(train_batches=(8,))
            # pinned at v0; the open batch waits while updates run
            # (updates cut the batch queue-order barrier via carry)
            pinned = asyncio.ensure_future(srv.submit(lits[:4]))
            await asyncio.sleep(0)
            for i in range(8):
                await srv.submit_labeled(lits[8 * i:8 * i + 8],
                                         labels[8 * i:8 * i + 8])
            s = srv.stats()
            assert s["history"]["capacity"] == 3
            assert s["history"]["versions"] == [6, 7, 8]
            assert srv.history_versions == (6, 7, 8)
            res = await pinned
            return res

    res = asyncio.run(go())
    # v0 left the ring long ago; the pinned predict still saw exactly v0
    np.testing.assert_array_equal(np.asarray(res.prediction),
                                  np.asarray(expected0.prediction))
    np.testing.assert_array_equal(np.asarray(res.class_sums),
                                  np.asarray(expected0.class_sums))


def test_rollback_from_ring_and_disk(tmp_path):
    cfg, state = _tm(seed=9)
    lits, labels = _stream(cfg, 80, 10)
    d = str(tmp_path / "ck")

    async def go():
        async with TMServer(cfg, state, ServePolicy(max_batch=8,
                                                    backend="oracle"),
                            train_backend="reference", history_size=3,
                            checkpoint_dir=d, checkpoint_every_updates=2,
                            checkpoint_keep=10) as srv:
            seen = {0: np.asarray(srv.state.ta)}
            for i in range(6):
                v = await srv.submit_labeled(lits[8 * i:8 * i + 8],
                                             labels[8 * i:8 * i + 8])
                seen[v] = np.asarray(srv.state.ta)
            assert srv.history_versions == (4, 5, 6)

            # ring rollback: version 5 is retained in memory
            assert srv.rollback(5) == 7
            np.testing.assert_array_equal(np.asarray(srv.state.ta), seen[5])
            # a predict after the rollback serves the rolled-back state
            res = await srv.submit(lits[:4])
            ref = get_engine("oracle", cfg,
                             TMState(ta=jnp.asarray(seen[5]))).infer(
                                 jnp.asarray(lits[:4]))
            np.testing.assert_array_equal(np.asarray(res.prediction),
                                          np.asarray(ref.prediction))

            # disk rollback: version 2 was checkpointed but evicted from
            # the ring — wait for its async writer, then roll back to it
            for t in list(srv._ckpt_threads):
                t.join(timeout=30)
            assert 2 in ckpt.valid_steps(d)
            assert srv.rollback(2) == 8
            np.testing.assert_array_equal(np.asarray(srv.state.ta), seen[2])

            with pytest.raises(KeyError, match="neither the history ring"):
                srv.rollback(3)       # never checkpointed, evicted
            assert srv.stats()["rollbacks"] == 2

    asyncio.run(go())


# -- drift monitoring --------------------------------------------------


def test_probe_drift_stats():
    """Every N applied updates the probe stream is scored; stats surface
    latest/best accuracy, drift (best − latest), and step deltas."""
    cfg, state = _tm(seed=11)
    lits, labels = _stream(cfg, 64, 12)
    probe = (lits[:16], labels[:16])

    async def go():
        async with TMServer(cfg, state, ServePolicy(max_batch=8,
                                                    backend="oracle"),
                            train_backend="packed", train_seed=13,
                            probe=probe, probe_every_updates=2) as srv:
            assert srv.stats()["probe"] == {
                "evals": 0, "accuracy": None, "best": None, "drift": 0.0,
                "delta": 0.0, "window_mean": 0.0, "at_version": None}
            for i in range(6):
                await srv.submit_labeled(lits[8 * i:8 * i + 8],
                                         labels[8 * i:8 * i + 8])
            # the update future resolves before its probe eval runs on
            # the training thread, and no predict waits for that eval:
            # poll until the third is counted (or give up after 30 s)
            t_end = time.monotonic() + 30
            while (srv.stats()["probe"]["evals"] < 3
                   and time.monotonic() < t_end):
                await asyncio.sleep(0.002)
            return srv.stats()["probe"], np.asarray(srv.state.ta)

    probe_stats, ta = asyncio.run(go())
    assert probe_stats["evals"] == 3
    assert probe_stats["at_version"] == 6
    # the scores are real accuracies of the published states
    eng = get_engine("oracle", cfg, TMState(ta=jnp.asarray(ta)))
    acc_final = float((np.asarray(eng.infer(jnp.asarray(probe[0]))
                                  .prediction) == probe[1]).mean())
    assert probe_stats["accuracy"] == pytest.approx(acc_final)
    assert probe_stats["best"] >= probe_stats["accuracy"]
    assert probe_stats["drift"] == pytest.approx(
        probe_stats["best"] - probe_stats["accuracy"])
    assert 0.0 <= probe_stats["window_mean"] <= 1.0


def test_probe_validation():
    cfg, state = _tm()
    lits, labels = _stream(cfg, 8, 1)
    with pytest.raises(ValueError, match="probe labels"):
        TMServer(cfg, state, probe=(lits, labels[:4]))
    with pytest.raises(ValueError, match="expected"):
        TMServer(cfg, state, probe=(lits[:, :3], labels))


# -- graceful-stop checkpointing ---------------------------------------


def test_stop_takes_final_checkpoint_and_joins_writers(tmp_path):
    cfg, state = _tm(seed=15)
    lits, labels = _stream(cfg, 40, 16)
    d = str(tmp_path / "ck")

    async def go():
        async with TMServer(cfg, state, ServePolicy(max_batch=8),
                            train_backend="reference",
                            checkpoint_dir=d,
                            checkpoint_every_updates=2) as srv:
            for i in range(5):
                await srv.submit_labeled(lits[8 * i:8 * i + 8],
                                         labels[8 * i:8 * i + 8])
            return srv

    srv = asyncio.run(go())
    # v5 wasn't on the every-2 cadence; stop() flushed it anyway, and
    # every writer thread was joined before stop returned
    assert ckpt.latest_step(d) == 5
    assert srv._ckpt_threads == []
    extra = ckpt.read_manifest_extra(d, 5)
    assert extra["version"] == 5 and extra["updates"] == 5
