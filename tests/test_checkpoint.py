"""repro.checkpoint: atomicity, retention, and the save/gc race.

The retention contract under concurrency: ``gc_keep`` may interleave
freely with ``save``/``save_async`` and must never prune a step whose
``.complete`` marker hasn't landed — including the re-save case where a
*stale completed* directory of the same step number exists (rollback →
re-checkpoint), which is exactly the interleaving that used to let
retention rmtree a directory out from under the writer's final rename.
"""

import threading

import jax
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core.tm import TMConfig
from repro.engine.train import export_key_cursor, import_key_cursor


def _tree(seed, shape=(3, 4)):
    rng = np.random.default_rng(seed)
    return {"ta": rng.integers(1, 256, shape).astype(np.int32)}


def test_save_restore_round_trip(tmp_path):
    d = str(tmp_path / "ck")
    tree = _tree(0)
    ckpt.save(d, 7, tree, extra={"version": 7, "note": "x"})
    assert ckpt.latest_step(d) == 7
    assert ckpt.valid_steps(d) == [7]
    got, extra = ckpt.restore(d, 7, {"ta": 0})
    np.testing.assert_array_equal(np.asarray(got["ta"]), tree["ta"])
    assert extra == {"version": 7, "note": "x"}
    assert ckpt.read_manifest_extra(d, 7) == extra


def test_latest_step_ignores_incomplete(tmp_path):
    d = tmp_path / "ck"
    ckpt.save(str(d), 1, _tree(1))
    # a crashed save: directory without the .complete marker
    (d / "step_9").mkdir()
    assert ckpt.latest_step(str(d)) == 1
    assert ckpt.valid_steps(str(d)) == [1]


def test_gc_keep_retention(tmp_path):
    d = str(tmp_path / "ck")
    for s in (1, 2, 3, 4):
        ckpt.save(d, s, _tree(s))
    ckpt.gc_keep(d, keep=2)
    assert ckpt.valid_steps(d) == [3, 4]


def test_gc_keep_never_prunes_in_flight_step(tmp_path, monkeypatch):
    """Regression: an in-flight re-save of an old step number pins that
    step against retention until its ``.complete`` lands."""
    d = str(tmp_path / "ck")
    for s in (5, 7):
        ckpt.save(d, s, _tree(s), extra={"gen": "old"})

    in_shard_write = threading.Event()
    release = threading.Event()
    real_savez = np.savez
    test_thread = threading.current_thread()

    def slow_savez(*args, **kwargs):
        # block the writer thread only; it may reach here before
        # save_async has even returned its handle
        if threading.current_thread() is not test_thread:
            in_shard_write.set()
            assert release.wait(timeout=30)
        return real_savez(*args, **kwargs)

    monkeypatch.setattr(np, "savez", slow_savez)
    t = ckpt.save_async(d, 5, _tree(50), extra={"gen": "new"})
    assert in_shard_write.wait(timeout=30)

    # while step 5's new write is in flight, retention must leave it
    # alone: the stale completed step_5 survives, step_7 is the newest
    ckpt.gc_keep(d, keep=1)
    assert ckpt.valid_steps(d) == [5, 7]
    assert ckpt.read_manifest_extra(d, 5) == {"gen": "old"}

    release.set()
    t.join(timeout=30)
    assert not t.is_alive()
    # the re-save landed atomically despite the interleaved gc ...
    assert ckpt.read_manifest_extra(d, 5) == {"gen": "new"}
    got, _ = ckpt.restore(d, 5, {"ta": 0})
    np.testing.assert_array_equal(np.asarray(got["ta"]), _tree(50)["ta"])
    # ... and once the writer finished, the step is an ordinary
    # retention candidate again
    ckpt.gc_keep(d, keep=1)
    assert ckpt.valid_steps(d) == [7]


def test_save_async_registers_before_thread_starts(tmp_path, monkeypatch):
    """The in-flight pin must exist the moment ``save_async`` returns —
    a gc issued immediately after may run before the writer thread is
    even scheduled."""
    d = str(tmp_path / "ck")
    ckpt.save(d, 3, _tree(3), extra={"gen": "old"})
    started = threading.Event()
    release = threading.Event()
    real_savez = np.savez

    def gated_savez(*args, **kwargs):
        started.set()
        assert release.wait(timeout=30)
        return real_savez(*args, **kwargs)

    monkeypatch.setattr(np, "savez", gated_savez)
    t = ckpt.save_async(d, 3, _tree(30), extra={"gen": "new"})
    ckpt.gc_keep(d, keep=0)      # prune everything prunable, right now
    assert ckpt.valid_steps(d) == [3], "in-flight step was pruned"
    release.set()
    t.join(timeout=30)
    assert ckpt.read_manifest_extra(d, 3) == {"gen": "new"}


def test_tm_lifecycle_round_trip(tmp_path):
    d = str(tmp_path / "ck")
    cfg = TMConfig(n_classes=3, n_clauses=8, n_features=9)
    ta = np.random.default_rng(0).integers(
        1, 257, (cfg.n_classes, cfg.n_clauses, cfg.n_literals)
    ).astype(np.int32)
    key = jax.random.key(42)
    data, impl = export_key_cursor(key)
    tree = ckpt.tm_lifecycle_tree(ta, data)
    ckpt.save(d, 12, tree, extra={"version": 12, "has_cursor": True,
                                  "key_impl": impl})

    step, got, extra = ckpt.restore_tm_lifecycle(d)
    assert step == 12 and extra["version"] == 12
    np.testing.assert_array_equal(np.asarray(got["ta"]), ta)
    restored = import_key_cursor(got["cursor"], extra["key_impl"])
    np.testing.assert_array_equal(np.asarray(jax.random.key_data(restored)),
                                  np.asarray(jax.random.key_data(key)))
    # the restored cursor draws the same splits as the original
    a = jax.random.split(key)
    b = jax.random.split(restored)
    np.testing.assert_array_equal(np.asarray(jax.random.key_data(a)),
                                  np.asarray(jax.random.key_data(b)))


def test_tm_lifecycle_without_cursor(tmp_path):
    d = str(tmp_path / "ck")
    ta = np.ones((2, 4, 6), np.int32)
    ckpt.save(d, 3, ckpt.tm_lifecycle_tree(ta),
              extra={"version": 3, "has_cursor": False})
    step, got, extra = ckpt.restore_tm_lifecycle(d)
    assert step == 3 and "cursor" not in got
    np.testing.assert_array_equal(np.asarray(got["ta"]), ta)


def test_restore_tm_lifecycle_empty_dir_raises(tmp_path):
    with pytest.raises(FileNotFoundError, match="no valid checkpoint"):
        ckpt.restore_tm_lifecycle(str(tmp_path / "nothing"))


@pytest.mark.parametrize("impl", ["threefry2x32", "rbg"])
def test_key_cursor_round_trip_impls(impl):
    """The cursor survives serialization for both PRNG implementations
    the train engines are tested against."""
    key = jax.random.key(7, impl=impl)
    data, name = export_key_cursor(key)
    assert data.dtype == np.uint32
    back = import_key_cursor(data, name)
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(back)),
        np.asarray(jax.random.key_data(key)))
    np.testing.assert_array_equal(
        np.asarray(jax.random.uniform(back, (4,))),
        np.asarray(jax.random.uniform(key, (4,))))
