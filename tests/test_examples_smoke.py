"""Runnable-docs smoke tests: the serving walkthroughs can't rot.

Imports ``examples/online_learning.py`` and ``examples/
checkpoint_serving.py`` and runs shortened versions of their loops,
asserting what each walkthrough claims: the online-learning server
climbs from chance accuracy to a trained level while predicts keep
being served, and a server killed mid-learning and restored from a
checkpoint continues bit-exactly against the uninterrupted run.
"""

import importlib.util
import pathlib

import pytest

_EXAMPLES = pathlib.Path(__file__).resolve().parents[1] / "examples"


def _load(name):
    spec = importlib.util.spec_from_file_location(name,
                                                  _EXAMPLES / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_online_learning_example_accuracy_climbs():
    mod = _load("online_learning")
    trajectory = mod.main(epochs=20, train_backend="packed", quiet=True)
    versions = [v for v, _ in trajectory]
    accs = [a for _, a in trajectory]
    # probes rode along the whole stream, tagged with climbing versions
    assert versions[0] == 0 and versions[-1] == 140
    assert versions == sorted(versions)
    # learning happened: from ~chance to the quickstart TM's regime
    assert accs[-1] >= 0.75, trajectory
    assert accs[-1] > accs[0], trajectory


def test_checkpoint_serving_example_bit_exact():
    mod = _load("checkpoint_serving")
    out = mod.main(n_batches=6, kill_after=3, train_backend="packed",
                   quiet=True)
    # the killed-and-restored run matched the uninterrupted one exactly
    assert out["bit_exact"], out
    assert out["version"] == 6 and out["n_predictions"] == 6


def test_tm_serve_launcher_deadline_flags(capsys, isolated_compile_cache):
    """The serving launcher runs end to end with SLO traffic: deadline +
    priority-mix flags, pipelined dispatch, and the deadline summary
    line (the docs' quickstart command can't rot)."""
    from repro.launch.tm_serve import main
    main(["--classes", "3", "--clauses", "16", "--features", "12",
          "--max-batch", "8", "--backend", "oracle", "--rate", "400",
          "--duration", "0.5", "--stats-every", "0.2",
          "--deadline-us", "500000", "--priority-mix", "0.5",
          "--pipeline-depth", "2"])
    out = capsys.readouterr().out
    assert "deadline 500000us" in out
    assert "mix 0.50" in out
    assert "req/s" in out


def test_tm_serve_launcher_exits_nonzero_on_failed_updates(
        capsys, monkeypatch, isolated_compile_cache):
    """A train step that passes warmup and then fails on every labeled
    update must fail the run: exit status 1, with both the error count
    and the stuck state version named — not a clean exit at version 0."""
    from repro.engine.train import PackedTrainEngine
    from repro.launch.tm_serve import main
    real_step = PackedTrainEngine.step
    calls = []

    def flaky_step(self, *args):
        calls.append(1)
        if len(calls) > 1:                  # the warmup step passes
            raise RuntimeError("injected train-step failure")
        return real_step(self, *args)

    monkeypatch.setattr(PackedTrainEngine, "step", flaky_step)
    with pytest.raises(SystemExit) as exc:
        main(["--classes", "3", "--clauses", "16", "--features", "12",
              "--max-batch", "8", "--backend", "oracle", "--rate", "200",
              "--duration", "0.5", "--stats-every", "0.2",
              "--train-backend", "packed", "--label-rate", "40",
              "--label-batch", "4"])
    assert exc.value.code == 1
    assert len(calls) > 1
    err = capsys.readouterr().err
    assert "failed requests or updates" in err
    assert "applied none of" in err


def test_tm_serve_launcher_learns_and_exits_cleanly(
        capsys, isolated_compile_cache):
    """The same serve-while-learn run with a working trainer returns
    normally, has climbed past version 0, and kept its compile cache in
    ``$JAX_COMPILATION_CACHE_DIR``."""
    import jax
    from repro.launch.tm_serve import main
    main(["--classes", "3", "--clauses", "16", "--features", "12",
          "--max-batch", "8", "--backend", "oracle", "--rate", "200",
          "--duration", "0.5", "--stats-every", "0.2",
          "--train-backend", "packed", "--label-rate", "40",
          "--label-batch", "4"])
    out = capsys.readouterr().out
    assert "state_version=" in out and "state_version=0 " not in out
    assert jax.config.jax_compilation_cache_dir == str(
        isolated_compile_cache)


def test_tm_serve_launcher_no_labels_offered_is_clean(
        capsys, isolated_compile_cache):
    """A run too short for the Poisson label feeder to offer a batch
    applied no update, yet nothing failed: it returns normally."""
    from repro.launch.tm_serve import main
    main(["--classes", "3", "--clauses", "16", "--features", "12",
          "--max-batch", "8", "--backend", "oracle", "--rate", "200",
          "--duration", "0.05", "--stats-every", "0.2",
          "--train-backend", "packed", "--label-rate", "0.001",
          "--label-batch", "4"])
    out = capsys.readouterr().out
    assert "state_version=0 " in out
