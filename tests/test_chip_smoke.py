"""``chip_smoke.py`` rehearsed on CPU, and the compile-cache helper.

The smoke's phases run here as functions at a tiny registered width
(``tm-iris-10``), Pallas in interpret mode, so a wrong path, argument or
check fails here before it costs chip time.  The script itself has no
switch around its device check: run as a program on CPU, or without the
rest of the repository beside it, it must fail and print no result.
"""

import asyncio
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_one_chip_phases_tiny(smoke):
    cfg, state = smoke.build_machine("tm-iris-10")
    report = asyncio.run(smoke.serve_and_learn(cfg, state, n_predicts=40))
    assert report["errors"] == 0
    assert report["updates"] == report["state_version"] == smoke.N_UPDATES
    assert report["requests"] >= 40 + 2 * 20
    assert {"swar_fused", "mxu_fused", "time_domain"} <= set(
        report["backends"])


def test_smoke_mesh_phases_tiny(smoke):
    assert len(jax.devices()) >= 4, "conftest must simulate 8 devices"
    cfg, state = smoke.build_machine("tm-iris-10")
    report = asyncio.run(smoke.mesh_phases(cfg, state, n_devices=4,
                                           n_predicts=20, n_updates=2))
    assert report["serve_devices"] == report["state_devices"] == 4
    assert report["updates"] == 2


def test_smoke_checks_catch_a_wrong_answer(smoke):
    """The parity check is live: a state that differs from the served
    one must be reported, not passed."""
    cfg, state = smoke.build_machine("tm-iris-10")
    _, wrong = smoke.build_machine("tm-iris-10", seed=1)
    pool, _ = smoke.make_traffic(cfg, seed=0, n_pool=64)
    rows = [list(range(64))]
    got = smoke.get_engine("oracle", cfg, state).infer(pool)
    smoke._check_served(rows, [got], [smoke.oracle(cfg, state, pool)], "ok")
    with pytest.raises(AssertionError, match="differs from oracle"):
        smoke._check_served(rows, [got], [smoke.oracle(cfg, wrong, pool)],
                            "wrong")


def _run(cwd, env):
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_smoke_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = _run(ROOT, env)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout


def test_smoke_fails_without_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    res = _run(tmp_path, env)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def test_compile_cache_follows_env(isolated_compile_cache):
    from repro.compile_cache import enable_compile_cache
    assert enable_compile_cache() == str(isolated_compile_cache)
    assert jax.config.jax_compilation_cache_dir == str(
        isolated_compile_cache)


def test_compile_cache_defaults_to_repo(monkeypatch, isolated_compile_cache):
    from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert REPO_CACHE_DIR == ROOT / ".jax_cache"
    assert enable_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


def test_compile_cache_hits_are_counted(smoke, isolated_compile_cache):
    """What the smoke reports as cache hits: a program compiled once,
    dropped from memory and compiled again is loaded from the cache."""
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    counts = {"hits": 0, "misses": 0}
    listener = smoke.count_cache_events(counts)
    try:
        fn = lambda x: x * 3 + 1                                # noqa: E731
        jax.jit(fn)(jax.numpy.arange(5)).block_until_ready()
        jax.clear_caches()
        jax.jit(fn)(jax.numpy.arange(5)).block_until_ready()
    finally:
        jax.monitoring.unregister_event_listener(listener)
    assert counts["misses"] >= 1 and counts["hits"] >= 1
    assert any(isolated_compile_cache.iterdir())


def test_smoke_last_line_contract(smoke, monkeypatch, capsys):
    """On a platform reported as TPU the last line is exactly the
    result object; the phases themselves are stubbed here (they run
    above at tiny width and on the chip at full width)."""
    class FakeDevice:
        platform, device_kind = "tpu", "TPU v5 lite"

    async def phases(cfg, state, **kw):
        return {"requests": 0}

    monkeypatch.setattr(smoke.jax, "devices", lambda: [FakeDevice()])
    monkeypatch.setattr(smoke, "enable_compile_cache", lambda: "cache")
    monkeypatch.setattr(smoke, "count_cache_events", lambda counts: None)
    monkeypatch.setattr(smoke, "serve_and_learn", phases)
    monkeypatch.setattr(smoke, "build_machine",
                        lambda seed: smoke.build_tm(2, 4, 3, density=0.5,
                                                    seed=seed))
    assert smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
