"""Test bootstrap: src/ on sys.path, hypothesis fallback + hygiene.

Keeps the tier-1 command working even without PYTHONPATH=src, and lets the
property tests collect on hermetic images that lack ``hypothesis`` (the
shim in ``repro.testing.hypothesis_fallback`` runs the same invariants via
seeded random sampling; real hypothesis is preferred when installed).

Property-suite hygiene, both flavors:

- the active randomness source is printed in the pytest header — the
  fallback's session seed, or the real-hypothesis profile — so every run
  is reproducible from its own output;
- ``--hypothesis-seed=N`` re-runs a fallback session's exact draws (real
  hypothesis registers the same flag via its pytest plugin);
- under real hypothesis, CI (``CI`` env set) loads a ``derandomize=True``
  profile with ``print_blob=True``, so CI property runs are deterministic
  and any failure prints its ``@reproduce_failure`` one-liner.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Simulate an 8-device host so the multi-host suites (test_multihost.py,
# test_elastic_restore.py) can build real 2/4/8-way meshes on one CPU.
# Must happen before the first `import jax` anywhere in the session;
# appended so an explicit XLA_FLAGS from the caller still applies.
_FORCE_DEVICES = "--xla_force_host_platform_device_count=8"
if _FORCE_DEVICES.split("=")[0] not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FORCE_DEVICES).strip()

_USING_FALLBACK = False
try:
    import hypothesis  # noqa: F401
    _USING_FALLBACK = getattr(hypothesis, "__is_repro_fallback__", False)
except ModuleNotFoundError:
    from repro.testing import hypothesis_fallback
    hypothesis_fallback.install()
    _USING_FALLBACK = True


def pytest_addoption(parser):
    # real hypothesis's pytest plugin registers --hypothesis-seed itself;
    # only the fallback needs our copy of the flag
    if _USING_FALLBACK:
        parser.addoption(
            "--hypothesis-seed", action="store", default="0",
            help="session seed for the hypothesis fallback shim's "
                 "deterministic draws (printed in the run header)")


def pytest_configure(config):
    if _USING_FALLBACK:
        from repro.testing import hypothesis_fallback
        hypothesis_fallback.set_seed(
            int(config.getoption("--hypothesis-seed")))
    else:
        from hypothesis import settings
        settings.register_profile("repro-ci", derandomize=True,
                                  print_blob=True)
        settings.register_profile("repro-local", print_blob=True)
        settings.load_profile(
            "repro-ci" if os.environ.get("CI") else "repro-local")


def pytest_report_header(config):
    if _USING_FALLBACK:
        from repro.testing import hypothesis_fallback
        seed = hypothesis_fallback.current_seed()
        return (f"hypothesis: fallback shim, seed={seed} "
                f"(reproduce with --hypothesis-seed={seed})")
    from hypothesis import settings
    return f"hypothesis: real, profile={settings._current_profile}"


@pytest.fixture
def isolated_compile_cache(tmp_path, monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` pointed at a scratch directory for a
    test that turns on the persistent compile cache (the entry points do);
    JAX's cache settings are restored afterwards, so the rest of the
    worker's tests compile exactly as before."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax"))
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs")}
    yield tmp_path / "jax"
    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
