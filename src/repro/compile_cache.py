"""Persistent XLA compilation cache for the entry points.

A cold process compiles every serving bucket and the train step before
it takes traffic; JAX's persistent cache lets the next process on the
same machine load those programs instead.  :func:`enable_compile_cache`
is called by the entry points (``chip_smoke.py``,
``repro.launch.tm_serve.main``), never at import, so library users and
the test suite keep JAX's defaults.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
``<repo>/.jax_cache`` otherwise: a fixed path, because the path is part
of what the cache is keyed on.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["REPO_CACHE_DIR", "enable_compile_cache"]

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory → the path.

    Must run before the process compiles anything.  Every program is
    cached, however quickly it compiled: the TM kernels and steps each
    compile in well under JAX's default one-second floor.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
