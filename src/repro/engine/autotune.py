"""Autotuner for tile-parameterized VoteEngine backends.

``mxu_fused`` and ``swar_fused`` take ``block_b``/``block_cm`` tile sizes
that used to be hardcoded guesses.  This module sweeps each backend's
candidate grid per TM shape, times the jitted ``infer`` end to end, and
persists the winners to a JSON cache (``benchmarks/autotune_cache.json``
by default, overridable via ``REPRO_AUTOTUNE_CACHE``).  ``get_engine``
consults :func:`lookup` on every build, so once a shape has been tuned on
a device kind, every engine constructed for it uses the measured-best
tiles instead of the defaults — explicitly passed opts always win.

Cache entries are keyed by ``backend|C|M|L|device_kind``: tile choice
depends on the clause geometry and the compiler target, not on the exact
batch size, so the tuner measures each candidate across the batch grid
and picks the config with the lowest *total* time.

Run the sweep:

    PYTHONPATH=src python -m repro.engine.autotune --quick
    PYTHONPATH=src python -m repro.engine.autotune --backends swar_fused
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SEARCH_SPACE", "cache_path", "device_kind", "shape_key",
           "lookup", "serve_key", "serve_lookup", "record_serve_routing",
           "autotune_backend", "run_sweep"]

# candidate tiles per tunable backend; every combination is measured.
SEARCH_SPACE: dict[str, dict[str, tuple[int, ...]]] = {
    "mxu_fused": {"block_b": (32, 64, 128, 256),
                  "block_cm": (64, 128, 256)},
    "swar_fused": {"block_b": (8, 16, 32, 64),
                   "block_cm": (64, 128, 256)},
    # early-exit cascade: exits need a stage-1 margin ≥ the remainder
    # size, so fractions below ~0.5 can never pay off — the grid starts
    # there.  The winner depends on the state's margin distribution, so
    # the sweep's random-state result is a default, not a guarantee.
    "cascade": {"stage1_fraction": (0.5, 0.625, 0.75, 0.875)},
}

_DEFAULT_CACHE = (Path(__file__).resolve().parents[3] / "benchmarks"
                  / "autotune_cache.json")
_loaded: dict = {}      # path → (mtime, parsed json)


def cache_path() -> Path:
    """The JSON cache file (``REPRO_AUTOTUNE_CACHE`` overrides default)."""
    return Path(os.environ.get("REPRO_AUTOTUNE_CACHE", _DEFAULT_CACHE))


def device_kind() -> str:
    """Compiler target the measurements are valid for (cpu/gpu/tpu)."""
    return jax.default_backend()


def shape_key(backend: str, cfg) -> str:
    """Cache key for tuned tiles: ``backend|C…|M…|L…|device_kind``."""
    return (f"{backend}|C{cfg.n_classes}|M{cfg.n_clauses}"
            f"|L{cfg.n_literals}|{device_kind()}")


def _load_cache() -> dict:
    path = cache_path()
    try:
        mtime = path.stat().st_mtime_ns
    except OSError:
        return {}
    cached = _loaded.get(str(path))
    if cached is not None and cached[0] == mtime:
        return cached[1]
    try:
        data = json.loads(path.read_text())
    except (OSError, ValueError):
        data = {}
    _loaded[str(path)] = (mtime, data)
    return data


def lookup(backend: str, cfg) -> dict:
    """Tuned ctor opts for (backend, cfg) on this device kind, or ``{}``."""
    if backend not in SEARCH_SPACE:
        return {}
    best = _load_cache().get("best", {}).get(shape_key(backend, cfg), {})
    # guard against stale caches naming opts the backend no longer takes
    return {k: v for k, v in best.items() if k in SEARCH_SPACE[backend]}


def serve_key(cfg, bucket: int) -> str:
    """Cache key for a measured bucket→backend serving route."""
    return (f"serve|C{cfg.n_classes}|M{cfg.n_clauses}"
            f"|L{cfg.n_literals}|B{bucket}|{device_kind()}")


def serve_lookup(cfg, bucket: int) -> str | None:
    """Measured-best backend for this TM shape at this bucket size, or
    ``None`` when ``benchmarks/serve_bench.py --update-routing`` hasn't
    recorded one on this device kind."""
    return _load_cache().get("serve_best", {}).get(serve_key(cfg, bucket))


def record_serve_routing(cfg, routes: dict[int, str]) -> None:
    """Persist measured bucket→backend routes (from the serve load bench)
    into the autotune cache, keyed like :func:`serve_lookup` reads them."""
    data = _load_cache()
    table = data.setdefault("serve_best", {})
    for bucket, backend in routes.items():
        table[serve_key(cfg, bucket)] = backend
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(data, indent=2, sort_keys=True) + "\n")
    _loaded.pop(str(path), None)


def _time_us(fn, *args, repeat: int = 5) -> float:
    for leaf in jax.tree_util.tree_leaves(fn(*args)):
        getattr(leaf, "block_until_ready", lambda: None)()
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
        for leaf in jax.tree_util.tree_leaves(out):
            getattr(leaf, "block_until_ready", lambda: None)()
    return (time.perf_counter() - t0) / repeat * 1e6


def autotune_backend(backend: str, cfg, state, batches, *,
                     repeat: int = 5) -> tuple[dict, list[dict]]:
    """Sweep ``SEARCH_SPACE[backend]`` for one (cfg, state).

    ``batches``: iterable of (B, L) literal arrays to measure over.
    → (best param dict, all measurement rows).
    """
    from .base import _REGISTRY
    from . import backends  # noqa: F401  (registration side effect)
    space = SEARCH_SPACE[backend]
    names, grids = zip(*space.items())
    rows, best, best_us = [], {}, float("inf")
    for combo in itertools.product(*grids):
        params = dict(zip(names, combo))
        try:
            engine = _REGISTRY[backend](cfg, state, **params)
            total = sum(_time_us(engine.infer, lits, repeat=repeat)
                        for lits in batches)
        except Exception as exc:      # invalid tile for this shape/target
            rows.append({"backend": backend, **params, "error": str(exc)})
            continue
        rows.append({"backend": backend, **params,
                     "total_us": round(total, 1)})
        if total < best_us:
            best_us, best = total, params
    return best, rows


def run_sweep(*, quick: bool = False, backends: list[str] | None = None,
              repeat: int = 5) -> dict:
    """Tune every (tunable backend × engine_bench shape); return the cache
    dict (also written to :func:`cache_path`)."""
    from benchmarks.engine_bench import (FULL_GRID, INCLUDE_DENSITY,
                                         F_FEATURES, QUICK_GRID,
                                         _random_state)
    from repro.core.tm import TMConfig

    grid = QUICK_GRID if quick else FULL_GRID
    names = [b for b in (backends or sorted(SEARCH_SPACE))
             if b in SEARCH_SPACE]
    rng = np.random.default_rng(0)
    data = _load_cache()
    data.setdefault("best", {})
    data["include_density"] = INCLUDE_DENSITY
    # keyed like "best" so reruns *replace* a shape's rows, never append
    # duplicates; device kind lives in the key, so cpu/tpu entries coexist
    measurements = data.setdefault("measurements", {})
    if isinstance(measurements, list):      # pre-keyed cache format
        measurements = data["measurements"] = {
            row["key"]: row["rows"] for row in measurements}
    for c in grid["C"]:
        for m in grid["M"]:
            cfg = TMConfig(n_classes=c, n_clauses=m, n_features=F_FEATURES)
            st = _random_state(cfg, rng)
            batches = [jnp.asarray(rng.integers(0, 2, (b, cfg.n_literals),
                                                dtype=np.int8))
                       for b in grid["B"]]
            for backend in names:
                best, rows = autotune_backend(backend, cfg, st, batches,
                                              repeat=repeat)
                key = shape_key(backend, cfg)
                data["best"][key] = best
                measurements[key] = rows
                print(f"{key}: best={best}")
    path = cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(data, indent=2, sort_keys=True) + "\n")
    _loaded.pop(str(path), None)
    print(f"wrote {path}")
    return data


def main() -> None:
    """CLI entry point: run the sweep (see module docstring)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="single engine_bench shape per backend")
    ap.add_argument("--backends", nargs="*", default=None,
                    help=f"subset of {sorted(SEARCH_SPACE)}")
    ap.add_argument("--repeat", type=int, default=5)
    args = ap.parse_args()
    run_sweep(quick=args.quick, backends=args.backends, repeat=args.repeat)


if __name__ == "__main__":
    main()
