"""Chip smoke test: TM serve-while-learn on a TPU at ``tm-mnist-100`` width.

    python chip_smoke.py              # one chip: the serving path, below
    python chip_smoke.py --chips 4    # four chips: the mesh phases only

One chip.  A ``TMServer`` over the paper's ``tm-mnist-100`` machine
(C=10 classes, M=100 clauses per class, F=784 features; random include
masks at a trained machine's 5% density, from ``--seed``) serves a few
hundred routed predicts of 1-64 rows while its ``fused`` trainer learns
from 8 labeled batches of 32 rows.  Checked, bit for bit:

- every served prediction and class sum against the ``oracle`` backend
  (predicts that overlap the updates against the state of some version);
- every registered inference backend at bucket 64 against ``oracle``, and
  that ``swar_fused``/``mxu_fused`` compiled to a TPU kernel
  (``tpu_custom_call`` in the HLO), not the Pallas interpreter;
- the published state after the updates against an offline replay of the
  server's key chain through the ``reference`` trainer;
- a fresh server restored from a checkpoint: same state, same answers.

Four chips.  A server on a 4-device mesh serves through ``ShardedEngine``
and learns with the ``sharded`` trainer; its state must equal the
``fused`` trainer's at D=1, its answers ``oracle``'s, and its checkpoint
must restore onto one device and resume bit-exactly.

It runs in one process and starts none.  It exits non-zero, before it
prints a result, when JAX finds no TPU (there is no CPU fallback), and on
any exception, served error or mismatch.  Lines before the last are
smoke output (set-up times, counters), not metrics.  The last line is
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Compiled
programs go to ``$JAX_COMPILATION_CACHE_DIR``, or ``.jax_cache/`` beside
this file, so a second run loads them.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.compile_cache import enable_compile_cache          # noqa: E402
from repro.configs import get_config                          # noqa: E402
from repro.distributed.sharding import data_mesh              # noqa: E402
from repro.engine import (available_backends, get_engine,     # noqa: E402
                          get_train_engine)
from repro.launch.tm_serve import build_tm                    # noqa: E402
from repro.serve import ServePolicy, TMServer                 # noqa: E402

ARCH = "tm-mnist-100"
DENSITY = 0.05
MAX_BATCH = 64
LABEL_BATCH = 32
N_UPDATES = 8
N_PREDICTS = 300
KERNEL_BACKENDS = ("swar_fused", "mxu_fused")


def build_machine(arch: str = ARCH, *, seed: int = 0):
    """The registered TM architecture at its full width → (cfg, state)."""
    mc = get_config(arch)
    cfg, state = build_tm(mc.n_heads, mc.d_ff, mc.d_model,
                          density=DENSITY, seed=seed)
    return dataclasses.replace(cfg, T=int(mc.rope_theta), s=mc.norm_eps), \
        state


def make_traffic(cfg, *, seed: int, n_pool: int = 1024,
                 n_updates: int = N_UPDATES, label_batch: int = LABEL_BATCH):
    """A literal pool for predicts, and labeled batches whose labels come
    from a second random "teacher" machine (as the launcher makes them)."""
    rng = np.random.default_rng(seed + 1)
    pool = rng.integers(0, 2, (n_pool, cfg.n_literals), dtype=np.int8)
    _, teacher = build_tm(cfg.n_classes, cfg.n_clauses, cfg.n_features,
                          density=DENSITY, seed=seed + 2)
    rows = rng.integers(0, n_pool, (n_updates, label_batch))
    labels = oracle(cfg, teacher, pool)[0]
    return pool, [(pool[r], labels[r]) for r in rows]


def oracle(cfg, state, lits) -> tuple[np.ndarray, np.ndarray]:
    """(prediction, class_sums) of the ``oracle`` backend."""
    res = get_engine("oracle", cfg, state).infer(jnp.asarray(lits))
    return np.asarray(res.prediction), np.asarray(res.class_sums)


def _same(res, want, rows) -> bool:
    return (np.array_equal(np.asarray(res.prediction), want[0][rows])
            and np.array_equal(np.asarray(res.class_sums), want[1][rows]))


async def _predicts(server, pool, n: int, rng, max_rows: int):
    """``n`` concurrent predicts of 1..max_rows pool rows → (rows, results)."""
    rows = [rng.integers(0, len(pool), rng.integers(1, max_rows + 1))
            for _ in range(n)]
    results = await asyncio.gather(*[server.submit(pool[r]) for r in rows])
    return rows, results


def _check_served(rows, results, wants, what: str) -> None:
    """Each result must equal ``oracle`` under one of ``wants``' states."""
    for r, res in zip(rows, results):
        if not any(_same(res, w, r) for w in wants):
            raise AssertionError(f"{what}: a served answer differs from "
                                 f"oracle")


def _check_no_errors(server, what: str) -> dict:
    s = server.stats()
    if s["errors"]:
        raise AssertionError(f"{what}: stats() counts {s['errors']} errors")
    return s


def _assert_state(got, want, what: str) -> None:
    if not np.array_equal(np.asarray(got.ta), np.asarray(want.ta)):
        raise AssertionError(f"{what}: states differ")


def check_backends(cfg, state, lits) -> list[str]:
    """Every registered backend's ``infer`` equals ``oracle`` on ``lits``;
    on a TPU the Pallas backends must hold a TPU kernel."""
    on_tpu = jax.devices()[0].platform == "tpu"
    want = oracle(cfg, state, lits)
    every = np.arange(len(lits))
    x = jnp.asarray(lits)
    names = available_backends()
    for name in names:
        engine = get_engine(name, cfg, state)
        if not _same(engine.infer(x), want, every):
            raise AssertionError(f"backend {name} differs from oracle")
        if on_tpu and name in KERNEL_BACKENDS:
            hlo = jax.jit(engine.infer).lower(x).compile().as_text()
            if "tpu_custom_call" not in hlo:
                raise AssertionError(f"backend {name} compiled without a "
                                     f"TPU kernel")
    return names


def replay(cfg, state, backend: str, seed: int, batches) -> list:
    """Offline replay of a server's update key chain → states v0..vN."""
    engine = get_train_engine(backend, cfg)
    chain = jax.random.key(seed)
    states = [state]
    for lits, labels in batches:
        chain, k = jax.random.split(chain)
        states.append(engine.step(states[-1], k, jnp.asarray(lits),
                                  jnp.asarray(labels)))
    return states


async def serve_and_learn(cfg, state, *, seed: int = 0,
                          n_predicts: int = N_PREDICTS) -> dict:
    """The one-chip phases: serve, check backends, learn, checkpoint and
    restore → counters for the report.  Raises on any mismatch."""
    rng = np.random.default_rng(seed + 5)
    pool, batches = make_traffic(cfg, seed=seed)
    policy = ServePolicy(max_batch=MAX_BATCH)
    report = {}
    server = TMServer(cfg, state, policy, train_backend="fused",
                      train_seed=seed)
    async with server:
        t0 = time.perf_counter()
        await server.warmup(train_batches=(LABEL_BATCH,))
        report["warmup_s"] = time.perf_counter() - t0
        report["routing"] = server.stats()["routing"]

        want0 = oracle(cfg, state, pool)
        rows, results = await _predicts(server, pool, n_predicts, rng,
                                        MAX_BATCH)
        _check_served(rows, results, [want0], "serve")

        report["backends"] = check_backends(cfg, state, pool[:MAX_BATCH])

        # learn while serving: predicts overlap the labeled updates
        async def learn():
            return [await server.submit_labeled(x, y) for x, y in batches]

        updates = asyncio.ensure_future(learn())
        rows, during = await _predicts(server, pool, n_predicts // 2, rng,
                                       MAX_BATCH)
        versions = await updates
        if versions != list(range(1, len(batches) + 1)):
            raise AssertionError(f"update versions {versions}")
        states = replay(cfg, state, "reference", seed, batches)
        _assert_state(server.state, states[-1],
                      "fused updates vs reference replay")
        _check_served(rows, during, [oracle(cfg, s, pool) for s in states],
                      "serve while learning")
        want = oracle(cfg, states[-1], pool)
        rows, after = await _predicts(server, pool, n_predicts // 2, rng,
                                      MAX_BATCH)
        _check_served(rows, after, [want], "serve after learning")

        with tempfile.TemporaryDirectory() as ckpt_dir:
            server.checkpoint(ckpt_dir)
            fresh = TMServer(cfg, state, policy, train_backend="fused")
            if fresh.restore(ckpt_dir) != len(batches):
                raise AssertionError("restored the wrong version")
        _assert_state(fresh.state, server.state, "restore")
        async with fresh:
            again = await asyncio.gather(*[fresh.submit(pool[r])
                                           for r in rows])
        for a, b in zip(after, again):
            if not _same(a, (np.asarray(b.prediction),
                             np.asarray(b.class_sums)), slice(None)):
                raise AssertionError("restored server answers differently")
        _check_no_errors(fresh, "restored server")
    s = _check_no_errors(server, "server")
    report.update(requests=s["requests"], errors=s["errors"],
                  updates=s["updates"], state_version=s["state_version"],
                  engine_cache_hits=s["engine_cache"]["hits"])
    return report


async def mesh_phases(cfg, state, *, n_devices: int, seed: int = 0,
                      n_predicts: int = N_PREDICTS // 2,
                      n_updates: int = 4) -> dict:
    """The four-chip phases: ``ShardedEngine`` serving and the ``sharded``
    trainer on an ``n_devices`` mesh, then a checkpoint restored onto one
    device → counters for the report.  Raises on any mismatch."""
    rng = np.random.default_rng(seed + 6)
    pool, batches = make_traffic(cfg, seed=seed, n_updates=n_updates + 1)
    fused = replay(cfg, state, "fused", seed, batches)
    policy = ServePolicy(max_batch=MAX_BATCH)
    mesh = data_mesh(n_devices)
    report = {"mesh_devices": n_devices}
    with tempfile.TemporaryDirectory() as ckpt_dir:
        server = TMServer(cfg, state, policy, mesh=mesh,
                          train_backend="sharded", train_seed=seed,
                          checkpoint_dir=ckpt_dir)
        async with server:
            t0 = time.perf_counter()
            await server.warmup(train_batches=(LABEL_BATCH,))
            report["warmup_s"] = time.perf_counter() - t0
            rows, results = await _predicts(server, pool, n_predicts, rng,
                                            MAX_BATCH)
            _check_served(rows, results, [oracle(cfg, state, pool)],
                          "sharded serve")
            out = server.engine_for(MAX_BATCH).infer(
                jnp.asarray(pool[:MAX_BATCH]))
            report["serve_devices"] = len(out.prediction.sharding.device_set)
            for x, y in batches[:n_updates]:
                await server.submit_labeled(x, y)
            _assert_state(server.state, fused[n_updates],
                          f"sharded D={n_devices} vs fused D=1")
            report["state_devices"] = len(server.state.ta.sharding.device_set)
            rows, results = await _predicts(server, pool, n_predicts, rng,
                                            MAX_BATCH)
            _check_served(rows, results, [oracle(cfg, fused[n_updates], pool)],
                          "sharded serve after learning")
        s = _check_no_errors(server, "sharded server")
        for k in ("serve_devices", "state_devices"):
            if report[k] != n_devices:
                raise AssertionError(f"{k}: arrays on {report[k]} of "
                                     f"{n_devices} devices")

        single = TMServer(cfg, state, policy)
        single.restore(ckpt_dir, mesh=1)
        async with single:
            await single.submit_labeled(*batches[n_updates])
            _assert_state(single.state, fused[n_updates + 1],
                          f"restored at D=1 from D={n_devices}, resumed")
            rows, results = await _predicts(single, pool, n_predicts, rng,
                                            MAX_BATCH)
            _check_served(rows, results,
                          [oracle(cfg, fused[n_updates + 1], pool)],
                          "restored D=1 serve")
        _check_no_errors(single, "restored D=1 server")
    report.update(requests=s["requests"], updates=s["updates"])
    return report


def count_cache_events(counts: dict):
    """Count the persistent cache's hits and misses into ``counts`` →
    the registered listener."""
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listener(event: str, **_) -> None:
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listener)
    return listener


def main(argv: list[str] | None = None) -> int:
    """Run the phases on the TPU → exit status (see module docstring)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phases, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    cache_events = {"hits": 0, "misses": 0}
    count_cache_events(cache_events)
    print(f"smoke: device {dev.platform} {dev.device_kind} x{len(devices)};"
          f" compile cache {cache_dir}")

    t0 = time.perf_counter()
    cfg, state = build_machine(seed=args.seed)
    print(f"smoke: {ARCH} C={cfg.n_classes} M={cfg.n_clauses} "
          f"F={cfg.n_features} density={DENSITY}")
    if args.chips == 4:
        report = asyncio.run(mesh_phases(cfg, state, n_devices=4,
                                         seed=args.seed))
    else:
        report = asyncio.run(serve_and_learn(cfg, state, seed=args.seed))
    report["wall_s"] = time.perf_counter() - t0
    report["compile_cache"] = cache_events
    for k, v in report.items():
        print(f"smoke: {k} = {v}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
