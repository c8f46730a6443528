"""Stage B and device time of each inference backend at one benchmark
configuration's widths, on the chip.

    PYTHONPATH=src python -m benchmarks.engine_width --config tm-imdb-10k \
        --bucket 64 --backends sparse_csr swar_fused mxu_fused

The machine and its rows are the benchmark's own (``bench/reference.py``,
from ``--seed``; rows flipped at ``--noise``).  For each backend, the
engine a ``TMServer`` pinned to it serves the bucket with
(``engine_for``: ``sparse_csr`` on the server's slack-padded ELL
layout, the kernels with the tiles ``get_engine`` picks), then:

- ``exact``: ``--check-batches`` batches of ``--bucket`` rows through
  ``infer_padded`` on host literals, equal (prediction and class sums) to
  the ``oracle`` engine and to ``bench.reference.infer``;
- ``stage_b_ms``: the median of ``--iters`` calls of ``infer_padded`` on
  host literals with the numpy views taken, one at a time: the server's
  stage B at pipeline depth 1;
- ``device_ms``: the device's busy time per batch, from a profiler trace
  of ``--iters`` ``infer_packed`` calls on device-resident literals, and
  the three longest device ops.

One JSON line per backend, then one with the route the density heuristic
would take (a server with no pinned backend).
TPU only: it exits 2 without one.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:           # the benchmark's package
    sys.path.insert(0, str(ROOT))

from bench import harness, reference, trace                 # noqa: E402
from repro.core.tm import TMState                           # noqa: E402
from repro.engine import get_engine, infer_padded           # noqa: E402
from repro.serve import ServePolicy, TMServer, route_buckets  # noqa: E402


def build(name: str, cfg, state: TMState, bucket: int):
    """The engine a server pinned to ``name`` serves ``bucket`` with."""
    server = TMServer(cfg, state, ServePolicy(max_batch=bucket,
                                              backend=name))
    return server.engine_for(bucket)


def exact(engine, oracle, ta, batches, n_states: int, bucket: int) -> bool:
    for lits in batches:
        got = infer_padded(engine, lits, bucket)
        want = infer_padded(oracle, lits, bucket)
        pred, sums = reference.infer(ta, lits, n_states=n_states)
        for p, s in ((np.asarray(want.prediction),
                      np.asarray(want.class_sums)), (pred, sums)):
            if not (np.array_equal(np.asarray(got.prediction), p)
                    and np.array_equal(np.asarray(got.class_sums), s)):
                return False
    return True


def stage_b_ms(engine, batches, bucket: int, iters: int) -> float:
    times = []
    for i in range(iters):
        lits = batches[i % len(batches)]
        t0 = time.perf_counter()
        res = infer_padded(engine, lits, bucket)
        np.asarray(res.prediction), np.asarray(res.class_sums)
        times.append(time.perf_counter() - t0)
    return statistics.median(times) * 1e3


def device_ms(engine, batches, iters: int) -> tuple[float, list]:
    dev = [jnp.asarray(b) for b in batches]
    jax.block_until_ready(engine.infer_packed(dev[0]))
    with tempfile.TemporaryDirectory() as log_dir:
        jax.profiler.start_trace(log_dir)
        with jax.profiler.TraceAnnotation("window"):
            out = [engine.infer_packed(dev[i % len(dev)])
                   for i in range(iters)]
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        red = trace.reduce(trace.load(log_dir))
    top = [[k, v / iters * 1e3] for k, v in list(red.op_s.items())[:3]]
    return red.busy_s / iters * 1e3, top


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", default="tm-imdb-10k")
    ap.add_argument("--bucket", type=int, default=64)
    ap.add_argument("--backends", nargs="+",
                    default=["sparse_csr", "swar_fused", "mxu_fused"])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--noise", type=float, default=0.003)
    ap.add_argument("--check-batches", type=int, default=8)
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("engine_width: JAX found no TPU", file=sys.stderr)
        return 2
    harness.enable_cache()
    cfg_file = json.loads((ROOT / "bench" / "configs"
                           / f"{args.config}.json").read_text())
    cfg = harness.tm_config(cfg_file)
    seeds = harness.seeds(args.seed)
    ta, proto = reference.make_machine(cfg_file, seeds["machine"])
    pool, _ = reference.make_pool(proto, seeds["pool"],
                                  args.bucket * args.check_batches,
                                  args.noise)
    batches = [pool[i:i + args.bucket]
               for i in range(0, len(pool), args.bucket)]
    state = TMState(ta=ta)
    oracle = get_engine("oracle", cfg, state)
    for name in args.backends:
        t0 = time.perf_counter()
        engine = build(name, cfg, state, args.bucket)
        build_s = time.perf_counter() - t0
        ok = exact(engine, oracle, ta, batches, cfg.n_states, args.bucket)
        busy_ms, top = device_ms(engine, batches, args.iters)
        print(json.dumps({
            "config": args.config, "backend": name, "bucket": args.bucket,
            "exact": ok, "build_s": build_s,
            "stage_b_ms": stage_b_ms(engine, batches, args.bucket,
                                     args.iters),
            "device_ms": busy_ms, "top_ops_ms": top,
            "tiles": list(getattr(engine, "_blocks", ())),
            "device": jax.devices()[0].device_kind}), flush=True)
    print(json.dumps({"config": args.config, "route": route_buckets(
        cfg, state, (1, args.bucket))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
