"""Read the check's numbers for the program and for its control, on the
chip, at a cell's own size and load.

    python3 bench/control.py --workload mnist100-poisson --seconds 3 \
        --seeds 101,102,103

For each seed, one process runs the cell's traffic for ``--seconds``
against the program, then compares what was served twice: with the
reference (the program's readings, which set each limit's lower end) and
with the control, the reference at the next lower precision put in the
program's place (the upper end):

- votes counted in a 4-bit wrapping register instead of int32 (the served
  answers: ``wrong_rows``);
- feedback uniforms compared in bfloat16 instead of float32 (the learned
  state: ``state_mismatch``).

Since the program's answers equal the reference's, comparing them with
the control reads the same gap as comparing the control with the
reference.  One JSON line per seed and side.  The benchmark's own runs do
not run this.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness                                     # noqa: E402

def readings(cfg: dict, traffic: dict, *, seed: int,
             seconds: float) -> dict:
    """{"program": checks, "control": checks} of one seed."""
    import jax.numpy as jnp
    seeds_ = harness.seeds(seed)
    ta, pool, labels, _ = harness.prepare(cfg, traffic, seeds_)
    got = asyncio.run(harness.serve(
        cfg, traffic, ta, pool, labels, seeds_=seeds_, seconds=seconds,
        traced=False, t_start=0.0, trace_dir=None,
        cache_events={"hits": 0, "misses": 0}))
    out = {}
    for side, opts in (("program", {}),
                       ("control", {"vote_bits": 4,
                                    "draw_dtype": jnp.bfloat16})):
        out[side] = harness.check(
            cfg, traffic, ta, pool, labels, got["window"], got["final_ta"],
            got["final_version"], train_seed=seeds_["train"],
            check_seed=seeds_["check"], **opts)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell, cfg, traffic = harness.resolve(spec, args.workload)
    try:
        harness.require_devices(int(cell["chips"]))
    except harness.NoChip as exc:
        print(f"control: {exc}", file=sys.stderr)
        return 2
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        for side, checks in readings(cfg, traffic, seed=seed,
                                     seconds=args.seconds).items():
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "side": side,
                              **{k: v for k, (v, _) in checks.items()}}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
