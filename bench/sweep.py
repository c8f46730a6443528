"""Find the knee of an open-loop cell once, on the chip.

    python3 bench/sweep.py --workload mnist100-poisson --seed 1 \
        --seconds 20 --rates 700,800,900,1000 --repeats 2

Runs the cell's traffic at each offered rate in turn, ``--repeats`` passes
over the rates, against one server built and warmed as the cell's runs
build it (:func:`bench.harness.warm_server`), and prints one JSON line
per rate and pass: p50 and p95 latency from the scheduled send, the
answered rate, how late the generator ran, and whether the backlog grew
(the last quarter's median latency over the first's).  A rate holds where
its p95 stays within the predict deadline (``--deadline-ms``, 30 by
default), the backlog does not grow (that ratio under 2), and at least 97%
of the offered rate is answered inside the window.  A window in which
the generator's 99th-percentile lateness passes :data:`STALL_MS` is
marked ``stalled``: the whole process stood still, which
says nothing of the rate, so it is neither held nor failed.  The knee is
the highest rate at and below which every window that was not stalled
held, in every pass.  The
cells' rates are written into their traffic files by hand from this
output; the benchmark's runs never sweep.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np                                            # noqa: E402

from bench import harness, readers                            # noqa: E402
from bench.traffic import Window                              # noqa: E402


STALL_MS = 250.0
"""Generator lateness (p99) above which a window counts as stalled."""


def measure(win: Window, rate: float, seconds: float,
            deadline_ms: float) -> dict:
    """One rate's row from its window."""
    p = win.predicts
    done = p.answered()
    done = done[np.argsort(p.t_sched[done], kind="stable")]
    lat = p.t_done[done] - p.t_sched[done]
    q = max(1, len(lat) // 4)
    growth = float(np.median(lat[-q:]) / np.median(lat[:q]))
    answered = len(p.answered_in_window(seconds)) / seconds
    p95 = readers.nearest_rank(lat, 0.95) * 1e3
    late = float(np.percentile(p.lateness_s(), 99)) * 1e3
    return {"rate": rate, "sent": p.n, "answered_per_s": answered,
            "p50_ms": readers.nearest_rank(lat, 0.50) * 1e3,
            "p95_ms": p95, "backlog_growth": growth,
            "lateness_p99_ms": late, "stalled": late > STALL_MS,
            "unanswered": win.unanswered(), "failed": win.failed(),
            "holds": bool(p95 <= deadline_ms and growth < 2.0
                          and answered >= 0.97 * rate
                          and not win.unanswered())}


def knee_of(rows: list[dict]) -> float | None:
    """The highest rate at and below which every row held, stalled rows
    left out."""
    knee = None
    for rate in sorted({r["rate"] for r in rows}):
        if not all(r["holds"] for r in rows
                   if r["rate"] == rate and not r.get("stalled")):
            break
        knee = rate
    return knee


async def sweep(cfg, traffic, rates, seconds, seed, deadline_ms, repeats):
    seeds_ = harness.seeds(seed)
    ta, pool, labels, _ = harness.prepare(cfg, traffic, seeds_)
    rows = []
    async with harness.warm_server(cfg, traffic, ta, pool,
                                   seeds_) as server:
        rng = np.random.default_rng(seeds_["traffic"])
        for rep in range(repeats):
            for rate in rates:
                mix = dict(traffic,
                           predict=dict(traffic["predict"], rate=rate))
                win = Window(pool, labels, submit=server.submit)
                await win.run(mix, seconds, rng)
                row = dict(measure(win, rate, seconds, deadline_ms),
                           repeat=rep)
                rows.append(row)
                print(json.dumps(row), flush=True)
                await asyncio.sleep(1.0)
    return knee_of(rows)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--deadline-ms", type=float, default=30.0)
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell, cfg, traffic = harness.resolve(spec, args.workload)
    try:
        harness.require_devices(int(cell["chips"]))
    except harness.NoChip as exc:
        print(f"sweep: {exc}", file=sys.stderr)
        return 2
    harness.enable_cache()
    t0 = time.perf_counter()
    knee = asyncio.run(sweep(cfg, traffic,
                             [float(r) for r in args.rates.split(",")],
                             args.seconds, args.seed, args.deadline_ms,
                             args.repeats))
    print(json.dumps({"knee": knee, "wall_s": time.perf_counter() - t0}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
