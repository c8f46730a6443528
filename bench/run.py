"""Run one benchmark cell once, on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  The cell, its configuration, its traffic
mix and its metrics are read from ``BENCHMARK.json`` and the files it
names under ``bench/``.  Lines before the last on standard output are
notes (compilations inside the window, how late the generator ran); the
last line is one JSON object with ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` in a traced run), and, last,
``checks``: each compared number with its limit.  The same numbers are
the last lines on standard error.

It exits 2, before any result, when JAX finds no TPU or fewer chips than
the cell asks for, and 1 on any other fault.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                               # noqa: E402
import json                                                   # noqa: E402
import sys                                                    # noqa: E402
from pathlib import Path                                      # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, work                               # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = harness.load_spec()
    cell, cfg, traffic = harness.resolve(spec, args.workload)
    try:
        devices = harness.require_devices(int(cell["chips"]))
    except harness.NoChip as exc:
        print(f"bench: {exc}", file=sys.stderr)
        return 2
    import repro.serve  # noqa: F401  the system under test, or fail here
    kind = devices[0].device_kind
    peak = work.peaks(kind)
    cache_dir = harness.enable_cache()
    cache_events = {"hits": 0, "misses": 0}
    harness.count_cache_events(cache_events)
    print(f"bench: {args.workload} on {devices[0].platform} {kind} "
          f"x{len(devices)}; compile cache {cache_dir}", flush=True)

    line, checks, notes = harness.run_cell(
        spec, args.workload, cfg, traffic, seed=args.seed,
        seconds=args.seconds, traced=bool(args.trace), t_start=T_START,
        device={"platform": devices[0].platform, "kind": kind,
                "count": len(devices)},
        cache_events=cache_events, peak=peak)
    for note in notes:
        print(f"bench: {note}")
    sys.stdout.flush()
    for name, (value, limit) in checks.items():
        print(f"check {name} {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
