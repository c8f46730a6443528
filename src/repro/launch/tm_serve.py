"""TM serving launcher: micro-batching scheduler under synthetic traffic.

Builds a trained-density TM at the requested shape, warms up every
(engine, bucket) pair, then drives the :class:`repro.serve.TMServer`
with an in-process open-loop (Poisson arrivals) or closed-loop
(``--clients`` lockstep callers) traffic source, printing periodic stats:
queue depth, batch fill, and p50/p99 latency.

    PYTHONPATH=src python -m repro.launch.tm_serve --rate 2000 --duration 10
    PYTHONPATH=src python -m repro.launch.tm_serve --clients 64 --duration 5
    PYTHONPATH=src python -m repro.launch.tm_serve --backend sparse_csr \
        --max-batch 128 --max-wait-us 500
    PYTHONPATH=src python -m repro.launch.tm_serve --train-backend fused \
        --label-rate 20 --label-batch 32        # serve + learn concurrently

Backpressure is visible live: at arrival rates beyond engine throughput,
``qdepth`` pins at ``--queue-depth`` and open-loop arrivals block in
``submit`` instead of growing an unbounded backlog.

``--shed-backend cascade`` arms the overload tier: batches dispatched
while the queue holds ≥ ``--shed-qdepth`` waiting items route to the
exact early-exit cascade (``exact_sums=False`` — predictions bit-exact,
wide-margin rows skip the remainder pass) instead of the bucket's routed
backend.  The live line then shows ``shed=`` (batches shed so far) and
``esc=`` (the cascade's escalation rate), and the final summary reports
the tier split plus the engine-cache hit/miss/eviction counters:

    PYTHONPATH=src python -m repro.launch.tm_serve --rate 20000 \
        --shed-backend cascade --shed-qdepth 64

``--train-backend`` opts into online learning: a label feeder submits
``--label-rate`` labeled batches per second (labels from a fixed random
"teacher" TM, so the served machine genuinely adapts) interleaved with
the predict traffic, and the stats line shows the state version climbing
while predict latency stays bounded.

State lifecycle (docs/operations.md is the operator runbook):

    PYTHONPATH=src python -m repro.launch.tm_serve --train-backend packed \
        --checkpoint-dir /tmp/tm-ckpt --checkpoint-every 50 \
        --probe-every 20                 # snapshot + drift-monitor
    # kill it mid-run, then resume from the newest valid snapshot:
    PYTHONPATH=src python -m repro.launch.tm_serve --train-backend packed \
        --checkpoint-dir /tmp/tm-ckpt --restore

``--checkpoint-every N`` snapshots ``(version, state, key-chain cursor,
train backend + its opts)`` every N applied updates off the worker
thread (``--checkpoint-keep`` newest retained); ``--restore`` resumes
the deterministic update chain bit-exactly from the newest valid step.
``--probe-every N`` scores a held-out teacher-labeled probe stream every
N updates; the live line then shows ``acc=``/``drift=`` next to the
version, which is the launcher view of drift monitoring.

SLO traffic (PR 7, docs/serving.md): ``--deadline-us N`` attaches an
N-microsecond completion deadline to predict requests; ``--priority-mix
P`` carries the deadline on fraction ``P`` of them (priority 0) and
submits the rest best-effort (priority 1), so EDF ordering and the
priority tiers are both exercised.  The live line gains ``miss=`` (the
running deadline-miss rate) and ``adm=`` (admission-control rejects);
``--pipeline-depth`` sets how many dispatched batches may be in flight
(1 = the legacy serial scheduler — useful for A/B):

    PYTHONPATH=src python -m repro.launch.tm_serve --rate 20000 \
        --deadline-us 5000 --priority-mix 0.8 --pipeline-depth 2

Multi-tenant fleet (docs/serving.md "Multi-tenant fleets"): ``--models
MANIFEST.json`` serves many named models behind one scheduler via
:class:`repro.serve.TMFleet`.  The manifest is a JSON list of model
entries; every field except ``name`` is optional and defaults to the
matching CLI flag, so same-shape tenants (which the fleet packs into
one fused serving plane) need only names and seeds:

    [{"name": "mnist", "seed": 0},
     {"name": "kws", "seed": 1, "weight": 4.0},
     {"name": "big", "clauses": 512, "train_backend": "packed",
      "checkpoint_dir": "/tmp/tm-ckpt-big"}]

Recognised per-model keys: ``name``, ``classes``/``clauses``/
``features``/``density``/``seed`` (shape), ``weight`` (static engine-
cache eviction weight; omitted → measured request share), plus any
``TMServer`` lifecycle keyword (``train_backend``, ``train_seed``,
``checkpoint_dir``, ``checkpoint_every_updates``, ``checkpoint_keep``,
``history_size``).  ``--cache-entries`` / ``--cache-bytes`` set the
shared engine-cache budget, ``--no-pack`` disables cross-model batch
packing (the A/B control), and traffic is split across tenants:
closed-loop ``--clients`` are distributed round-robin, open-loop
``--rate`` is divided evenly.

    PYTHONPATH=src python -m repro.launch.tm_serve \
        --models fleet.json --clients 16 --duration 10

Multi-host data parallelism (docs/operations.md "Multi-host serving"):
``--mesh N`` shards every serving batch and (with ``--train-backend
sharded``) every labeled update across N devices on a 1-D ``data`` mesh
— post-update states stay bit-identical to the single-host run for any
N.  ``--host-devices N`` simulates an N-device host on CPU (sets
``XLA_FLAGS`` before the first JAX import, so it must come from this
flag or the environment — never after jax loads).  ``--ckpt-role``
selects the checkpoint discipline for multi-process launches sharing
one directory: the ``leader`` (default) writes snapshots as usual;
a ``follower`` never writes — it waits for the leader's first valid
``.complete`` marker, restores it, and serves:

    # leader: train + write checkpoints on a simulated 8-device mesh
    PYTHONPATH=src python -m repro.launch.tm_serve --host-devices 8 \
        --mesh 8 --train-backend sharded \
        --checkpoint-dir /tmp/tm-ckpt --checkpoint-every 50
    # follower on another host (any mesh size — restore is elastic):
    PYTHONPATH=src python -m repro.launch.tm_serve --host-devices 4 \
        --mesh 4 --ckpt-role follower --checkpoint-dir /tmp/tm-ckpt
"""

from __future__ import annotations

import argparse
import asyncio
import os
import sys
import time

import numpy as np


def build_tm(c: int, m: int, f: int, *, density: float, seed: int):
    """A TM at trained-machine include density (the serving-relevant
    regime: ~5% of literals included per clause)."""
    import jax.numpy as jnp
    from repro.core.tm import TMConfig, TMState
    cfg = TMConfig(n_classes=c, n_clauses=m, n_features=f)
    rng = np.random.default_rng(seed)
    ta = np.where(rng.random((c, m, cfg.n_literals)) < density,
                  cfg.n_states + 1, cfg.n_states)
    return cfg, TMState(ta=jnp.asarray(ta, dtype=jnp.int32))


async def _stats_printer(server, every: float) -> None:
    """Print one live stats line per ``every`` seconds until cancelled."""
    t0 = time.monotonic()
    prev = 0
    while True:
        await asyncio.sleep(every)
        s = server.stats()
        rps = (s["requests"] - prev) / every
        prev = s["requests"]
        learn = (f"  ver={s['state_version']}" if s["updates"] or
                 s["state_version"] else "")
        probe = s["probe"]
        if probe is not None and probe["accuracy"] is not None:
            learn += (f"  acc={probe['accuracy']:.3f}"
                      f"  drift={probe['drift']:+.3f}")
        ckpt = s["checkpoint"]
        if ckpt is not None and ckpt["last_step"] is not None:
            learn += f"  ckpt@{ckpt['last_step']}"
        tiers = s["tiers"]
        if tiers["shed_backend"] is not None or tiers["cascade_rows"]:
            learn += f"  shed={tiers['shed_batches']}"
            if tiers["cascade_rows"]:
                learn += f"  esc={tiers['escalation_rate']:.2f}"
        dl = s["deadline"]
        if dl["requests"] or dl["admission_rejects"]:
            learn += (f"  miss={dl['miss_rate']:.3f}"
                      f"  adm={dl['admission_rejects']}")
        print(f"[t+{time.monotonic() - t0:5.1f}s] {rps:8.0f} req/s  "
              f"qdepth={s['qdepth']:4d}  "
              f"fill={s['batch_fill']:.2f}  "
              f"mean_batch={s['mean_batch_rows']:.1f}  "
              f"p50={s['p50_ms']:.2f}ms  p99={s['p99_ms']:.2f}ms{learn}",
              flush=True)


async def _label_feeder(server, pool, labels, *, rate: float, batch: int,
                        rng, fed: dict) -> None:
    """Offer ``rate`` labeled batches/s (Poisson) until cancelled,
    counting them in ``fed["batches"]``.

    Fire-and-forget: awaiting each update would cap the offered rate at
    update throughput; instead pending futures accumulate against the
    server's bounded queue (backpressure), like open-loop predicts.
    """
    pending: set[asyncio.Task] = set()
    next_t = time.monotonic()
    while True:
        next_t += rng.exponential(1.0 / rate)
        delay = next_t - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        rows = rng.integers(0, len(pool), batch)
        task = asyncio.ensure_future(
            server.submit_labeled(pool[rows], labels[rows]))
        fed["batches"] += 1
        pending.add(task)

        def _done(t: asyncio.Task) -> None:
            pending.discard(t)
            if not t.cancelled():
                t.exception()       # retrieve: no 'never retrieved' noise

        task.add_done_callback(_done)


class _ModelClient:
    """Adapter giving one fleet member the ``server.submit`` surface the
    load generators drive, so the same loops hammer a named model."""

    def __init__(self, fleet, name: str):
        self._fleet = fleet
        self._name = name

    async def submit(self, literals, *, client=None, **kwargs):
        return await self._fleet.submit(self._name, literals,
                                        client=client, **kwargs)


def _load_manifest(path: str, args) -> dict:
    """Parse a ``--models`` JSON manifest → TMFleet spec dict.

    Unspecified shape fields fall back to the CLI flags, so a manifest
    of bare ``{"name": ..., "seed": ...}`` entries yields same-shape
    tenants that pack into one fused serving plane."""
    import json
    with open(path) as fh:
        manifest = json.load(fh)
    if not isinstance(manifest, list):
        raise SystemExit(f"--models {path}: expected a JSON list of "
                         f"model entries, got {type(manifest).__name__}")
    specs = {}
    for i, ent in enumerate(manifest):
        ent = dict(ent)
        try:
            name = ent.pop("name")
        except KeyError:
            raise SystemExit(f"--models {path}: entry {i} has no 'name'")
        if name in specs:
            raise SystemExit(f"--models {path}: duplicate model "
                             f"name {name!r}")
        cfg, state = build_tm(ent.pop("classes", args.classes),
                              ent.pop("clauses", args.clauses),
                              ent.pop("features", args.features),
                              density=ent.pop("density", args.density),
                              seed=ent.pop("seed", args.seed))
        # whatever remains (weight + TMServer lifecycle keywords) rides
        # through the spec dict verbatim — TMFleet._build_model pops
        # 'weight' and hands the rest to the member TMServer
        if ent.get("train_backend") and "train_seed" not in ent:
            ent["train_seed"] = args.seed
        specs[name] = {"cfg": cfg, "state": state, **ent}
    return specs


async def _fleet_stats_printer(fleet, every: float) -> None:
    """One aggregate live line per ``every`` seconds until cancelled."""
    t0 = time.monotonic()
    prev = 0
    while True:
        await asyncio.sleep(every)
        s = fleet.stats()
        total = sum(m["requests"] for m in s["models"].values())
        rps = (total - prev) / every
        prev = total
        worst = max((m["p99_ms"] for m in s["models"].values()
                     if m["p99_ms"] is not None), default=0.0)
        cache = s["engine_cache"]
        hits = cache["hits"] + cache["misses"]
        print(f"[t+{time.monotonic() - t0:5.1f}s] {rps:8.0f} req/s  "
              f"models={len(s['models'])}  groups={len(s['groups'])}  "
              f"worst_p99={worst:.2f}ms  "
              f"cache_hit={cache['hits'] / max(hits, 1):.3f}",
              flush=True)


async def _run_fleet(args) -> list[str]:
    """``--models`` mode: serve a manifest of named models as a fleet,
    splitting the requested traffic across tenants → failure messages."""
    from repro.serve import ServePolicy, TMFleet, closed_loop, open_loop

    specs = _load_manifest(args.models, args)
    policy = ServePolicy(max_batch=args.max_batch,
                         max_wait_us=args.max_wait_us,
                         queue_depth=args.queue_depth,
                         backend=args.backend,
                         shed_backend=args.shed_backend,
                         shed_qdepth=args.shed_qdepth,
                         pipeline_depth=args.pipeline_depth)
    fleet = TMFleet(specs, policy, pack=not args.no_pack,
                    cache_entries=args.cache_entries or None,
                    cache_bytes=args.cache_bytes or None,
                    mesh=args.mesh or None)
    names = fleet.model_names()
    pools = {}
    for i, name in enumerate(names):
        cfg = fleet.server_for(name).cfg
        rng = np.random.default_rng(args.seed + 10_000 + i)
        pools[name] = rng.integers(0, 2, (1024, cfg.n_literals),
                                   dtype=np.int8)
    async with fleet:
        s = fleet.stats()
        print(f"fleet: {len(names)} models, {len(s['groups'])} pack "
              f"group(s)" + ("" if not s["groups"] else "  " + "  ".join(
                  f"[{'+'.join(g['members'])}: "
                  f"{g['fused_classes']} fused classes]"
                  for g in s["groups"])))
        t0 = time.monotonic()
        await fleet.warmup()
        print(f"warmup in {time.monotonic() - t0:.2f}s")

        printer = asyncio.ensure_future(
            _fleet_stats_printer(fleet, args.stats_every))
        t0 = time.monotonic()
        if args.clients:
            # round-robin split, every tenant gets at least one caller
            per = [max(1, args.clients // len(names)
                       + (1 if i < args.clients % len(names) else 0))
                   for i in range(len(names))]
            served = sum(await asyncio.gather(*[
                closed_loop(_ModelClient(fleet, name), pools[name],
                            clients=n, duration=args.duration)
                for name, n in zip(names, per)]))
            mode = f"closed-loop x{args.clients} over {len(names)} models"
        else:
            rate = args.rate / len(names)
            served = sum(await asyncio.gather(*[
                open_loop(_ModelClient(fleet, name), pools[name],
                          rate=rate, duration=args.duration,
                          rng=np.random.default_rng(args.seed + 20_000 + i))
                for i, name in enumerate(names)]))
            mode = (f"open-loop {args.rate:.0f}/s over {len(names)} "
                    f"models")
        wall = time.monotonic() - t0
        printer.cancel()

        s = fleet.stats()
        print(f"\n{mode}: {served} requests in {wall:.2f}s "
              f"({served / wall:,.0f} req/s aggregate)")
        for name in names:
            m = s["models"][name]
            plane = (f"group {m['group']} seg {m['segment']}"
                     if m["packed"] else "solo")
            print(f"  {name:>12}: {m['requests']:6d} req  "
                  f"p50={m['p50_ms'] or 0:.2f}ms  "
                  f"p99={m['p99_ms'] or 0:.2f}ms  v{m['version']}  "
                  f"weight={m['weight']:.3f}  errors={m['errors_total']}  "
                  f"[{plane}]")
        cache = s["engine_cache"]
        print(f"engine cache: {cache['hits']} hits  {cache['misses']} "
              f"misses  {cache['evictions']} evictions  "
              f"{cache['superseded']} superseded  "
              f"(size {cache['size']}/{cache['maxsize']}, "
              f"{cache['bytes']} bytes)")
    errors = sum(m["errors_total"] for m in fleet.stats()["models"].values())
    return [f"{errors} failed requests or updates"] if errors else []


async def _run(args) -> list[str]:
    """Serve (and learn) for ``--duration`` → failure messages, empty
    when the run was clean."""
    from repro.serve import ServePolicy, TMServer, closed_loop, open_loop

    if args.models:
        return await _run_fleet(args)

    cfg, state = build_tm(args.classes, args.clauses, args.features,
                          density=args.density, seed=args.seed)
    policy = ServePolicy(max_batch=args.max_batch,
                         max_wait_us=args.max_wait_us,
                         queue_depth=args.queue_depth,
                         backend=args.backend,
                         shed_backend=args.shed_backend,
                         shed_qdepth=args.shed_qdepth,
                         pipeline_depth=args.pipeline_depth)
    rng = np.random.default_rng(args.seed + 1)
    pool = rng.integers(0, 2, (4096, cfg.n_literals), dtype=np.int8)

    labels = None
    probe = None
    if args.train_backend:
        # labels from a fixed random "teacher" machine: the served TM has
        # something consistent to adapt toward while it serves
        import jax.numpy as jnp
        from repro.engine import get_engine
        _, teacher = build_tm(args.classes, args.clauses, args.features,
                              density=args.density, seed=args.seed + 2)
        labels = np.asarray(get_engine("oracle", cfg, teacher)
                            .infer(jnp.asarray(pool)).prediction)
        if args.probe_every:
            # held-out probe stream: fresh rows the label feeder never
            # submits, teacher-labeled — accuracy against it is the
            # launcher's drift monitor
            probe_lits = np.random.default_rng(args.seed + 4).integers(
                0, 2, (args.probe_size, cfg.n_literals), dtype=np.int8)
            probe_y = np.asarray(get_engine("oracle", cfg, teacher)
                                 .infer(jnp.asarray(probe_lits)).prediction)
            probe = (probe_lits, probe_y)

    follower = args.ckpt_role == "follower"
    if follower and not args.checkpoint_dir:
        raise SystemExit("--ckpt-role follower needs --checkpoint-dir")
    server = TMServer(cfg, state, policy,
                      train_backend=args.train_backend or None,
                      train_seed=args.seed,
                      checkpoint_dir=None if follower
                      else args.checkpoint_dir,
                      checkpoint_every_updates=0 if follower
                      else args.checkpoint_every,
                      checkpoint_keep=args.checkpoint_keep,
                      history_size=args.history_size,
                      probe=probe, probe_every_updates=args.probe_every,
                      mesh=args.mesh or None)
    if follower:
        # followers never write to the shared directory — they wait for
        # the leader's atomic rename to land a ``.complete`` marker,
        # then restore (elastically, onto whatever --mesh this host has)
        from repro import checkpoint as ckpt
        step = ckpt.wait_for_complete(args.checkpoint_dir,
                                      timeout=args.ckpt_wait)
        version = server.restore(args.checkpoint_dir)
        print(f"follower: restored step_{step} from {args.checkpoint_dir} "
              f"at state version {version} (read-only)")
    elif args.restore:
        if not args.checkpoint_dir:
            raise SystemExit("--restore needs --checkpoint-dir")
        version = server.restore()
        print(f"restored from {args.checkpoint_dir} at state version "
              f"{version} (resuming the deterministic update chain)")
    fed = {"batches": 0}
    async with server:
        print(f"TM C={cfg.n_classes} M={cfg.n_clauses} F={cfg.n_features} "
              f"density={args.density}  buckets={server.buckets}")
        print(f"routing: {server.stats()['routing']}")
        t0 = time.monotonic()
        await server.warmup(train_batches=(args.label_batch,)
                            if args.train_backend else ())
        print(f"warmup: {len(server.buckets)} buckets compiled in "
              f"{time.monotonic() - t0:.2f}s")

        printer = asyncio.ensure_future(
            _stats_printer(server, args.stats_every))
        feeder = None
        if args.train_backend:
            feeder = asyncio.ensure_future(
                _label_feeder(server, pool, labels, rate=args.label_rate,
                              batch=args.label_batch,
                              rng=np.random.default_rng(args.seed + 3),
                              fed=fed))
        rejects = []
        slo = dict(deadline_us=args.deadline_us or None,
                   deadline_fraction=args.priority_mix,
                   on_reject=lambda row, exc: rejects.append(row))
        t0 = time.monotonic()
        if args.clients:
            served = await closed_loop(server, pool,
                                       clients=args.clients,
                                       duration=args.duration, **slo)
        else:
            served = await open_loop(server, pool, rate=args.rate,
                                     duration=args.duration, rng=rng,
                                     **slo)
        wall = time.monotonic() - t0
        printer.cancel()
        if feeder is not None:
            feeder.cancel()

        s = server.stats()
        mode = (f"closed-loop x{args.clients}" if args.clients
                else f"open-loop {args.rate:.0f}/s")
        learn = (f"  state_version={s['state_version']} "
                 f"({s['update_rows']} labeled rows)"
                 if args.train_backend else "")
        print(f"\n{mode}: {served} requests in {wall:.2f}s "
              f"({served / wall:,.0f} req/s)  "
              f"batches={s['batches']}  fill={s['batch_fill']:.2f}  "
              f"p50={s['p50_ms']:.2f}ms  p99={s['p99_ms']:.2f}ms{learn}")
        if args.deadline_us:
            dl = s["deadline"]
            print(f"deadline {args.deadline_us}us (mix "
                  f"{args.priority_mix:.2f}, pipeline depth "
                  f"{args.pipeline_depth}): {dl['requests']} deadline "
                  f"requests, {dl['misses']} missed "
                  f"(rate {dl['miss_rate']:.3f}); "
                  f"{len(rejects)} rejected at admission; "
                  f"{dl['slack_shed_batches']} batches slack-shed")
        if s["checkpoint"] is not None:
            c = s["checkpoint"]
            print(f"checkpoints: dir={c['dir']}  last_step={c['last_step']}"
                  f"  restored_from={c['restored_from']}  "
                  f"history={s['history']['versions']}")
        if s["probe"] is not None and s["probe"]["accuracy"] is not None:
            p = s["probe"]
            print(f"drift probe: acc={p['accuracy']:.3f}  "
                  f"best={p['best']:.3f}  drift={p['drift']:+.3f}  "
                  f"({p['evals']} evals, last at v{p['at_version']})")
        tiers, cache = s["tiers"], s["engine_cache"]
        if tiers["shed_backend"] is not None:
            print(f"shed tier ({tiers['shed_backend']}, qdepth≥"
                  f"{tiers['shed_qdepth']}): {tiers['shed_batches']} "
                  f"batches / {tiers['shed_rows']} rows shed; "
                  f"escalated {tiers['escalated_rows']}/"
                  f"{tiers['cascade_rows']} rows "
                  f"(rate {tiers['escalation_rate']:.3f})")
        print(f"engine cache: {cache['hits']} hits  {cache['misses']} "
              f"misses  {cache['evictions']} evictions  "
              f"(size {cache['size']}/{cache['maxsize']})")

    # read after stop(): it drains the queued updates, whose failures
    # count too
    s = server.stats()
    failures = []
    if s["errors"]:
        failures.append(f"{s['errors']} failed requests or updates")
    if fed["batches"] and not s["updates"]:
        failures.append(f"--train-backend {args.train_backend} applied none "
                        f"of {fed['batches']} labeled batches")
    return failures


def main(argv: list[str] | None = None) -> None:
    """CLI entry point: parse flags, stand up the server, drive traffic
    (see the module docstring for the flag reference and the lifecycle
    workflows; docs/operations.md for the operator runbook).  ``argv``
    overrides ``sys.argv`` (the smoke tests drive it in-process).  Exits
    with status 1 when any request or update failed, or when
    the label feeder offered batches and none was applied."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--clauses", type=int, default=100)
    ap.add_argument("--features", type=int, default=196)
    ap.add_argument("--density", type=float, default=0.05,
                    help="include density (trained machines ≈ 0.05)")
    ap.add_argument("--backend", default=None,
                    help="pin one backend (default: route per bucket)")
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--max-wait-us", type=int, default=2000)
    ap.add_argument("--queue-depth", type=int, default=1024)
    ap.add_argument("--shed-backend", default=None,
                    help="overload-tier backend (typically 'cascade'): "
                         "batches shed here when qdepth crosses "
                         "--shed-qdepth")
    ap.add_argument("--shed-qdepth", type=int, default=0,
                    help="queue depth at dispatch that triggers shedding "
                         "(0 = shed every batch when --shed-backend set)")
    ap.add_argument("--train-backend", default=None,
                    help="TrainEngine name (reference/packed/fused): serve "
                         "and learn concurrently from a label feeder")
    ap.add_argument("--label-rate", type=float, default=10.0,
                    help="labeled feedback batches per second")
    ap.add_argument("--label-batch", type=int, default=32,
                    help="rows per labeled feedback batch")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="persist lifecycle snapshots here (see "
                         "docs/operations.md)")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="async snapshot every N applied updates "
                         "(0 = only on graceful stop; needs "
                         "--checkpoint-dir)")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="newest valid snapshots retained on disk")
    ap.add_argument("--restore", action="store_true",
                    help="resume from the newest valid snapshot in "
                         "--checkpoint-dir before serving")
    ap.add_argument("--mesh", type=int, default=0,
                    help="data-parallel mesh size: shard serving batches "
                         "(and 'sharded' training) over N devices on a "
                         "1-D 'data' mesh (0 = unsharded)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="simulate an N-device host on CPU (sets "
                         "XLA_FLAGS=--xla_force_host_platform_device_"
                         "count before the first jax import; 0 = leave "
                         "the environment alone)")
    ap.add_argument("--ckpt-role", choices=("leader", "follower"),
                    default="leader",
                    help="multi-process checkpoint discipline for a "
                         "shared --checkpoint-dir: the leader writes, "
                         "a follower waits for a valid snapshot, "
                         "restores it, and never writes")
    ap.add_argument("--ckpt-wait", type=float, default=60.0,
                    help="follower: seconds to wait for the leader's "
                         "first valid checkpoint before giving up")
    ap.add_argument("--history-size", type=int, default=8,
                    help="bounded in-memory ring of recent (version, "
                         "state) rollback targets")
    ap.add_argument("--probe-every", type=int, default=0,
                    help="score the held-out probe stream every N "
                         "applied updates (0 = off; needs "
                         "--train-backend)")
    ap.add_argument("--probe-size", type=int, default=256,
                    help="rows in the held-out drift probe stream")
    ap.add_argument("--models", default=None, metavar="MANIFEST.json",
                    help="serve a JSON manifest of named models as a "
                         "TMFleet (see the module docstring for the "
                         "format; shape fields default to the flags "
                         "above)")
    ap.add_argument("--no-pack", action="store_true",
                    help="fleet mode: disable cross-model batch packing "
                         "(every tenant serves solo — the A/B control)")
    ap.add_argument("--cache-entries", type=int, default=0,
                    help="fleet mode: shared engine-cache entry budget "
                         "(0 = leave the process default)")
    ap.add_argument("--cache-bytes", type=int, default=0,
                    help="fleet mode: shared engine-cache byte budget "
                         "(0 = unlimited)")
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="dispatched batches in flight at once "
                         "(1 = legacy serial scheduler)")
    ap.add_argument("--deadline-us", type=int, default=0,
                    help="per-request completion deadline in us "
                         "(0 = no deadlines)")
    ap.add_argument("--priority-mix", type=float, default=1.0,
                    help="fraction of requests carrying the deadline at "
                         "priority 0; the rest go best-effort at "
                         "priority 1")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="open-loop Poisson arrival rate (req/s)")
    ap.add_argument("--clients", type=int, default=0,
                    help="closed-loop concurrent callers (0 → open loop)")
    ap.add_argument("--duration", type=float, default=5.0)
    ap.add_argument("--stats-every", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.host_devices:
        # XLA only reads this at backend init — it must land before the
        # first jax import anywhere in the process
        if "jax" in sys.modules:
            raise SystemExit(
                "--host-devices: jax is already imported; set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N in the "
                "environment instead")
        flag = ("--xla_force_host_platform_device_count="
                f"{args.host_devices}")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    if args.mesh and args.mesh < 1:
        raise SystemExit("--mesh must be >= 1")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    failures = asyncio.run(_run(args))
    if failures:
        print("tm_serve: FAILED: " + "; ".join(failures), file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
