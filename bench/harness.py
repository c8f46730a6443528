"""One run of one benchmark cell: set-up, the measured window, the check
and the metrics.

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name in ``BENCHMARK.json``: ``configs/<config>.json``,
``traffic/<traffic>.json`` and ``metrics/<metric>.py`` (a module with
``read(run) -> float | None``).  Nothing here lists cells or metrics.

The system under test is ``repro.serve.TMServer`` from ``src/``: the
window drives its ``submit`` (and ``submit_labeled`` where the mix
learns).  The check compares a sample of the served answers, drawn from
the seed once the window has closed, with :mod:`bench.reference` under a
state version current between the request's send and its answer, and the
learned state with the reference's replay of the server's key chain.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import reference, trace
from bench.traffic import Window

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- the specification --------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def resolve(spec: dict, workload: str) -> tuple[dict, dict, dict]:
    """(cell entry, configuration dict, traffic dict) of ``workload``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return cell, cfg, traffic


def metrics_for(spec: dict, workload: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics, or with ``traced`` its per-layer
    ones: those that list the cell, or that list no cells and move (or
    are) an end-to-end metric the cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read


# -- the device and the compile cache -----------------------------------

def require_devices(chips: int):
    """The TPU devices; raises :class:`NoChip` without a TPU or with fewer
    than ``chips`` of them (there is no CPU fallback)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform "
                     f"{devices[0].platform!r}); the benchmark runs on "
                     f"the chip only")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devices)}")
    return devices


def enable_cache(root: Path = ROOT) -> str:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout: ``$JAX_COMPILATION_CACHE_DIR`` where it points inside it,
    else ``<checkout>/.jax_cache``."""
    import jax
    path = root / ".jax_cache"
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env and Path(env).resolve().is_relative_to(root.resolve()):
        path = Path(env)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(path)


def count_cache_events(counts: dict):
    """Count the persistent cache's hits and misses into ``counts`` (each
    is one compile request of the process) → the registered listener."""
    import jax
    names = {"/jax/compilation_cache/cache_hits": "hits",
             "/jax/compilation_cache/cache_misses": "misses"}

    def listener(event: str, **_) -> None:
        if event in names:
            counts[names[event]] += 1

    jax.monitoring.register_event_listener(listener)
    return listener


def seeds(seed: int) -> dict[str, int]:
    """Independent 31-bit seeds for each use, from any whole ``seed``."""
    words = np.random.SeedSequence(abs(int(seed)), spawn_key=(
        int(seed < 0),)).generate_state(5, np.uint32)
    keys = ("machine", "pool", "traffic", "train", "check")
    return {k: int(w) >> 1 for k, w in zip(keys, words)}


# -- one run ------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers from
    it."""
    cfg: dict
    window: Window
    seconds: float
    setup_s: float
    nnz: int
    peak: dict | None
    stats0: dict
    stats1: dict
    reduced: trace.Reduced | None = None
    window_compiles: int = 0


def tm_config(cfg: dict):
    """The program's ``TMConfig`` for a configuration file."""
    from repro.core.tm import TMConfig
    return TMConfig(n_classes=int(cfg["n_classes"]),
                    n_clauses=int(cfg["n_clauses"]),
                    n_features=int(cfg["n_features"]),
                    n_states=int(cfg["n_states"]), T=int(cfg["T"]),
                    s=float(cfg["s"]))


def warm_buckets(traffic: dict, policy) -> list[int]:
    """The buckets this mix can fill: coalesced batches hold from the
    smallest request's rows up to ``max_batch``."""
    from repro.serve import bucket_for
    buckets = policy.resolved_buckets()
    lo = int(traffic["predict"]["rows"]["min"])
    return sorted({bucket_for(n, buckets)
                   for n in range(lo, policy.max_batch + 1)})


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _gc_timer(pauses: list):
    """A ``gc.callbacks`` entry appending (generation, seconds) of each
    collection to ``pauses``."""
    started = [0.0]

    def timer(phase: str, info: dict) -> None:
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            pauses.append((info["generation"],
                           time.perf_counter() - started[0]))
    return timer


def spans(traced: bool):
    """``span(name)``: a profiler annotation in a traced run, else
    nothing."""
    if not traced:
        return lambda name: contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation


@contextlib.asynccontextmanager
async def warm_server(cfg: dict, traffic: dict, ta, pool, seeds_: dict,
                      mark=lambda phase: None):
    """The server a cell times, started and warmed: built from the
    configuration (its serve policy and trainer, the key chain from the
    seed), with the buckets the mix can fill and, where it learns, its
    train step compiled.  ``mark(phase)`` is called once the server is
    built ("server") and once it is warm ("warm")."""
    from repro.core.tm import TMState
    from repro.engine import infer_padded
    from repro.serve import ServePolicy, TMServer

    policy = ServePolicy(**cfg["serve_policy"])
    learn = traffic.get("learn")
    server = TMServer(tm_config(cfg), TMState(ta=ta), policy,
                      train_backend=cfg["train_backend"],
                      train_seed=seeds_["train"])
    mark("server")
    zeros = np.zeros((1, pool.shape[1]), np.int8)
    async with server:
        for bucket in warm_buckets(traffic, policy):
            np.asarray(infer_padded(server.engine_for(bucket), zeros,
                                    bucket).prediction)
        if learn:
            await server.warmup(train_batches=(int(learn["rows"]),))
        mark("warm")
        yield server


async def serve(cfg: dict, traffic: dict, ta, pool, labels, *, seeds_: dict,
                seconds: float, traced: bool, t_start: float,
                trace_dir: str | None, cache_events: dict) -> dict:
    """Build the server, warm it, run the window → what the check and the
    metrics need.  The server is stopped and dropped before it returns; a
    trace started as the window opened is left running for the check."""
    import jax

    learn = traffic.get("learn")
    out: dict = {"phases": {}}

    def mark(phase: str) -> None:
        out["phases"][phase] = time.perf_counter() - t_start

    async with warm_server(cfg, traffic, ta, pool, seeds_, mark) as server:
        stats0 = server.stats()
        before = dict(cache_events)
        rng = np.random.default_rng(seeds_["traffic"])

        pauses: list[tuple[int, float]] = []
        gc_timer = _gc_timer(pauses)

        def on_open():
            out["setup_s"] = time.perf_counter() - t_start
            gc.callbacks.append(gc_timer)
            if traced:
                jax.profiler.start_trace(trace_dir,
                                         profiler_options=_profile_options())

        win = Window(pool, labels, submit=server.submit,
                     submit_labeled=server.submit_labeled if learn else None,
                     version=lambda: server.state_version,
                     span=spans(traced))
        try:
            await win.run(traffic, seconds, rng, on_open=on_open,
                          span_window=traced)
        except BaseException:
            if traced and "setup_s" in out:
                jax.profiler.stop_trace()
            raise
        finally:
            if gc_timer in gc.callbacks:
                gc.callbacks.remove(gc_timer)
        out["gc_pauses"] = pauses
        out["window_compiles"] = sum(cache_events.values()) - \
            sum(before.values())
        out["stats0"], out["stats1"] = stats0, server.stats()
        out["window"] = win
        out["final_ta"] = np.asarray(server.state.ta)
        out["final_version"] = server.state_version
        stats = jax.devices()[0].memory_stats() or {}
        out["memory_peak_bytes"] = stats.get("peak_bytes_in_use")
    del server
    gc.collect()
    return out


def check(cfg: dict, traffic: dict, ta0, pool, labels, win: Window,
          final_ta, final_version: int, *, train_seed: int, check_seed: int,
          vote_bits: int = 0, draw_dtype=None) -> dict:
    """The compared numbers → ``{name: (value, limit)}``.

    ``wrong_rows``: sampled served rows whose prediction or class sums
    differ from the reference under every state version current between
    the request's send and its answer.  ``unanswered``: requests with no
    answer a minute past the close.  Where the mix learns,
    ``version_gaps``: applied updates whose versions are not 1..N in
    order, and ``state_mismatch``: TA entries of the served state that
    differ from the reference's replay.  Every limit is 0: the answers
    are exact.  ``vote_bits``/``draw_dtype`` run the reference at the
    control's lower precision.
    """
    import jax.numpy as jnp
    n = int(cfg["n_states"])
    p = win.predicts
    answered = p.answered()
    sample = answered[:0]
    if len(answered):
        longest = answered[np.argmax(p.size[answered])]
        order = answered[np.random.default_rng(check_seed).permutation(
            len(answered))]
        order = np.concatenate([[longest], order[order != longest]])
        before = np.cumsum(p.size[order]) - p.size[order]
        sample = order[before < int(traffic["check_rows"])]
    rows = int(p.size[sample].sum())
    u = win.updates
    updates = u.answered()
    updates = updates[np.argsort(u.value[updates], kind="stable")]
    versions = u.value[updates]
    gaps = int(np.sum(versions != np.arange(1, len(versions) + 1))) \
        + abs(final_version - len(versions))
    matched = np.zeros(len(sample), bool)

    def match(state, version):
        due = np.flatnonzero(~matched & (p.v_lo[sample] <= version)
                             & (p.v_hi[sample] >= version))
        if not len(due):
            return
        reqs = sample[due]
        at = np.concatenate([np.arange(p.start[i], p.start[i] + p.size[i])
                             for i in reqs])
        pred, sums = reference.infer(state, pool[p.idx[at]], n_states=n,
                                     vote_bits=vote_bits)
        same = (p.prediction[at] == pred) \
            & np.all(p.class_sums[at] == sums, axis=1)
        starts = np.cumsum(p.size[reqs]) - p.size[reqs]
        matched[due] = np.logical_and.reduceat(same, starts)

    match(ta0, 0)
    state = ta0
    batches = ((pool[u.rows_of(i)], labels[u.rows_of(i)])
               for i in updates)
    for v, state in enumerate(reference.replay(
            cfg, ta0, train_seed, batches,
            draw_dtype=draw_dtype or jnp.float32), start=1):
        match(state, v)
    out = {"wrong_rows": (int(p.size[sample[~matched]].sum()), 0),
           "unanswered": (win.unanswered(), 0)}
    if traffic.get("learn"):
        out["version_gaps"] = (gaps, 0)
        out["state_mismatch"] = (
            int(np.sum(np.asarray(state) != np.asarray(final_ta))), 0)
    out["checked_rows"] = (rows, None)
    return out


def prepare(cfg: dict, traffic: dict, seeds_: dict):
    """(ta on the device, pool literals, pool labels, nnz): the bench's
    own weights and inputs, from the seed."""
    ta, proto = reference.make_machine(cfg, seeds_["machine"])
    pool, labels = reference.make_pool(proto, seeds_["pool"],
                                       int(traffic["pool_rows"]),
                                       float(traffic["noise"]))
    nnz = int(np.sum(np.asarray(ta) > int(cfg["n_states"])))
    return ta, pool, labels, nnz


def run_cell(spec: dict, workload: str, cfg: dict, traffic: dict, *,
             seed: int, seconds: float, traced: bool, t_start: float,
             device: dict, cache_events: dict, peak: dict | None
             ) -> tuple[dict, dict, list[str]]:
    """One run after the device check → (result line, compared numbers,
    earlier lines)."""
    seeds_ = seeds(seed)
    t_begin = time.perf_counter() - t_start
    ta, pool, labels, nnz = prepare(cfg, traffic, seeds_)
    t_made = time.perf_counter() - t_start
    with contextlib.ExitStack() as stack:
        trace_dir = (stack.enter_context(tempfile.TemporaryDirectory())
                     if traced else None)
        got = asyncio.run(serve(cfg, traffic, ta, pool, labels,
                                seeds_=seeds_, seconds=seconds,
                                traced=traced, t_start=t_start,
                                trace_dir=trace_dir,
                                cache_events=cache_events))
        win = got["window"]
        try:
            with spans(traced)("check"):
                checks = check(cfg, traffic, ta, pool, labels, win,
                               got["final_ta"], got["final_version"],
                               train_seed=seeds_["train"],
                               check_seed=seeds_["check"])
        finally:
            if traced:
                import jax
                jax.profiler.stop_trace()
        events = trace.load(trace_dir) if traced else None
    run = Run(cfg=cfg, window=win, seconds=seconds,
              setup_s=got["setup_s"], nnz=nnz, peak=peak,
              stats0=got["stats0"], stats1=got["stats1"],
              reduced=(trace.reduce(events, *trace.DEVICE_LINES[
                  device["platform"]]) if traced else None),
              window_compiles=got["window_compiles"])
    metrics = {}
    for m in metrics_for(spec, workload, traced):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = dict(device, memory_peak_bytes=got["memory_peak_bytes"])
    correct = all(v <= lim for v, lim in checks.values() if lim is not None)
    line = {"correct": bool(correct),
            "attempted": win.attempted(),
            "failed": win.failed(), "metrics": metrics, "device": device}
    if traced:
        red = run.reduced
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        line["breakdown"] = {
            "device_ops": [[k, v] for k, v in list(red.op_s.items())[:10]],
            "idle_gaps": [[k, v] for k, v in red.gaps[:10]]}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    late = win.lateness_s()
    full = [t for g, t in got["gc_pauses"] if g == 2]
    notes = [
        "gc_in_window collections {} full {} full_max_ms {:.3f} "
        "total_ms {:.3f}".format(
            len(got["gc_pauses"]), len(full), max(full, default=0) * 1e3,
            sum(t for _, t in got["gc_pauses"]) * 1e3),
        f"window_compiles {run.window_compiles}",
        "generator_lateness_ms p50 {:.4f} p99 {:.4f} max {:.4f}".format(
            *(np.percentile(late, [50, 99, 100]) * 1e3 if len(late)
              else (0.0, 0.0, 0.0))),
        f"requests {win.predicts.n} updates {win.updates.n} "
        f"unanswered {win.unanswered()} failed {win.failed()}",
        f"routing {run.stats1['routing']}",
        f"nnz {nnz} setup_s {run.setup_s:.3f}: device found at "
        f"{t_begin:.3f}, weights and pool made at {t_made:.3f}, server "
        f"built at {got['phases']['server']:.3f}, warm at "
        f"{got['phases']['warm']:.3f}",
    ]
    return line, checks, notes
