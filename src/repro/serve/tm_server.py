"""TM serving: async micro-batching scheduler over the VoteEngine registry.

The paper's inference core (popcount + argmax) is embarrassingly
batchable, but *requests* arrive one at a time — variable-size,
asynchronous, bursty.  Like the paper's asynchronous time-domain design,
throughput here comes from decoupling arrival from evaluation:

- :class:`ServePolicy` — the batching knobs: coalesce waiting requests
  until ``max_batch`` rows are gathered or ``max_wait_us`` has elapsed
  since the batch opened, bounded backpressure at ``queue_depth``.
- bucketing — each coalesced batch pads (``repro.engine.pad_batch``,
  all-zero neutral rows that provably cannot flip any real row's argmax)
  to the smallest configured bucket that fits, so XLA compiles one
  ``infer`` per (engine, bucket) instead of one per request size.
- routing — each bucket maps to a backend name (:func:`route_buckets`):
  an explicit choice, a measured route recorded in the autotune cache by
  ``benchmarks/serve_bench.py --update-routing``, or the include-density
  heuristic from the README.  Engines come from ``get_engine``, so
  buckets sharing a backend share one cached engine (and tuned tiles).
  Heuristic routes *re-resolve on every state publish*: online learning
  drifts include density, and a route picked from the initial state
  would silently go stale (the pre-fix bug) — each publish also
  refreshes the server's incremental ELL layout by include deltas
  (O(changed rows), no from-scratch CSR rebuild), prebuilds the
  ``sparse_csr`` engine for the newest state from it, and evicts the
  superseded state's engines from the keyed cache.  Explicit
  ``routing=`` tables and ``policy.backend`` stay pinned.

**Pipelined dispatch** (``pipeline_depth``, default 2) — the hot path is
a three-stage pipeline instead of one serial loop:

- *Stage A (host, event loop)*: coalesce the next batch and assemble its
  padded numpy buffer.  Assembly buffers are double-buffered (one
  reusable buffer per pipeline slot), so stage A writes slot ``k+1``
  while the device still reads slot ``k``.
- *Stage B (device)*: the engine call runs on a single worker thread;
  up to ``pipeline_depth`` batches are in flight (a semaphore bounds
  them), so host assembly of batch ``k+1`` overlaps compute of ``k``.
  The result's copy back is asked for at dispatch, so it queues behind
  the device's work; an engine with ``infer_packed`` returns the whole
  result in one array, so a batch comes back in one transfer
  (``stats()['result_fetch']`` counts the batches by path).
- *Stage C (fan-out)*: a dedicated coroutine consumes a FIFO completion
  queue and resolves per-request futures — awaiting clients never sit
  behind assembly of the next batch.  The worker thread is serial, so
  completion order equals dispatch order and the exactly-once,
  in-order-per-client contract is preserved bit-exactly.

The *scoreboard*: states are immutable and every request is pinned to
the ``(version, state)`` pair current at arrival, so the classic
read-after-write hazard ("a predict pinned to v overlaps the publish of
v+1") needs only bookkeeping, never a stall — ``stats()['pipeline']``
shows the in-flight count per state version.  The one true pipeline
barrier is update-after-update: labeled updates serialize on their own
training thread (one in flight), while independent predict batches keep
flowing around them.  At ``pipeline_depth=1`` the scheduler degenerates
to the exact legacy serial semantics (each batch is awaited to
completion before the next opens, updates quiesce predicts).

**Deadline scheduling** (SLO policy) — :meth:`submit` takes optional
``deadline_us`` / ``priority``:

- *EDF ordering*: waiting requests are served by ``(priority, absolute
  deadline, arrival seq)`` — earliest-deadline-first within a priority
  tier; traffic without deadlines degrades to pure FIFO.
- *admission control* (``admission_control``, default on), in two
  halves sharing one switch: at *submit*, a request whose deadline is
  below the fastest service time ever observed for its bucket
  (``stats()['buckets']`` min) *provably* cannot meet it — rejected
  immediately with :class:`~repro.serve.loadgen.DeadlineExceeded`; at
  *dispatch*, a queued request whose deadline has already passed is
  reaped the same way in O(1) (``stats()['deadline']
  ['expired_drops']``).  Under sustained overload the reap is what
  keeps compute flowing to requests that can still make their SLO
  instead of burning batches on answers nobody is waiting for.
- *slack shedding*: at dispatch, a batch whose tightest deadline is
  inside the bucket's EWMA service time routes to the shed tier (below)
  even when the queue is shallow — slack exhaustion and raw queue depth
  are independent overload signals.

- fan-out — results slice back per request; each request resolves
  exactly once via its own future.  A failing batch (bad routing entry,
  backend error) sets the exception on its own requests' futures only —
  the scheduler outlives engine errors.
- overload shedding (opt-in via ``shed_backend=``) — when the backlog is
  at least ``shed_qdepth`` deep at dispatch time (or a batch's slack is
  exhausted, see above), the batch routes to the shed tier's engine
  instead of the bucket's routed backend.  The intended tier is the
  exact early-exit ``cascade`` (:mod:`repro.engine.cascade`, built with
  ``exact_sums=False``): predictions stay provably bit-exact while
  wide-margin rows skip most clause work, so overload degrades
  *class-sum completeness* — never correctness.  ``shed_qdepth=0`` turns
  the tier into the permanent route.  Counters: :meth:`stats` ``tiers``.
- online learning (opt-in via ``train_backend=``) — :meth:`submit_labeled`
  enqueues labeled feedback batches.  Updates run a
  :mod:`repro.engine.train` ``TrainEngine`` step on a dedicated training
  thread (overlapping predict compute) and swap in the new state
  copy-on-write: JAX states are immutable, so the swap publishes a
  fully-built ``(version, state)`` pair atomically and a predict can
  never observe a half-applied update.  Each predict is pinned to the
  ``(version, state)`` current *when it arrived* — the batcher never
  mixes state versions in one batch, and results stay bit-exact against
  the state version they arrived under even while training runs
  concurrently.

- state lifecycle (``checkpoint_dir=``) — the learning state no longer
  dies with the process.  :meth:`checkpoint` snapshots ``(version,
  TMState, update-key-chain cursor, train backend + autotune picks)``
  through :mod:`repro.checkpoint` (atomic, sharded, ``.complete``-marked);
  ``checkpoint_every_updates=`` takes them periodically off the worker
  thread via ``save_async`` with ``gc_keep`` retention, and
  :meth:`restore` resumes a killed server bit-exactly — the restored key
  chain draws the same keys the uninterrupted run would have, so the
  replay contract survives the restart.  A bounded ring of recent
  ``(version, state)`` pairs (``history_size=``) keeps rollback targets
  and recent versions alive with bounded memory, and :meth:`rollback`
  re-publishes a historical or checkpointed state.  Drift monitoring
  (``probe=``, ``probe_every_updates=``) scores a held-out probe stream
  as the state advances and surfaces rolling accuracy/regression deltas
  in :meth:`stats`.  Operator procedures: docs/operations.md.

- tracing — every stage above records a ``jax.profiler`` span (the
  names in :data:`SPANS`; ``tm.engine_build`` nests in ``tm.publish``),
  so a profiler trace of a live server shows the host's stages on the
  same clock as the device's ops; each batch's spans carry its sequence
  number as ``batch=<n>``.  With the profiler
  off a span costs about a microsecond and adds no device work.
  ``stats()['queue_wait']`` counts the time requests waited between
  arrival and dispatch, ``stats()['engine_build']`` the publishes and
  the seconds of their serving builds.  What each span covers:
  docs/operations.md.

Ordering caveat: a single client with *multiple concurrently
outstanding* requests carrying different deadlines/priorities may see
them complete in EDF order rather than submission order — sequential
awaiters (the normal pattern, and all deadline-free traffic) keep exact
arrival order.

>>> async with TMServer(cfg, state, ServePolicy(max_batch=64),
...                     train_backend="packed") as srv:
...     result = await srv.submit(literals)       # (n, 2F) or (2F,)
...     result.prediction                         # (n,) int32
...     fast = await srv.submit(literals, deadline_us=5000, priority=0)
...     version = await srv.submit_labeled(literals, labels)
"""

from __future__ import annotations

import asyncio
import dataclasses
import heapq
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from repro.core.tm import TMConfig, TMState, include_mask
from repro.engine import (EngineResult, ServiceStats, available_backends,
                          engine_cache_info, evict_engines_for_state,
                          get_engine, infer_padded)
from repro.engine import autotune
from repro.engine.sparse import IncrementalEll

from .loadgen import DeadlineExceeded, percentiles_ms

__all__ = ["ServePolicy", "TMServer", "DeadlineExceeded", "SPANS",
           "AWAIT_SPANS", "bucket_for", "default_buckets", "route_buckets"]

_STOP = object()        # queue sentinel: wakes the scheduler for shutdown

# A host span in a profiler trace.  Keyword metadata is formatted only
# while a trace is being taken, so with the profiler off a span costs
# about a microsecond.
_span = jax.profiler.TraceAnnotation

#: The server's profiler spans, in pipeline order (docs/operations.md):
#: ``tm.idle`` (scheduler waiting with nothing pending), ``tm.coalesce``,
#: ``tm.pipeline_wait``, ``tm.assemble`` (stage A, event loop);
#: ``tm.stageB.dispatch``, ``tm.stageB.sync``, ``tm.stageB.copy``
#: (stage B, worker thread); ``tm.fanout`` (stage C); ``tm.publish``
#: (state swap and serving refresh) and ``tm.train_step`` (training
#: thread).  A batch's spans carry ``batch=<n>``, a publish
#: ``version=<n>``.  Inside ``tm.publish``, ``tm.engine_build`` covers
#: the serving build (``stats()["engine_build"]`` times it); it is not
#: listed here, as ``bench/spans.py`` mirrors this tuple name for name.
SPANS = ("tm.idle", "tm.coalesce", "tm.pipeline_wait", "tm.assemble",
         "tm.stageB.dispatch", "tm.stageB.sync", "tm.stageB.copy",
         "tm.fanout", "tm.publish", "tm.train_step")
#: The spans of :data:`SPANS` that cover the event loop awaiting work
#: (an arrival, the coalescing budget, a pipeline slot); every other
#: span covers work.
AWAIT_SPANS = ("tm.idle", "tm.coalesce", "tm.pipeline_wait")


def default_buckets(max_batch: int) -> tuple[int, ...]:
    """Powers of two up to (and always including) ``max_batch``."""
    buckets = []
    b = 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    return tuple(buckets)


def bucket_for(n: int, buckets: tuple[int, ...]) -> int:
    """Smallest configured bucket holding ``n`` rows; oversized batches
    round up to a multiple of the largest bucket (a rare extra shape
    beats failing the request)."""
    for b in buckets:
        if n <= b:
            return b
    top = buckets[-1]
    return ((n + top - 1) // top) * top


@dataclasses.dataclass(frozen=True)
class ServePolicy:
    """Micro-batching knobs.

    ``max_batch``: row budget per coalesced batch — a waiting request that
    would overflow it opens the *next* batch (requests are never split).
    ``max_wait_us``: how long an open batch may wait for more arrivals;
    0 dispatches every batch as soon as the queue momentarily drains.
    ``buckets``: padded shapes to compile for (``None`` → powers of two up
    to ``max_batch``).  ``queue_depth``: bound on waiting requests —
    ``submit`` awaits (backpressure) instead of growing an unbounded
    backlog; labeled updates get their own gate of the same depth so
    neither plane can starve the other.  ``backend``: pin every bucket to one backend; ``None``
    routes per bucket (measured routes, then density heuristic).

    ``shed_backend``: name of the overload tier's backend (``None`` turns
    shedding off).  A batch dispatched while the backlog holds at least
    ``shed_qdepth`` waiting items — or whose tightest deadline is inside
    the bucket's EWMA service time (slack exhaustion) — routes there
    instead of the bucket's normal backend; ``shed_qdepth=0`` sheds
    *every* batch (a pure latency tier).  ``shed_opts`` are forwarded to
    the tier engine's constructor; a ``cascade`` tier defaults to
    ``exact_sums=False`` — exact predictions, stage-1 class sums on
    early-exited rows.

    ``pipeline_depth``: how many dispatched batches may be in flight at
    once (assembly of batch ``k+1`` overlaps compute of ``k``); ``1``
    reproduces the legacy serial scheduler exactly.
    ``admission_control``: reject a request outright when its deadline is
    provably unmeetable — below the bucket's fastest observed service
    time at submit, or already expired while queued at dispatch —
    instead of serving a guaranteed miss.
    """

    max_batch: int = 64
    max_wait_us: int = 2000
    buckets: tuple[int, ...] | None = None
    queue_depth: int = 1024
    backend: str | None = None
    shed_backend: str | None = None
    shed_qdepth: int = 0
    shed_opts: dict | None = None
    pipeline_depth: int = 2
    admission_control: bool = True

    def __post_init__(self):
        if self.pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {self.pipeline_depth}")

    def resolved_buckets(self) -> tuple[int, ...]:
        """The sorted, deduplicated bucket shapes this policy compiles."""
        if self.buckets is not None:
            return tuple(sorted(set(self.buckets)))
        return default_buckets(self.max_batch)

    def resolved_shed_opts(self) -> dict:
        """Constructor opts for the shed tier engine.

        ``shed_opts`` wins; a ``cascade`` tier additionally defaults to
        ``exact_sums=False`` — the overload tier's whole point is to
        skip the remainder completion pass (predictions stay exact).
        """
        opts = dict(self.shed_opts or {})
        if self.shed_backend == "cascade":
            opts.setdefault("exact_sums", False)
        return opts


def route_buckets(cfg: TMConfig, state: TMState,
                  buckets: tuple[int, ...], *,
                  backend: str | None = None,
                  density: float | None = None) -> dict[int, str]:
    """bucket size → backend name.

    Priority per bucket: explicit ``backend`` > a measured route in the
    autotune cache (``autotune.serve_lookup``) > the README's density
    heuristic (trained machines are ~5% include-dense → ``sparse_csr``;
    dense/untrained → ``swar_packed``).  A measured route naming a
    backend that is no longer registered (stale cache from an older
    version) falls back to the heuristic, mirroring the stale-opts
    guard in ``autotune.lookup``.

    ``density`` short-circuits the include-mask reduction when the
    caller already knows the state's include density (the server's
    publish path computes it once for the layout refresh and the route
    re-resolution together).
    """
    if backend is not None:
        return {b: backend for b in buckets}
    from repro.engine import available_backends
    registered = set(available_backends())
    if density is None:
        density = float(np.asarray(include_mask(cfg, state)).mean())
    fallback = "sparse_csr" if density <= 0.10 else "swar_packed"
    routes = {}
    for b in buckets:
        measured = autotune.serve_lookup(cfg, b)
        routes[b] = measured if measured in registered else fallback
    return routes


class _Request:
    """A queued predict, pinned to the state version current at arrival.

    ``deadline`` is the absolute monotonic completion target (``None``
    for best-effort); ``priority`` orders tiers (lower serves first);
    ``seq`` is the arrival sequence number — the EDF heap orders by
    ``(priority, deadline, seq)``, so deadline-free traffic is FIFO.
    """

    __slots__ = ("lits", "n", "future", "t_in", "client", "version",
                 "state", "deadline", "priority", "seq")

    def __init__(self, lits, future, client, version, state, *,
                 deadline=None, priority=0, seq=0):
        self.lits = lits
        self.n = lits.shape[0]
        self.future = future
        self.t_in = time.monotonic()
        self.client = client
        self.version = version
        self.state = state
        self.deadline = deadline
        self.priority = priority
        self.seq = seq

    def sort_key(self):
        return (self.priority,
                self.deadline if self.deadline is not None else float("inf"),
                self.seq)


class _Update:
    """A queued labeled feedback batch (online-learning mode)."""

    __slots__ = ("lits", "labels", "future", "t_in")

    def __init__(self, lits, labels, future):
        self.lits = lits
        self.labels = labels
        self.future = future
        self.t_in = time.monotonic()


class TMServer:
    """Async micro-batching front end over one (cfg, state) TM.

    Use as an async context manager, or call :meth:`start` / :meth:`stop`
    explicitly.  :meth:`submit` awaits queue space (backpressure), then
    awaits the request's slice of a batched ``infer``.  One scheduler
    coroutine owns coalescing and assembly (stage A), a single worker
    thread owns JAX predict compute (stage B, up to
    ``policy.pipeline_depth`` batches in flight), and a fan-out
    coroutine resolves futures (stage C) — see the module docstring for
    the pipeline and the deadline/admission semantics.

    ``train_backend`` opts into online learning: :meth:`submit_labeled`
    feeds labeled batches through the named :mod:`repro.engine.train`
    backend on a dedicated training thread, and the served state
    advances through immutable, versioned copies.  ``train_seed`` seeds
    the server's update-key chain: update ``i`` uses ``split(chain)[1]``
    with ``chain = split(chain)[0]`` advanced each update, so a replay
    with the same seed and update order is bit-identical.

    Lifecycle knobs: ``checkpoint_dir`` names where :meth:`checkpoint` /
    :meth:`restore` persist snapshots; ``checkpoint_every_updates > 0``
    auto-snapshots asynchronously every that many applied updates
    (``checkpoint_keep`` newest retained on disk).  ``history_size``
    bounds the in-memory ring of recent ``(version, state)`` pairs that
    :meth:`rollback` draws from.  ``probe=(literals, labels)`` with
    ``probe_every_updates > 0`` scores the held-out probe stream every N
    applied updates (drift monitoring — see :meth:`stats` and
    docs/operations.md).

    ``mesh=`` (a 1-D ``jax.sharding.Mesh``, a device count, or ``None``)
    turns on data-parallel execution: stage-B bucket engines wrap in
    :class:`~repro.engine.sharding.ShardedEngine` over the mesh (predict
    *and* shed tiers, the prebuilt sparse slot included), and a
    ``train_backend="sharded"`` shards its update step over the same
    mesh.  Bit-exact vs the single-device server by the sharding
    contracts (``tests/test_multihost.py``); :meth:`restore` can
    retarget the mesh at restore time (elastic re-shard, see its
    docstring and docs/operations.md).
    """

    def __init__(self, cfg: TMConfig, state: TMState,
                 policy: ServePolicy | None = None, *,
                 routing: dict[int, str] | None = None,
                 mesh=None,
                 train_backend: str | None = None, train_seed: int = 0,
                 checkpoint_dir: str | None = None,
                 checkpoint_every_updates: int = 0,
                 checkpoint_keep: int = 3,
                 history_size: int = 8,
                 probe: tuple | None = None,
                 probe_every_updates: int = 0,
                 probe_window: int = 256,
                 latency_window: int = 4096,
                 on_publish=None,
                 executor: ThreadPoolExecutor | None = None):
        self.cfg = cfg
        # mesh= turns on data-parallel serving *and* training: stage-B
        # bucket engines wrap in ShardedEngine over this mesh, and a
        # "sharded" train backend shards its step over it.  Accepts a
        # 1-D jax Mesh, a device count (→ repro.distributed.data_mesh),
        # or None (single-device, the default).  Resolved before any
        # engine is built so the constructor publish already serves
        # sharded.
        self._mesh = None
        if mesh is not None:
            from jax.sharding import Mesh
            from repro.distributed.sharding import data_mesh
            self._mesh = mesh if isinstance(mesh, Mesh) else \
                data_mesh(int(mesh))
            if len(self._mesh.axis_names) != 1:
                raise ValueError(f"TMServer needs a 1-D mesh, got "
                                 f"{self._mesh.axis_names}")
        # one lock for every counter stats() reads: fan-out, the update
        # path and stats() itself all take it, so a stats() snapshot is
        # internally consistent (satellite: no more field-by-field reads
        # racing the worker thread)
        self._mu = threading.Lock()
        # (version, state): swapped as one tuple so concurrent readers
        # (submit on the event loop, stats) always see a matched pair —
        # _publish also appends the pair to the bounded history ring
        self._history: deque[tuple[int, TMState]] = deque(
            maxlen=max(1, int(history_size)))
        self.policy = policy or ServePolicy()
        self.buckets = self.policy.resolved_buckets()
        # routing re-resolves on every state publish, so density-heuristic
        # routes track include drift under online learning instead of
        # reflecting the initial state forever; an explicit routing= table
        # or policy.backend pins routes for the server's lifetime
        self._routing_pinned = (routing is not None
                                or self.policy.backend is not None)
        self.routing = dict(routing) if routing is not None else \
            route_buckets(cfg, state, self.buckets,
                          backend=self.policy.backend)
        self._n_routing_updates = 0
        # publish-path sparse serving maintenance: an IncrementalEll
        # mirror of the served state's include mask plus a one-slot
        # (state, engine) pair prebuilt for the newest state (EllLayout
        # holds jax arrays, so it can't key the global engine cache);
        # swapped as one tuple so lock-free readers see a matched pair
        self._serve_ell: IncrementalEll | None = None
        self._sparse_serving: tuple[TMState, object] | None = None
        # publishes and the seconds spent in their serving build
        # (tm.engine_build), over the server's life
        self._n_engine_builds = 0
        self._engine_build_s = 0.0
        # fleet seam: called as on_publish(version, state) after every
        # publish (including this constructor one); hook errors are
        # contained (counted, never propagated into the update path)
        self._on_publish = on_publish
        self._n_publish_hook_errors = 0
        self._publish(0, state)
        self._train_engine = None
        self._train_key = None
        self._train_backend = train_backend
        self._train_pool: ThreadPoolExecutor | None = None
        if train_backend is not None:
            from repro.engine import get_train_engine
            # a mesh-configured server shards its training too: the
            # sharded backend takes the mesh directly (Mesh is hashable,
            # so the engine caches normally); other backends are
            # single-device and ignore it
            topts = {"mesh": self._mesh} \
                if (self._mesh is not None
                    and train_backend == "sharded") else {}
            self._train_engine = get_train_engine(train_backend, cfg,
                                                  **topts)
            self._train_key = jax.random.key(train_seed)
            # updates get their own thread: a training step overlaps
            # predict compute (stage B) instead of serializing behind it
            self._train_pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="tm-serve-train")
        # -- lifecycle: checkpointing, rollback, drift probe ----------
        self._ckpt_dir = checkpoint_dir
        self._ckpt_every = int(checkpoint_every_updates)
        self._ckpt_keep = int(checkpoint_keep)
        if self._ckpt_every and checkpoint_dir is None:
            raise ValueError("checkpoint_every_updates needs checkpoint_dir=")
        self._ckpt_threads: list = []     # live save_async writer threads
        self._last_ckpt_version: int | None = None
        self._restored_from: int | None = None
        self._n_rollbacks = 0
        self._probe = None
        if probe is not None:
            lits, labels = probe
            lits = self._check_literals(lits)
            y = np.asarray(labels, dtype=np.int32).reshape(-1)
            if y.shape[0] != lits.shape[0]:
                raise ValueError(f"probe labels {y.shape} do not match "
                                 f"{lits.shape[0]} literal rows")
            self._probe = (lits, y)
        self._probe_every = int(probe_every_updates)
        if self._probe_every and self._probe is None:
            raise ValueError("probe_every_updates needs probe=(lits, labels)")
        self._probe_history: deque[tuple[int, float]] = deque(
            maxlen=probe_window)
        self._probe_best: float | None = None
        self._n_probe_evals = 0
        # -- queues + pipeline state ----------------------------------
        # the arrival queue is unbounded; the capacity semaphores are
        # the real backpressure bound — acquired by submit (predict
        # gate) / submit_labeled (update gate), released only when the
        # scheduler pops the item into a dispatched batch, so each
        # plane never exceeds queue_depth waiting items.  The gates are
        # separate on purpose: semaphore waiters are FIFO, so a
        # saturating predict flood sharing one gate would park every
        # labeled update behind the whole predict backlog
        self._queue: asyncio.Queue = asyncio.Queue()
        self._capacity = asyncio.Semaphore(self.policy.queue_depth)
        self._update_capacity = asyncio.Semaphore(self.policy.queue_depth)
        self._sem = asyncio.Semaphore(self.policy.pipeline_depth)
        self._completions: asyncio.Queue = asyncio.Queue()
        self._pending: list[tuple] = []            # EDF heap of predicts
        self._pending_updates: deque[_Update] = deque()
        self._get_task: asyncio.Task | None = None
        self._update_task: asyncio.Task | None = None
        self._fanout_task: asyncio.Task | None = None
        self._seq = 0
        self._batch_seq = 0               # the batch= number of the spans
        self._next_slot = 0
        self._asm_buffers: list[np.ndarray | None] = \
            [None] * self.policy.pipeline_depth
        self._inflight = 0
        self._inflight_versions: dict[int, int] = {}
        self._svc = ServiceStats()        # per-bucket service-time ring
        # executor= shares one device-worker thread across servers (the
        # fleet's single-device model); the server only shuts down a
        # pool it created itself
        self._owns_pool = executor is None
        self._pool = executor if executor is not None else \
            ThreadPoolExecutor(max_workers=1,
                               thread_name_prefix="tm-serve-infer")
        self._task: asyncio.Task | None = None
        self._closed = False
        self._stop_seen = False
        # stats (mutated under self._mu; snapshotted by stats())
        self._latencies: deque[float] = deque(maxlen=latency_window)
        self._n_requests = 0
        self._n_rows = 0
        self._n_batches = 0
        self._n_padded_rows = 0
        self._n_errors = 0
        self._n_updates = 0
        self._n_update_rows = 0
        self._n_deadline_reqs = 0
        self._n_deadline_misses = 0
        self._n_admission_rejects = 0
        self._n_expired_drops = 0
        self._n_slack_shed_batches = 0
        # requests dispatched and their summed arrival→dispatch wait
        self._n_waited = 0
        self._wait_s = 0.0
        # tier counters: shed decisions are per batch; escalation splits
        # are per row, reported by any engine whose aux carries an
        # "escalated" mask (the cascade, shed or routed)
        self._n_shed_batches = 0
        self._n_shed_rows = 0
        self._n_cascade_rows = 0
        self._n_escalated_rows = 0
        # served batches by how their result came back (infer_padded)
        self._n_fetch_packed = 0
        self._n_fetch_per_leaf = 0
        if (self.policy.shed_backend is not None
                and self.policy.shed_backend not in available_backends()):
            raise ValueError(
                f"unknown shed_backend {self.policy.shed_backend!r}; "
                f"available: {available_backends()}")

    def _publish(self, version: int, state: TMState) -> None:
        """Swap in a ``(version, state)`` pair atomically and remember it
        in the bounded history ring (rollback targets; memory stays
        bounded because the ring evicts oldest-first while in-flight
        predicts keep their own pinned references alive).  Every publish
        then re-resolves serving against the new state
        (:meth:`_refresh_serving`) — routes, sparse layout, and the
        superseded state's cached engines."""
        with _span("tm.publish", version=version):
            with self._mu:
                prev = getattr(self, "_current", None)
                self._current = (version, state)
                self._history.append((version, state))
            self._refresh_serving(
                state, superseded=prev[1] if prev is not None else None)
            if self._on_publish is not None:
                try:
                    self._on_publish(version, state)
                except Exception:
                    # a broken observer must not poison the publish/update
                    # path — count it and keep serving the new state
                    with self._mu:
                        self._n_publish_hook_errors += 1

    def publish(self, state: TMState) -> int:
        """Swap in ``state`` as a new version (bumped by one) → version.

        The fleet republish path: a pack-group server's fused state is
        rebuilt outside any training step, so its version counter just
        advances monotonically.  Runs the full publish path (history
        ring, route re-resolution, superseded-engine eviction,
        ``on_publish`` hook).  Call from the event-loop thread only,
        like every other lifecycle mutation.
        """
        version = self._current[0] + 1
        self._publish(version, state)
        return version

    def _refresh_serving(self, state: TMState, *,
                         superseded: TMState | None = None) -> None:
        """Publish-path serving maintenance — the stale-routing fix.

        Runs on the event-loop thread after each ``(version, state)``
        swap:

        1. re-resolves density-heuristic routes against the *new*
           state's include density (unless routing is pinned by an
           explicit table or ``policy.backend``), so a model that
           drifts across the 0.10 boundary actually flips between
           ``swar_packed`` and ``sparse_csr``;
        2. refreshes the server's :class:`IncrementalEll` mirror by
           include deltas and prebuilds the ``sparse_csr`` engine for
           the newest state from it — O(changed rows) per publish
           instead of a from-scratch CSR rebuild;
        3. evicts the superseded state's engines from the keyed cache
           (they are stale *for this logical model* and would otherwise
           leak until LRU pressure; in-flight predicts still pinned to
           the old version just rebuild on a cache miss).
        """
        t0 = time.perf_counter()
        with _span("tm.engine_build"):
            # pinned routes without sparse_csr read no host mask (at
            # C·M·2F = 200 M literals its copy alone takes seconds)
            if self._routing_pinned and \
                    "sparse_csr" not in self.routing.values():
                inc = None
            else:
                inc = np.asarray(
                    include_mask(self.cfg, state), dtype=bool).reshape(
                    self.cfg.n_classes * self.cfg.n_clauses,
                    self.cfg.n_literals)
            if not self._routing_pinned:
                new_routes = route_buckets(self.cfg, state, self.buckets,
                                           density=float(inc.mean()))
                if new_routes != self.routing:
                    self.routing = new_routes
                    with self._mu:
                        self._n_routing_updates += 1
            if "sparse_csr" in self.routing.values():
                if self._serve_ell is None:
                    self._serve_ell = IncrementalEll(inc)
                else:
                    self._serve_ell.refresh(inc)
                engine = get_engine("sparse_csr", self.cfg, state, cache=False,
                                    ell=self._serve_ell.layout)
                if self._mesh is not None:
                    # the one-slot engine bypasses get_engine's shard_batch
                    # wrapping (cache=False + EllLayout opts), so wrap here —
                    # mesh-configured serving must cover the sparse route too
                    from repro.engine.sharding import ShardedEngine
                    engine = ShardedEngine(engine, mesh=self._mesh)
                self._sparse_serving = (state, engine)
            else:
                self._sparse_serving = None
        with self._mu:
            self._n_engine_builds += 1
            self._engine_build_s += time.perf_counter() - t0
        if superseded is not None and superseded is not state:
            evict_engines_for_state(superseded)

    @property
    def state(self) -> TMState:
        """The currently served ``TMState`` (the newest applied version)."""
        return self._current[1]

    @property
    def state_version(self) -> int:
        """How many labeled updates have been applied (0 at start; a
        restore adopts the checkpoint's version, a rollback bumps it)."""
        return self._current[0]

    @property
    def history_versions(self) -> tuple[int, ...]:
        """Versions currently retained in the bounded history ring
        (oldest → newest) — the in-memory :meth:`rollback` targets."""
        return tuple(v for v, _ in self._history)

    # -- lifecycle ----------------------------------------------------

    async def start(self) -> "TMServer":
        """Launch the fan-out + scheduler coroutines (once only)."""
        if self._task is not None:
            raise RuntimeError("server already started")
        loop = asyncio.get_running_loop()
        self._fanout_task = loop.create_task(
            self._fanout_loop(), name="tm-serve-fanout")
        self._task = loop.create_task(
            self._scheduler(), name="tm-serve-scheduler")
        return self

    async def stop(self) -> None:
        """Graceful shutdown: drain queued requests and in-flight
        pipeline stages, take a final checkpoint when periodic
        checkpointing is on and the state has advanced past the last
        snapshot, then join any in-flight checkpoint writers so no
        snapshot is torn by process exit."""
        if self._closed:
            return
        self._closed = True
        await self._queue.put(_STOP)
        if self._task is not None:
            await self._task
        if self._owns_pool:
            self._pool.shutdown(wait=True)
        if self._train_pool is not None:
            self._train_pool.shutdown(wait=True)
        if (self._ckpt_dir is not None
                and self._current[0] != self._last_ckpt_version):
            self.checkpoint()
        for t in self._ckpt_threads:
            t.join()
        self._ckpt_threads.clear()

    async def __aenter__(self) -> "TMServer":
        return await self.start()

    async def __aexit__(self, *exc) -> None:
        await self.stop()

    # -- state lifecycle: checkpoint / restore / rollback -------------

    def checkpoint(self, directory: str | None = None, *,
                   block: bool = True) -> int:
        """Snapshot the full serving lifecycle → the step number written.

        Persists ``(version, TMState, update-key-chain cursor, train
        backend + resolved autotune opts)`` through
        :mod:`repro.checkpoint` at ``step == state_version`` — atomic
        (tmp-dir + rename), valid only once ``.complete`` lands.
        ``block=False`` hands serialization to a background writer
        thread (``save_async``; the host copy is taken up-front, so the
        served state may keep advancing) and applies ``gc_keep``
        retention, which is what the periodic auto-checkpoint path uses.

        Call from the event-loop thread (or on a stopped server): the
        snapshot must pair the published ``(version, state)`` with the
        key-chain cursor, and both are only mutated there.
        """
        directory = self._ckpt_dir if directory is None else directory
        if directory is None:
            raise ValueError("no checkpoint directory: pass directory= or "
                             "construct TMServer with checkpoint_dir=")
        from repro import checkpoint as ckpt
        from repro.engine.train import export_key_cursor, train_engine_opts
        version, state = self._current
        cursor = None
        extra = {"version": version, "has_cursor": False,
                 "cfg": dataclasses.asdict(self.cfg),
                 "train_backend": self._train_backend,
                 "train_opts": {}, "updates": self._n_updates,
                 "rollbacks": self._n_rollbacks,
                 # mesh *size* only — metadata for operators and the
                 # elastic-restore tests; arrays are host-gathered, so
                 # the snapshot itself is mesh-agnostic
                 "mesh_devices": (None if self._mesh is None else
                                  int(self._mesh.devices.size))}
        if self._train_key is not None:
            data, impl = export_key_cursor(self._train_key)
            cursor, extra["has_cursor"], extra["key_impl"] = data, True, impl
            extra["train_opts"] = train_engine_opts(self._train_engine)
        tree = ckpt.tm_lifecycle_tree(state.ta, cursor)
        if block:
            ckpt.save(directory, version, tree, extra=extra)
        else:
            self._ckpt_threads = [t for t in self._ckpt_threads
                                  if t.is_alive()]
            self._ckpt_threads.append(
                ckpt.save_async(directory, version, tree, extra=extra))
        ckpt.gc_keep(directory, self._ckpt_keep)
        self._last_ckpt_version = version
        return version

    def restore(self, directory: str | None = None, *,
                step: int | None = None, mesh=None,
                shardings=None) -> int:
        """Resume from a checkpoint → the restored state version.

        Loads the newest valid step (or ``step=``), verifies the saved
        ``TMConfig`` matches this server's, and adopts the snapshot's
        ``(version, state)``, update-key-chain cursor, and train backend
        with its saved autotune opts — so a killed-and-restarted server
        replays bit-exactly against the uninterrupted run (the next
        update draws the key the unbroken chain would have drawn).  The
        history ring restarts at the restored pair.  Must be called
        before :meth:`start` (restore swaps state non-atomically with
        respect to a live scheduler).

        **Elastic re-shard**: ``mesh=`` (a 1-D ``Mesh``, a device count,
        or ``None`` to keep the constructor's) retargets *this* server's
        mesh before the restored state publishes, so a checkpoint
        written on mesh A restores onto mesh B — including B =
        single-host (``mesh=1``).  Safe because snapshots are
        host-gathered and training is mesh-size invariant (bit-identical
        states for any D, ``tests/test_elastic_restore.py``).  A
        ``sharded`` train backend whose recorded ``n_devices`` exceeds
        this host's devices is clamped (or replaced by the override);
        ``shardings=`` optionally re-``device_put``s the loaded arrays
        under NamedShardings for the new mesh (see
        :func:`repro.checkpoint.restore_tm_lifecycle`).
        """
        if self._task is not None and not self._closed:
            raise RuntimeError("restore() must run before start()")
        directory = self._ckpt_dir if directory is None else directory
        if directory is None:
            raise ValueError("no checkpoint directory: pass directory= or "
                             "construct TMServer with checkpoint_dir=")
        import jax.numpy as jnp
        from repro import checkpoint as ckpt
        if mesh is not None:
            from jax.sharding import Mesh
            from repro.distributed.sharding import data_mesh
            self._mesh = mesh if isinstance(mesh, Mesh) else \
                data_mesh(int(mesh))
            if len(self._mesh.axis_names) != 1:
                raise ValueError(f"TMServer needs a 1-D mesh, got "
                                 f"{self._mesh.axis_names}")
        step, tree, extra = ckpt.restore_tm_lifecycle(directory, step,
                                                      shardings=shardings)
        saved_cfg = extra.get("cfg")
        if saved_cfg and saved_cfg != dataclasses.asdict(self.cfg):
            raise ValueError(f"checkpoint step_{step} was written for "
                             f"cfg {saved_cfg}, not {self.cfg}")
        version = int(extra.get("version", step))
        self._history.clear()
        self._publish(version, TMState(ta=jnp.asarray(tree["ta"])))
        if extra.get("has_cursor"):
            from repro.engine import get_train_engine
            from repro.engine.train import (RETIRED_TRAIN_OPTS,
                                            import_key_cursor)
            backend = extra.get("train_backend")
            if backend:
                # the checkpoint's backend + autotune picks win — even
                # when the backend name matches the constructor's, the
                # saved opts override this host's autotune cache:
                # restore means resume *that* run, not a local retune
                topts = {k: v for k, v in extra.get("train_opts", {}).items()
                         if k not in RETIRED_TRAIN_OPTS}
                if backend == "sharded":
                    # mesh size is elastic: the override mesh wins, and
                    # a recorded size this host can't build clamps to
                    # the local device count — both resume bit-exactly
                    if self._mesh is not None:
                        topts.pop("n_devices", None)
                        topts["mesh"] = self._mesh
                    else:
                        avail = len(jax.devices())
                        n = topts.get("n_devices") or avail
                        topts["n_devices"] = min(int(n), avail)
                self._train_engine = get_train_engine(
                    backend, self.cfg, **topts)
                self._train_backend = backend
                if self._train_pool is None:
                    self._train_pool = ThreadPoolExecutor(
                        max_workers=1, thread_name_prefix="tm-serve-train")
            self._train_key = import_key_cursor(tree["cursor"],
                                                extra["key_impl"])
        self._restored_from = step
        self._last_ckpt_version = version
        return version

    def rollback(self, version: int) -> int:
        """Re-publish a historical state → the new (bumped) version.

        Looks the target up in the bounded history ring first, then —
        when a checkpoint directory is configured — on disk at
        ``step == version``.  The old state publishes under
        ``state_version + 1`` so versions stay monotonic (in-flight
        predicts pinned to other versions are untouched).  Rollback
        restores *state only*: the update-key chain keeps advancing from
        its current cursor, and the rollback is recorded in ``stats()``
        (offline replay of a rolled-back server must replay the rollback
        at the same position).  Operator action — quiesce the label
        stream first; an update already executing when the rollback
        lands publishes its own pre-rollback-derived state on top (see
        docs/operations.md).
        """
        state = next((s for v, s in self._history if v == version), None)
        if state is None and self._ckpt_dir is not None:
            import jax.numpy as jnp
            from repro import checkpoint as ckpt
            if version in ckpt.valid_steps(self._ckpt_dir):
                _, tree, _ = ckpt.restore_tm_lifecycle(self._ckpt_dir,
                                                       version)
                state = TMState(ta=jnp.asarray(tree["ta"]))
        if state is None:
            raise KeyError(
                f"version {version} is in neither the history ring "
                f"{list(self.history_versions)} nor the checkpoint dir")
        new_version = self._current[0] + 1
        self._publish(new_version, state)
        self._n_rollbacks += 1
        return new_version

    def engine_for(self, bucket: int, state: TMState | None = None):
        """The (cached) engine serving this bucket.

        ``state`` pins a specific state version (the batcher passes each
        batch's arrival-time state); default is the newest.  Engines come
        from ``get_engine``'s keyed LRU, so each live state version keeps
        its own precompiled layout and retired versions self-evict when
        their arrays are garbage-collected — except ``sparse_csr`` for
        the newest state, which is served from the one-slot engine the
        publish path prebuilt from the incrementally refreshed layout
        (an ``EllLayout`` can't key the LRU).
        """
        st = self.state if state is None else state
        backend = self.routing.get(bucket) or \
            self.routing.get(self.buckets[-1], "oracle")
        if backend == "sparse_csr":
            # one atomic read of the (state, engine) pair: publishes swap
            # the whole tuple, so a racing reader sees a matched pair or
            # misses the identity check and builds its own — never a
            # stale engine for the wrong state
            pair = self._sparse_serving
            if pair is not None and pair[0] is st:
                return pair[1]
        return get_engine(backend, self.cfg, st,
                          shard_batch=self._mesh or False)

    def shed_engine_for(self, bucket: int, state: TMState | None = None):
        """The (cached) overload-tier engine (``policy.shed_backend``).

        Same keyed-LRU reuse as :meth:`engine_for`; ``bucket`` is unused
        for engine identity (engines are shape-polymorphic per bucket via
        jit) but kept for signature symmetry.
        """
        if self.policy.shed_backend is None:
            raise RuntimeError("no shed tier configured (shed_backend=)")
        return get_engine(self.policy.shed_backend, self.cfg,
                          self.state if state is None else state,
                          shard_batch=self._mesh or False,
                          **self.policy.resolved_shed_opts())

    async def warmup(self, *, train_batches: tuple[int, ...] = ()) -> None:
        """Compile every (engine, bucket) pair before taking traffic.
        ``infer_padded`` on host literals is stage B's call, so this
        compiles the programs that are served (the packed one where the
        engine has it).

        In online-learning mode, ``train_batches`` also compiles the
        train step for those labeled-batch row counts (the update path
        compiles per batch shape, exactly like predict buckets — feed
        fixed-size labeled batches to avoid mid-traffic compiles) on the
        training thread.  When a drift probe is configured, its
        (possibly oversized) bucket compiles here too, so the first
        probe eval doesn't stall the worker thread on XLA.  The warmup
        step's result is discarded; the served state is untouched.
        """
        loop = asyncio.get_running_loop()
        zeros = np.zeros((1, self.cfg.n_literals), np.int8)
        buckets = list(self.buckets)
        if self._probe is not None:
            probe_bucket = bucket_for(self._probe[0].shape[0], self.buckets)
            if probe_bucket not in buckets:
                buckets.append(probe_bucket)
        for bucket in buckets:
            engines = [self.engine_for(bucket)]
            if self.policy.shed_backend is not None:
                # the overload tier must be warm *before* overload: a
                # mid-backlog XLA compile is the worst possible moment.
                # A cascade tier's escalation sub-buckets still compile
                # lazily (first near-tie batch), bounded at log2(bucket)
                # shapes.
                engines.append(self.shed_engine_for(bucket))
            for eng in engines:
                await loop.run_in_executor(
                    self._pool,
                    lambda e=eng, b=bucket: np.asarray(
                        infer_padded(e, zeros, b).prediction))
        for n in train_batches:
            if self._train_engine is None:
                raise RuntimeError("train_batches warmup needs online "
                                   "learning (train_backend=)")
            lits = np.zeros((n, self.cfg.n_literals), np.int8)
            labels = np.zeros((n,), np.int32)
            key = jax.random.key(0)
            await loop.run_in_executor(
                self._train_pool,
                lambda l=lits, y=labels: jax.block_until_ready(
                    self._train_engine.step(self._current[1], key, l, y).ta))

    # -- request path -------------------------------------------------

    async def submit(self, literals, *, client=None,
                     deadline_us: int | None = None,
                     priority: int = 0) -> EngineResult:
        """One request: ``(n, 2F)`` or ``(2F,)`` {0,1} literals → the
        request's own :class:`EngineResult` (batch-leading, ``n`` rows).

        ``deadline_us`` is the completion SLO from now; the scheduler
        serves tighter slack first (EDF within a priority tier) and may
        reject (:class:`DeadlineExceeded`) when admission control
        proves the deadline unmeetable — at submit, when the fastest
        service time ever observed for the request's bucket already
        exceeds it; or at dispatch, when the deadline expired while
        the request waited in the queue.
        ``priority`` orders tiers (lower first; deadline-free traffic at
        equal priority stays FIFO).  Awaits queue space when
        ``queue_depth`` requests are already waiting — callers *feel*
        overload as latency, the server never grows an unbounded
        backlog.
        """
        if self._closed:
            raise RuntimeError("TMServer is stopped")
        lits = self._check_literals(literals)
        if deadline_us is not None:
            deadline_us = int(deadline_us)
            if deadline_us <= 0:
                raise ValueError(f"deadline_us must be > 0, "
                                 f"got {deadline_us}")
            if self.policy.admission_control:
                floor = self._svc.floor(
                    bucket_for(lits.shape[0], self.buckets))
                if floor is not None and floor > deadline_us * 1e-6:
                    with self._mu:
                        self._n_admission_rejects += 1
                    raise DeadlineExceeded(
                        f"deadline {deadline_us}us is below the fastest "
                        f"observed service time {floor * 1e6:.0f}us for "
                        f"this bucket — the request provably cannot "
                        f"meet it")
        future = asyncio.get_running_loop().create_future()
        await self._capacity.acquire()
        # pin *after* backpressure resolves: the version current when
        # the request actually enters the scheduler's queue
        version, state = self._current
        self._seq += 1
        req = _Request(
            lits, future, client, version, state,
            deadline=(time.monotonic() + deadline_us * 1e-6
                      if deadline_us is not None else None),
            priority=int(priority), seq=self._seq)
        self._queue.put_nowait(req)
        return await future

    def _check_literals(self, literals) -> np.ndarray:
        """Validate/promote request literals to ``(n, 2F)`` int8."""
        lits = np.asarray(literals, dtype=np.int8)
        if lits.ndim == 1:
            lits = lits[None, :]
        if lits.ndim != 2 or lits.shape[1] != self.cfg.n_literals:
            raise ValueError(
                f"expected (n, {self.cfg.n_literals}) literals, "
                f"got {np.shape(literals)}")
        return lits

    async def submit_labeled(self, literals, labels) -> int:
        """One labeled feedback batch: ``(n, 2F)`` literals + ``(n,)``
        labels → the state version that includes this update.

        Requires online-learning mode (``train_backend=`` at
        construction).  Updates apply in FIFO order among themselves and
        have their *own* admission gate (also ``queue_depth`` deep): a
        saturating predict flood waiting on the predict gate's FIFO
        cannot starve the learning control plane, and vice versa.  The
        returned future resolves once the new state version is live.
        Predicts already queued keep the version they arrived under.
        """
        if self._closed:
            raise RuntimeError("TMServer is stopped")
        if self._train_engine is None:
            raise RuntimeError(
                "online learning is off: construct TMServer with "
                "train_backend=<TrainEngine name> to enable submit_labeled")
        lits = self._check_literals(literals)
        y = np.asarray(labels, dtype=np.int32).reshape(-1)
        if y.shape[0] != lits.shape[0]:
            raise ValueError(f"labels {y.shape} do not match "
                             f"{lits.shape[0]} literal rows")
        if y.size and (y.min() < 0 or y.max() >= self.cfg.n_classes):
            raise ValueError(f"labels out of range [0, {self.cfg.n_classes})")
        future = asyncio.get_running_loop().create_future()
        await self._update_capacity.acquire()
        self._queue.put_nowait(_Update(lits, y, future))
        return await future

    # -- scheduler (stage A: coalesce + assemble) ---------------------

    def _ingest(self, item) -> None:
        """Sort one arrival into the EDF heap / update FIFO."""
        if item is _STOP:
            self._stop_seen = True
        elif isinstance(item, _Update):
            self._pending_updates.append(item)
        else:
            heapq.heappush(self._pending, (*item.sort_key(), item))

    def _drain_queue(self) -> None:
        """Move every already-arrived item into the reorder structures."""
        t = self._get_task
        if t is not None and t.done():
            self._get_task = None
            self._ingest(t.result())
        while True:
            try:
                self._ingest(self._queue.get_nowait())
            except asyncio.QueueEmpty:
                break

    async def _next_arrival(self, timeout, extra: asyncio.Task | None = None
                            ) -> bool:
        """Block up to ``timeout`` for the next queue item (ingested on
        arrival; returns True) — or until ``extra`` (the in-flight
        update task) finishes.  The queue getter is a persistent task so
        a timeout never cancels a get that already claimed an item."""
        if self._get_task is None:
            self._get_task = asyncio.ensure_future(self._queue.get())
        waits = {self._get_task}
        if extra is not None:
            waits.add(extra)
        done, _ = await asyncio.wait(waits, timeout=timeout,
                                     return_when=asyncio.FIRST_COMPLETED)
        if self._get_task in done:
            item = self._get_task.result()
            self._get_task = None
            self._ingest(item)
            return True
        return False

    def _qdepth(self) -> int:
        """Waiting (undispatched) items: arrival queue + reorder heap +
        update FIFO — the quantity the shed tier triggers on
        (``queue_depth`` bounds the predict and update planes each,
        through their separate admission gates)."""
        return (self._queue.qsize() + len(self._pending)
                + len(self._pending_updates))

    def _reap_expired(self) -> None:
        """Fail already-dead queue heads without compute.

        The lazy half of admission control (same ``admission_control``
        switch): a request whose deadline passed while it waited can
        provably no longer be met, so it gets :class:`DeadlineExceeded`
        in O(1) at dispatch time instead of a batch slot — under
        overload this is what keeps compute flowing to requests that
        can still make their SLO.  Only heads are reaped: EDF order
        means a live head proves the rest of its priority tier is live,
        and lower tiers get reaped when they surface."""
        if not self.policy.admission_control:
            return
        now = time.monotonic()
        while self._pending:
            req = self._pending[0][-1]
            if req.deadline is None or req.deadline > now:
                return
            heapq.heappop(self._pending)
            self._capacity.release()
            if not req.future.done():
                req.future.set_exception(DeadlineExceeded(
                    f"deadline passed {(now - req.deadline) * 1e6:.0f}us "
                    f"ago while queued — dropped at dispatch"))
            with self._mu:
                self._n_expired_drops += 1

    def _pop_head(self, version: int | None = None,
                  max_rows: int | None = None) -> _Request | None:
        """Pop the EDF head if it can join the open batch (matching
        state version, fits the row budget); popping releases one unit
        of backpressure capacity.  Strictly in-order: a head that cannot
        join closes the batch even if a deeper item could."""
        if not self._pending:
            return None
        req = self._pending[0][-1]
        if version is not None and req.version != version:
            return None
        if max_rows is not None and req.n > max_rows:
            return None
        heapq.heappop(self._pending)
        self._capacity.release()
        return req

    async def _service_updates(self) -> None:
        """Dispatch the next pending update when the barrier allows.

        Updates serialize among themselves (one in flight — the only
        true pipeline barrier); at ``pipeline_depth=1`` the update also
        quiesces in-flight predicts first, reproducing the legacy
        serial interleaving exactly."""
        if self._update_task is not None and self._update_task.done():
            await self._update_task   # surfaces scheduler bugs, not
            self._update_task = None  # engine errors (_run_update catches)
        if self._update_task is None and self._pending_updates:
            upd = self._pending_updates.popleft()
            self._update_capacity.release()
            if self.policy.pipeline_depth == 1:
                await self._completions.join()
                await self._run_update(upd)
            else:
                self._update_task = asyncio.get_running_loop().create_task(
                    self._run_update(upd), name="tm-serve-update")

    async def _scheduler(self) -> None:
        try:
            while True:
                # drain BEFORE servicing updates: an update that arrived
                # ahead of this pass must dispatch now, not after the
                # next (possibly never-coming) arrival
                self._drain_queue()
                self._reap_expired()
                await self._service_updates()
                if self._pending:
                    await self._coalesce_and_dispatch()
                    continue
                update_running = (self._update_task is not None
                                  and not self._update_task.done())
                if self._pending_updates and not update_running:
                    # depth 1 ran one queued update inline; the next
                    # must not wait for an arrival that may never come
                    continue
                if (self._stop_seen and self._queue.empty()
                        and not self._pending_updates
                        and not update_running):
                    break
                # idle: wake on the next arrival, or on the in-flight
                # update finishing (its successor may be waiting)
                with _span("tm.idle"):
                    await self._next_arrival(
                        None, extra=self._update_task if update_running
                        else None)
        finally:
            t, self._get_task = self._get_task, None
            if t is not None:
                t.cancel()
                try:
                    item = await t
                except (asyncio.CancelledError, Exception):
                    pass
                else:
                    self._ingest(item)   # cancel raced a claimed item
            if self._update_task is not None:
                try:
                    await self._update_task
                except Exception:
                    pass
                self._update_task = None
            # abnormal exit only: on a graceful stop everything below
            # is empty — fail whatever would otherwise hang forever
            leftovers = [entry[-1] for entry in self._pending]
            self._pending.clear()
            leftovers.extend(self._pending_updates)
            self._pending_updates.clear()
            while not self._queue.empty():
                item = self._queue.get_nowait()
                if item is not _STOP:
                    leftovers.append(item)
            for item in leftovers:
                if not item.future.done():
                    item.future.set_exception(
                        RuntimeError("TMServer scheduler exited"))
            # drain the pipeline, then retire the fan-out coroutine
            await self._completions.join()
            self._completions.put_nowait(_STOP)
            if self._fanout_task is not None:
                await self._fanout_task
                self._fanout_task = None

    async def _coalesce_and_dispatch(self) -> None:
        """Open a batch at the EDF head and coalesce until full, closed,
        or out of wait budget — then hand it to stage B."""
        policy = self.policy
        self._batch_seq += 1
        seq = self._batch_seq
        with _span("tm.coalesce", batch=seq):
            first = self._pop_head()
            batch, rows = [first], first.n
            deadline = time.monotonic() + policy.max_wait_us * 1e-6
            while rows < policy.max_batch:
                self._drain_queue()
                nxt = self._pop_head(version=first.version,
                                     max_rows=policy.max_batch - rows)
                if nxt is not None:
                    batch.append(nxt)
                    rows += nxt.n
                    continue
                if (self._pending or self._pending_updates
                        or self._stop_seen):
                    # the head exists but cannot join (version cut / row
                    # overflow), or an update/stop wants the floor: close
                    break
                timeout = deadline - time.monotonic()
                if timeout <= 0 or not await self._next_arrival(timeout):
                    break
        await self._dispatch_batch(batch, rows, seq)

    def _assemble(self, batch: list[_Request], rows: int, bucket: int,
                  slot: int) -> np.ndarray:
        """Stage A assembly into the slot's reusable double buffer.

        Slot ``k`` is provably idle when reused: re-acquiring the
        pipeline semaphore ``depth`` dispatches later implies the
        dispatch that last wrote it has completed compute and fan-out.
        An exact-fit single request skips the copy entirely."""
        if len(batch) == 1 and batch[0].n == bucket:
            return batch[0].lits
        buf = self._asm_buffers[slot]
        if buf is None or buf.shape[0] < bucket:
            buf = np.zeros((bucket, self.cfg.n_literals), np.int8)
            self._asm_buffers[slot] = buf
        off = 0
        for req in batch:
            buf[off:off + req.n] = req.lits
            off += req.n
        buf[off:bucket] = 0          # neutral padding rows
        return buf[:bucket]

    async def _dispatch_batch(self, batch: list[_Request], rows: int,
                              seq: int) -> None:
        """Assemble (stage A) and launch compute (stage B), bounded at
        ``pipeline_depth`` in flight; completion metadata goes to the
        FIFO that stage C fans out from.  ``seq`` is the batch's
        sequence number, carried by its spans."""
        with _span("tm.pipeline_wait", batch=seq):
            await self._sem.acquire()
        with _span("tm.assemble", batch=seq):
            slot = self._next_slot
            self._next_slot = (slot + 1) % self.policy.pipeline_depth
            bucket = bucket_for(rows, self.buckets)
            lits = self._assemble(batch, rows, bucket, slot)
            # shed decision at dispatch time: backlog depth (arrivals are
            # outpacing compute) OR slack exhaustion (the tightest
            # deadline in the batch is inside the bucket's expected
            # service time)
            slack_shed = False
            if self.policy.shed_backend is not None:
                deadlines = [r.deadline for r in batch
                             if r.deadline is not None]
                if deadlines:
                    ewma = self._svc.ewma(bucket)
                    slack_shed = (ewma is not None and
                                  min(deadlines) - time.monotonic() < ewma)
            shed = (self.policy.shed_backend is not None
                    and (self._qdepth() >= self.policy.shed_qdepth
                         or slack_shed))
        t_dispatch = time.monotonic()
        waited = sum(t_dispatch - req.t_in for req in batch)
        fut = asyncio.get_running_loop().run_in_executor(
            self._pool, self._compute, lits, bucket, batch[0].state, shed,
            seq)
        with self._mu:
            self._inflight += 1
            v = batch[0].version
            self._inflight_versions[v] = \
                self._inflight_versions.get(v, 0) + 1
            if shed and slack_shed:
                self._n_slack_shed_batches += 1
            self._n_waited += len(batch)
            self._wait_s += waited
        self._completions.put_nowait((batch, rows, bucket, shed, fut, seq))
        if self.policy.pipeline_depth == 1:
            # legacy serial semantics: this batch fully retires (compute
            # + fan-out) before the next one opens
            await self._completions.join()

    # -- stage B: device compute (worker thread) ----------------------

    def _compute(self, lits: np.ndarray, bucket: int, state: TMState,
                 shed: bool, seq: int) -> EngineResult:
        """One padded engine call, materialized to numpy (worker
        thread).  Only the engine call is traced, so XLA compiles once
        per (engine, bucket) no matter how request sizes combine; the
        wall time feeds the per-bucket service ring admission control
        and slack shedding read.  Its spans split that time into the
        dispatch (host work, the host→device copy, and asking for the
        copy back, which ``infer_padded`` does for host literals), the
        wait for the device with the first copy back (the predictions:
        an engine with ``infer_packed`` brings the whole result in that
        one copy), and the rest (numpy views of that copy, or the other
        leaves' copies).
        The first copy does the waiting: a sync of its own before it
        would wake this thread once more per batch."""
        t0 = time.perf_counter()
        with _span("tm.stageB.dispatch", batch=seq):
            engine = (self.shed_engine_for(bucket, state) if shed
                      else self.engine_for(bucket, state))
            res = infer_padded(engine, lits, bucket)
        with _span("tm.stageB.sync", batch=seq):
            prediction = np.asarray(res.prediction)
        with _span("tm.stageB.copy", batch=seq):
            out = EngineResult(
                prediction, np.asarray(res.class_sums),
                {k: np.asarray(v) for k, v in res.aux.items()})
        self._svc.observe(bucket, time.perf_counter() - t0)
        with self._mu:
            if hasattr(engine, "infer_packed"):
                self._n_fetch_packed += 1
            else:
                self._n_fetch_per_leaf += 1
        return out

    # -- stage C: fan-out ---------------------------------------------

    async def _fanout_loop(self) -> None:
        """Resolve per-request futures in dispatch (FIFO) order.

        A dedicated coroutine so awaiting clients never sit behind
        stage A assembling the next batch; the worker thread is serial,
        so FIFO completion order preserves per-client arrival order."""
        while True:
            item = await self._completions.get()
            if item is _STOP:
                self._completions.task_done()
                return
            batch, rows, bucket, shed, fut, seq = item
            try:
                try:
                    res = await fut
                except Exception as exc:
                    # a failing batch (bad routing entry, backend error)
                    # fails *its own* requests and nothing else
                    for req in batch:
                        if not req.future.done():
                            req.future.set_exception(exc)
                    with self._mu:
                        self._n_errors += len(batch)
                else:
                    with _span("tm.fanout", batch=seq):
                        self._fan_out(batch, rows, bucket, shed, res)
            finally:
                with self._mu:
                    self._inflight -= 1
                    v = batch[0].version
                    left = self._inflight_versions.get(v, 1) - 1
                    if left > 0:
                        self._inflight_versions[v] = left
                    else:
                        self._inflight_versions.pop(v, None)
                self._sem.release()
                self._completions.task_done()

    def _fan_out(self, batch: list[_Request], rows: int, bucket: int,
                 shed: bool, res: EngineResult) -> None:
        """Slice one completed batch back per request and settle
        counters (one locked update — stats() snapshots are
        consistent)."""
        done = time.monotonic()
        lats = []
        n_dead = n_miss = 0
        offset = 0
        for req in batch:
            sl = slice(offset, offset + req.n)
            offset += req.n
            out = EngineResult(res.prediction[sl], res.class_sums[sl],
                               {k: v[sl] for k, v in res.aux.items()})
            if not req.future.done():
                req.future.set_result(out)
            lats.append(done - req.t_in)
            if req.deadline is not None:
                n_dead += 1
                if done > req.deadline:
                    n_miss += 1
        esc = res.aux.get("escalated")
        with self._mu:
            self._latencies.extend(lats)
            self._n_requests += len(batch)
            self._n_rows += rows
            self._n_batches += 1
            self._n_padded_rows += bucket
            self._n_deadline_reqs += n_dead
            self._n_deadline_misses += n_miss
            if shed:
                self._n_shed_batches += 1
                self._n_shed_rows += rows
            if esc is not None:         # a cascade served this batch
                # the executor hands over the bucket-shaped result, so
                # trim the mask to real rows — pad rows aren't traffic
                self._n_cascade_rows += rows
                self._n_escalated_rows += int(np.asarray(esc)[:rows].sum())

    # -- online learning ----------------------------------------------

    async def _run_update(self, upd: _Update) -> None:
        """Apply one labeled batch on the training thread, then publish
        the new ``(version, state)`` pair — predicts never see a partial
        state because the swap is a single tuple assignment of an
        immutable, fully-computed state.  The key-chain cursor advances
        on the event loop *after* the step succeeds, so a checkpoint
        always pairs a published state with its matching cursor."""
        def learn() -> tuple:
            # advance the key chain only on success: the offline-replay
            # contract covers *applied* updates, so a failed step must
            # not consume a key
            with _span("tm.train_step"):
                chain, k = jax.random.split(self._train_key)
                new_state = self._train_engine.step(
                    self._current[1], k, upd.lits, upd.labels)
                jax.block_until_ready(new_state.ta)
            return chain, new_state

        try:
            chain, new_state = await asyncio.get_running_loop() \
                .run_in_executor(self._train_pool, learn)
        except Exception as exc:
            if not upd.future.done():
                upd.future.set_exception(exc)
            with self._mu:
                self._n_errors += 1
            return
        self._train_key = chain
        version = self._current[0] + 1
        self._publish(version, new_state)
        with self._mu:
            self._n_updates += 1
            self._n_update_rows += upd.lits.shape[0]
        if not upd.future.done():
            upd.future.set_result(version)
        if (self._ckpt_dir is not None and self._ckpt_every
                and version % self._ckpt_every == 0):
            # async snapshot: the host copy is taken here on the loop,
            # serialization runs on a background writer thread
            self.checkpoint(block=False)
        if (self._probe is not None and self._probe_every
                and self._n_updates % self._probe_every == 0):
            try:
                acc = await asyncio.get_running_loop().run_in_executor(
                    self._train_pool, self._probe_eval, new_state)
            except Exception:
                with self._mu:
                    self._n_errors += 1
            else:
                self._probe_history.append((version, acc))
                self._n_probe_evals += 1
                if self._probe_best is None or acc > self._probe_best:
                    self._probe_best = acc

    def _probe_eval(self, state: TMState) -> float:
        """Score the held-out probe stream under ``state`` (training
        thread): accuracy through the same padded-bucket engine path
        predicts take, so probing stays off the event loop and shares
        the compiled (engine, bucket) pairs."""
        lits, labels = self._probe
        bucket = bucket_for(lits.shape[0], self.buckets)
        engine = self.engine_for(bucket, state)
        res = infer_padded(engine, lits, bucket)
        return float((np.asarray(res.prediction) == labels).mean())

    # -- observability ------------------------------------------------

    def stats(self) -> dict:
        """Serving counters: queue depth, batch fill, latency percentiles.

        Every counter is read under one lock in a single snapshot, so
        the ``tiers`` / ``deadline`` / latency blocks are mutually
        consistent even while fan-out and the update path mutate them.

        ``batch_fill`` is real rows ÷ padded rows — how much of each
        compiled bucket carried actual work.  Percentiles (p50/p90/p99)
        come from a sliding window of per-request latencies (seconds →
        ms).  In online-learning mode, ``state_version``/``updates``/
        ``update_rows`` track the learning stream.

        ``pipeline`` shows the dispatch scoreboard: configured depth,
        batches currently in flight (and per state version — predicts
        pinned to old versions overlapping newer publishes), and whether
        an update is in flight.  ``deadline`` tracks the SLO policy:
        deadline-carrying requests served/missed, ``miss_rate``,
        admission rejects, and batches shed for slack exhaustion.
        ``buckets`` is the per-bucket service-time ring (count, EWMA,
        min, p50/p90/p99 ms) — the *same* numbers admission control and
        slack shedding decide on.  ``queue_wait`` counts the requests
        dispatched and their summed (``total_ms``) and mean (``mean_ms``)
        wait from arrival to dispatch: coalescing, the EDF queue and the
        pipeline semaphore, not stage B.  ``result_fetch`` counts the
        served batches by how their result came back: ``packed`` (one
        array from an engine's ``infer_packed``) or ``per_leaf`` (a copy
        per result array, from any other engine).  ``engine_build``
        counts the publishes since construction (the constructor's own
        included) and sums the ``seconds`` their serving build took: the
        include mask's copy to the host, the route re-resolution, the
        ELL refresh and the ``sparse_csr`` engine's build (the
        ``tm.engine_build`` span); a route pinned to another backend
        skips all four, its engines built on first use.

        ``tiers`` tracks the overload path: the configured shed backend
        and threshold, how many batches/rows were shed, and — whenever a
        cascade engine served a batch (shed *or* routed) — the rows it
        saw, how many escalated to the full backend, and the resulting
        ``escalation_rate``.  ``engine_cache`` mirrors
        :func:`repro.engine.engine_cache_info` (hits/misses/evictions):
        a growing eviction count under steady serving means live state
        versions are thrashing the engine LRU.

        Lifecycle keys: ``history`` (versions retained in the bounded
        ring + its capacity), ``rollbacks``, ``checkpoint`` (directory,
        last step written, pending async writers, restored-from step;
        ``None`` when checkpointing is off), ``routing_updates`` (how
        many publishes actually changed the route table — density drift
        crossing the heuristic boundary), ``sparse_layout`` (the
        serving ``IncrementalEll``'s refresh counters, ``None`` until a
        ``sparse_csr`` route exists), ``mesh`` (device count + axis name
        of the configured data-parallel mesh, ``None`` single-device),
        and ``probe`` (``None``
        when drift monitoring is off; otherwise latest/best accuracy,
        ``drift`` = best − latest ≥ 0, ``delta`` = latest − previous,
        window mean, eval count — how an operator reads regression, see
        docs/operations.md).
        """
        with self._mu:
            lats = list(self._latencies)
            snap = {
                "requests": self._n_requests,
                "rows": self._n_rows,
                "batches": self._n_batches,
                "padded": self._n_padded_rows,
                "errors": self._n_errors,
                "updates": self._n_updates,
                "update_rows": self._n_update_rows,
                "version": self._current[0],
                "history": list(v for v, _ in self._history),
                "inflight": self._inflight,
                "inflight_versions": dict(self._inflight_versions),
                "deadline_reqs": self._n_deadline_reqs,
                "deadline_misses": self._n_deadline_misses,
                "admission_rejects": self._n_admission_rejects,
                "expired_drops": self._n_expired_drops,
                "slack_shed": self._n_slack_shed_batches,
                "shed_batches": self._n_shed_batches,
                "shed_rows": self._n_shed_rows,
                "cascade_rows": self._n_cascade_rows,
                "escalated_rows": self._n_escalated_rows,
                "routing_updates": self._n_routing_updates,
                "publish_hook_errors": self._n_publish_hook_errors,
                "waited": self._n_waited,
                "wait_s": self._wait_s,
                "fetch_packed": self._n_fetch_packed,
                "fetch_per_leaf": self._n_fetch_per_leaf,
                "engine_builds": self._n_engine_builds,
                "engine_build_s": self._engine_build_s,
            }
        p50_ms, p90_ms, p99_ms = percentiles_ms(lats, (0.50, 0.90, 0.99))
        ckpt_stats = None
        if self._ckpt_dir is not None:
            ckpt_stats = {
                "dir": self._ckpt_dir,
                "last_step": self._last_ckpt_version,
                "pending": sum(t.is_alive() for t in self._ckpt_threads),
                "restored_from": self._restored_from,
            }
        probe_stats = None
        if self._probe is not None:
            probe_stats = {"evals": self._n_probe_evals, "accuracy": None,
                           "best": self._probe_best, "drift": 0.0,
                           "delta": 0.0, "window_mean": 0.0,
                           "at_version": None}
            if self._probe_history:
                accs = [a for _, a in self._probe_history]
                probe_stats.update(
                    accuracy=accs[-1],
                    drift=round(self._probe_best - accs[-1], 6),
                    delta=round(accs[-1] - accs[-2], 6)
                    if len(accs) > 1 else 0.0,
                    window_mean=round(float(np.mean(accs)), 6),
                    at_version=self._probe_history[-1][0])
        return {
            "requests": snap["requests"],
            "rows": snap["rows"],
            "batches": snap["batches"],
            "errors": snap["errors"],
            "publish_hook_errors": snap["publish_hook_errors"],
            "qdepth": self._qdepth(),
            "mean_batch_rows": snap["rows"] / max(snap["batches"], 1),
            "batch_fill": snap["rows"] / max(snap["padded"], 1),
            "p50_ms": p50_ms,
            "p90_ms": p90_ms,
            "p99_ms": p99_ms,
            "state_version": snap["version"],
            "updates": snap["updates"],
            "update_rows": snap["update_rows"],
            "history": {"versions": snap["history"],
                        "capacity": self._history.maxlen},
            "rollbacks": self._n_rollbacks,
            "checkpoint": ckpt_stats,
            "probe": probe_stats,
            "routing": {str(k): v for k, v in sorted(self.routing.items())},
            "routing_updates": snap["routing_updates"],
            "mesh": (None if self._mesh is None else {
                "devices": int(self._mesh.devices.size),
                "axis": self._mesh.axis_names[0],
            }),
            "sparse_layout": (None if self._serve_ell is None
                              else self._serve_ell.stats()),
            "pipeline": {
                "depth": self.policy.pipeline_depth,
                "inflight": snap["inflight"],
                "inflight_versions": {str(k): v for k, v in
                                      sorted(snap["inflight_versions"]
                                             .items())},
                "update_inflight": (self._update_task is not None
                                    and not self._update_task.done()),
            },
            "deadline": {
                "requests": snap["deadline_reqs"],
                "misses": snap["deadline_misses"],
                "miss_rate": round(snap["deadline_misses"]
                                   / max(snap["deadline_reqs"], 1), 6),
                "admission_rejects": snap["admission_rejects"],
                "expired_drops": snap["expired_drops"],
                "slack_shed_batches": snap["slack_shed"],
            },
            "buckets": {str(k): v
                        for k, v in sorted(self._svc.snapshot().items())},
            "queue_wait": {
                "requests": snap["waited"],
                "total_ms": snap["wait_s"] * 1e3,
                "mean_ms": snap["wait_s"] * 1e3 / max(snap["waited"], 1),
            },
            "result_fetch": {
                "packed": snap["fetch_packed"],
                "per_leaf": snap["fetch_per_leaf"],
            },
            "engine_build": {
                "count": snap["engine_builds"],
                "seconds": snap["engine_build_s"],
            },
            "tiers": {
                "shed_backend": self.policy.shed_backend,
                "shed_qdepth": self.policy.shed_qdepth,
                "shed_batches": snap["shed_batches"],
                "shed_rows": snap["shed_rows"],
                "cascade_rows": snap["cascade_rows"],
                "escalated_rows": snap["escalated_rows"],
                "escalation_rate": round(
                    snap["escalated_rows"]
                    / max(snap["cascade_rows"], 1), 6),
            },
            "engine_cache": engine_cache_info(),
        }
