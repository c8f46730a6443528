"""VoteEngine: one backend-dispatched inference path for popcount + argmax.

The paper's point is that TM inference past clause evaluation — count the
votes, pick the winner — is *one fused operation* with many interchangeable
implementations (adder tree, SWAR words, MXU matmul chain, PDL delay race).
This module is the seam that makes them interchangeable in software:

- :class:`EngineResult` — what every backend returns: the prediction, the
  signed class sums, and backend-specific per-sample extras (``aux``).
- :class:`VoteEngine` — the protocol: ``infer(literals) -> EngineResult``.
  A backend whose result is int32 with no ``aux`` may also offer
  ``infer_packed(literals)``, the same result as one ``(B, 1 + C)`` array
  (:func:`pack_result`, built by :func:`packed_form`), which a server
  copies back to the host in one transfer.
- a string-keyed registry (:func:`register_backend`, :func:`get_engine`,
  :func:`available_backends`) so backend choice is a config knob, not a
  code fork.

Engines are built once per ``(TMConfig, TMState)`` pair: each backend
precompiles its own clause-state layout (include masks, bit-packed words,
vote matrices, delay tables) at construction, so per-call work is only the
math that depends on the input literals.

:func:`get_engine` additionally keeps a small keyed LRU cache of built
engines: repeated calls with the *same* (backend, cfg, state arrays,
options) — as ``tm.predict`` makes on every call — reuse the precompiled
layout instead of rebuilding it.  State identity is by array object
(``id``); entries hold only *weakrefs* to the state arrays and evict
themselves when a state is garbage-collected, so the cache can neither
confuse two different states nor retain dead ones.  A new ``TMState``
simply builds (and caches) a new engine.  ``get_engine(..., cache=False)``
bypasses it and :func:`clear_engine_cache` empties it.

``aux`` entries must be batch-leading arrays — that invariant is what lets
:class:`repro.engine.sharding.ShardedEngine` shard any backend's ``infer``
over the batch axis with a single ``PartitionSpec``, and what lets
:func:`infer_padded` strip padding rows from any backend's result.

Padding seam: serving coalesces variable-size requests into a small set of
bucket shapes (bounding XLA compilations).  :func:`pad_batch` /
:func:`infer_padded` implement that *backend-agnostically*: every
backend's ``infer`` is data-parallel over the batch axis — sample ``b``'s
prediction, class sums, and aux depend only on literal row ``b`` — so
extra all-zero rows provably cannot flip any real row's argmax and are
sliced off before the caller sees them.

The registry cache is guarded by a lock: a serving process hits
``get_engine`` from scheduler/executor threads concurrently, and the bare
``OrderedDict`` check-then-act sequences (``in`` → ``move_to_end``,
``len`` → ``popitem``) race without one.
"""

from __future__ import annotations

import functools
import threading
import weakref
from collections import OrderedDict
from typing import Callable, NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tm import TMConfig, TMState

__all__ = ["EngineResult", "VoteEngine", "Registry", "KeyedEngineCache",
           "ServiceStats", "nearest_rank",
           "register_backend", "get_engine",
           "available_backends", "clear_engine_cache", "engine_cache_info",
           "evict_engines_for_state", "weight_engines_for_state",
           "set_engine_cache_budget", "state_nbytes",
           "pad_batch", "infer_padded", "pack_result", "packed_form",
           "DEFAULT_BACKEND"]

DEFAULT_BACKEND = "oracle"
ENGINE_CACHE_SIZE = 16


class EngineResult(NamedTuple):
    """What every inference backend returns (all arrays batch-leading)."""

    prediction: jax.Array           # (B,) int32 — argmax class (ties → lowest)
    class_sums: jax.Array           # (B, C) int32 — signed vote counts
    aux: dict[str, jax.Array]       # backend extras; each array batch-leading


@runtime_checkable
class VoteEngine(Protocol):
    """A built inference engine over one (cfg, state) clause layout."""

    name: str
    cfg: TMConfig

    def infer(self, literals: jax.Array) -> EngineResult:
        """(B, 2F) {0,1} literals → :class:`EngineResult`."""
        ...


class Registry:
    """String-keyed backend factory registry.

    One instance per engine family — the :class:`VoteEngine` inference
    registry here and the ``TrainEngine`` registry in
    :mod:`repro.engine.train` share this machinery, so backend choice is
    a config knob on both paths.  ``kind`` names the family in error
    messages (e.g. ``"VoteEngine"``).
    """

    def __init__(self, kind: str):
        self.kind = kind
        self.factories: dict[str, Callable] = {}

    def register(self, name: str):
        """Class decorator: register a backend factory under ``name``."""
        def deco(factory):
            self.factories[name] = factory
            factory.name = name
            return factory
        return deco

    def names(self) -> list[str]:
        """Sorted names of all registered backends."""
        return sorted(self.factories)

    def build(self, name: str, *args, **opts):
        """Instantiate the named backend, ``KeyError`` on unknown names."""
        if name not in self.factories:
            raise KeyError(f"unknown {self.kind} backend {name!r}; "
                           f"available: {self.names()}")
        return self.factories[name](*args, **opts)


class KeyedEngineCache:
    """Thread-safe keyed LRU of built engines, weakref-pinned to state.

    Entries map a hashable key → (weakrefs to the key's state arrays,
    engine); an ``OrderedDict`` provides LRU order.  The weakref death
    callbacks evict an entry the moment any of its state arrays is
    garbage-collected, which (a) keeps id-based state identity sound — an
    id can only be recycled after the old array died, and by then its
    entry is gone — and (b) means the cache never retains dead states: a
    training loop predicting with a fresh state per step frees each old
    state's layout as soon as the caller drops it.

    Guarded by an RLock (not Lock): gc can run a weakref eviction
    callback on the thread that already holds the lock (e.g. while
    inserting triggers a collection), and a serving process hits the
    cache from scheduler/executor threads concurrently — the bare
    ``OrderedDict`` check-then-act sequences (``in`` → ``move_to_end``,
    ``len`` → ``popitem``) race without one.
    """

    def __init__(self, maxsize: int, max_bytes: int | None = None):
        self.maxsize = maxsize
        self.max_bytes = max_bytes
        self._data: OrderedDict[tuple, tuple] = OrderedDict()
        self._bytes = 0
        # id(array) -> (weakref-or-None, weight): the per-model weight
        # registry backing weighted eviction.  Keyed like entry pinning
        # (array identity) so a weight registered for a model's state
        # covers every engine built on that state.
        self._weights: dict[int, tuple] = {}
        self._stats = {"hits": 0, "misses": 0, "evictions": 0,
                       "superseded": 0}
        self._lock = threading.RLock()

    def get(self, key):
        """The cached engine for ``key`` (marking it most-recent), or None."""
        with self._lock:
            hit = self._data.get(key)
            if hit is None:
                return None
            self._data.move_to_end(key)
            self._stats["hits"] += 1
            return hit[1]

    def set_state_weight(self, state, weight: float) -> None:
        """Register eviction ``weight`` for every array in ``state``.

        Entries pinned to a weighted array are evicted *after* lighter
        ones regardless of recency (weight first, LRU as tie-break), so
        a hot model's engines survive budget pressure from cold
        siblings.  Unweighted entries default to weight 1.0.  The
        registry holds weakrefs — a weight dies with its arrays and can
        never pin them.
        """
        w = float(weight)
        for a in state:
            i = id(a)

            def _drop(_ref, _i=i):
                with self._lock:
                    self._weights.pop(_i, None)

            try:
                ref = weakref.ref(a, _drop)
            except TypeError:    # non-weakreferenceable leaf: weight only
                ref = None
            with self._lock:
                self._weights[i] = (ref, w)

    def _entry_weight_locked(self, refs) -> float:
        """Max registered weight over an entry's live pinned arrays."""
        w = None
        for r in refs:
            obj = r() if isinstance(r, weakref.ref) else r
            if obj is None:
                continue
            reg = self._weights.get(id(obj))
            if reg is not None and (w is None or reg[1] > w):
                w = reg[1]
        return 1.0 if w is None else w

    def _evict_one_locked(self) -> None:
        """Evict the minimum-(weight, LRU-age) entry (capacity path)."""
        victim, vw = None, None
        for k, ent in self._data.items():    # oldest -> newest
            w = self._entry_weight_locked(ent[0])
            if vw is None or w < vw:         # strict <: ties keep oldest
                victim, vw = k, w
        if victim is not None:
            self._bytes -= self._data.pop(victim)[2]
            self._stats["evictions"] += 1

    def _over_budget_locked(self) -> bool:
        return len(self._data) > self.maxsize or \
            (self.max_bytes is not None and self._bytes > self.max_bytes)

    def set_budget(self, maxsize: int | None = None,
                   max_bytes: int | None = None) -> None:
        """Update the entry and/or byte budget and evict down to it.

        ``None`` leaves a limit unchanged; ``max_bytes <= 0`` removes the
        byte limit.  Eviction under the new budget is weighted (see
        :meth:`set_state_weight`).
        """
        with self._lock:
            if maxsize is not None:
                self.maxsize = int(maxsize)
            if max_bytes is not None:
                self.max_bytes = int(max_bytes) if max_bytes > 0 else None
            while self._data and self._over_budget_locked():
                self._evict_one_locked()

    def insert(self, key, state, engine, nbytes: int | None = None) -> None:
        """Cache ``engine`` under ``key``, pinned to ``state``'s arrays.

        Holds only weakrefs to the arrays (self-evicting, see class
        docstring); a non-weakreferenceable leaf pins the array instead.
        ``nbytes`` (default: the summed ``nbytes`` of ``state``'s
        arrays, a proxy for the engine's layout footprint) charges the
        byte budget.  Evicts minimum-(weight, LRU-age) entries past
        ``maxsize`` / ``max_bytes``.  Replacing an existing key (the
        benign duplicate-build race in :func:`get_engine`) counts the
        displaced twin under ``"evictions"`` — otherwise ``misses``
        would silently stop reconciling with
        ``size + evictions + superseded``.
        """
        def _evict(_ref, _key=key):
            with self._lock:
                ent = self._data.pop(_key, None)
                if ent is not None:
                    self._bytes -= ent[2]
                    self._stats["evictions"] += 1

        try:
            refs = tuple(weakref.ref(a, _evict) for a in state)
        except TypeError:       # non-weakreferenceable leaf: pin instead
            refs = tuple(state)
        if nbytes is None:
            nbytes = sum(int(getattr(a, "nbytes", 0)) for a in state)
        with self._lock:
            self._stats["misses"] += 1
            old = self._data.pop(key, None)
            if old is not None:
                self._bytes -= old[2]
                self._stats["evictions"] += 1
            self._data[key] = (refs, engine, nbytes)
            self._bytes += nbytes
            while self._data and self._over_budget_locked():
                self._evict_one_locked()

    def evict_state(self, state) -> int:
        """Drop every entry pinned to any of ``state``'s arrays → count.

        The *superseded* eviction path: when a serving publish replaces
        a state, its cached engines' layouts are stale for the logical
        model yet stay pinned (the old arrays remain alive in the
        history ring / in-flight predicts), so LRU pressure is the only
        thing that would ever reclaim them.  Counted under
        ``"superseded"``, separate from ``"evictions"`` (capacity /
        state-death) — a growing superseded count under online learning
        is refresh working, not cache thrash.  An in-flight predict
        still pinned to the old state just rebuilds on its next miss;
        correctness never depends on an entry being present.
        """
        targets = {id(a) for a in state}

        def _held(r):
            obj = r() if isinstance(r, weakref.ref) else r
            return obj is not None and id(obj) in targets

        with self._lock:
            stale = [k for k, ent in self._data.items()
                     if any(_held(r) for r in ent[0])]
            for k in stale:
                self._bytes -= self._data.pop(k)[2]
            self._stats["superseded"] += len(stale)
            return len(stale)

    def clear(self) -> None:
        """Drop every cached engine, registered weight, and counter.

        A deliberate ``clear`` is not an eviction: the counter tracks
        entries pushed out by capacity or state death, the cache-health
        signal surfaced in ``TMServer.stats()``.
        """
        with self._lock:
            self._data.clear()
            self._weights.clear()
            self._bytes = 0
            for k in self._stats:
                self._stats[k] = 0

    def info(self) -> dict:
        """``{"size", "maxsize", "bytes", "max_bytes", "weights",
        "hits", "misses", "evictions", "superseded"}``."""
        with self._lock:
            return {"size": len(self._data), "maxsize": self.maxsize,
                    "bytes": self._bytes, "max_bytes": self.max_bytes,
                    "weights": len(self._weights), **self._stats}


def nearest_rank(sorted_vals, p: float) -> float:
    """The nearest-rank percentile (``ceil(p·n)``-th order statistic) of an
    ascending-sorted non-empty sequence — the one percentile definition
    shared by every latency reporter in the repo (``ServiceStats`` here,
    ``repro.serve.loadgen.percentiles_ms``, the serve bench), so admission
    control, ``stats()``, and ``check_perf.py`` all compare identical
    math.  Nearest-rank, not ``int(p·n)``: the latter is one rank high
    and would report the single worst outlier as p99 for any window of
    ≤100 samples."""
    import math
    return sorted_vals[min(len(sorted_vals) - 1,
                           max(0, math.ceil(p * len(sorted_vals)) - 1))]


class ServiceStats:
    """Thread-safe per-key service-time tracker: EWMA + fixed-size ring.

    The measurement seam between engine execution and scheduling policy:
    the serving worker thread calls :meth:`observe` with each engine
    call's wall time, and the event loop reads :meth:`ewma` /
    :meth:`floor` / :meth:`snapshot` for deadline admission control and
    ``stats()`` — both sides therefore see the *same* numbers, by
    construction.  Keys are arbitrary hashables (the TM server keys by
    padded bucket size).  Per key it keeps an exponentially-weighted
    moving average (the scheduler's expected-service estimate, tracking
    drift) and a bounded ring of recent raw samples (percentiles + the
    ring minimum, a lower bound used for "provably cannot meet the
    deadline" rejections).  A lock guards every access: observers run on
    worker threads while readers run on the event loop.
    """

    def __init__(self, alpha: float = 0.2, window: int = 512):
        if not 0.0 < alpha <= 1.0:
            raise ValueError(f"alpha must be in (0, 1], got {alpha}")
        self.alpha = alpha
        self.window = window
        self._ewma: dict = {}
        self._rings: dict = {}
        self._counts: dict = {}
        self._lock = threading.Lock()

    def observe(self, key, seconds: float) -> None:
        """Record one service time (seconds) under ``key``."""
        with self._lock:
            prev = self._ewma.get(key)
            self._ewma[key] = seconds if prev is None else \
                self.alpha * seconds + (1.0 - self.alpha) * prev
            ring = self._rings.get(key)
            if ring is None:
                from collections import deque
                ring = self._rings[key] = deque(maxlen=self.window)
            ring.append(seconds)
            self._counts[key] = self._counts.get(key, 0) + 1

    def ewma(self, key) -> float | None:
        """Expected service time (seconds) for ``key``; None if unseen."""
        with self._lock:
            return self._ewma.get(key)

    def floor(self, key) -> float | None:
        """Fastest service time (seconds) in ``key``'s ring; None if
        unseen.  A lower bound on how fast ``key`` can possibly be
        served right now — the admission-control side of "provably"."""
        with self._lock:
            ring = self._rings.get(key)
            return min(ring) if ring else None

    def snapshot(self) -> dict:
        """``{key: {count, ewma_ms, min_ms, p50_ms, p90_ms, p99_ms}}`` —
        one consistent copy of every key's measurements (ms, rounded),
        taken under the lock."""
        with self._lock:
            out = {}
            for key, ring in self._rings.items():
                lat = sorted(ring)
                out[key] = {
                    "count": self._counts[key],
                    "ewma_ms": round(self._ewma[key] * 1e3, 3),
                    "min_ms": round(lat[0] * 1e3, 3),
                    "p50_ms": round(nearest_rank(lat, 0.50) * 1e3, 3),
                    "p90_ms": round(nearest_rank(lat, 0.90) * 1e3, 3),
                    "p99_ms": round(nearest_rank(lat, 0.99) * 1e3, 3),
                }
            return out


_VOTE_REGISTRY = Registry("VoteEngine")
_REGISTRY = _VOTE_REGISTRY.factories      # back-compat alias (autotune, tests)
_ENGINE_CACHE = KeyedEngineCache(ENGINE_CACHE_SIZE)


def register_backend(name: str):
    """Class decorator: register a ``VoteEngine`` factory under ``name``."""
    return _VOTE_REGISTRY.register(name)


def available_backends() -> list[str]:
    """Sorted names of all registered backends."""
    from . import backends  # noqa: F401  (import side effect: registration)
    return _VOTE_REGISTRY.names()


def _cache_key(name, cfg, state, opts, *flags):
    """Hashable cache key, or ``None`` when opts aren't cacheable
    (e.g. a ``PDLDevice`` of arrays or a ``noise_key``).  ``state`` is
    the engine family's state pytree leaves (empty for train engines,
    which rebuild their layout from the state passed to each step)."""
    try:
        opts_key = tuple(sorted(opts.items()))
        state_key = tuple((id(a), a.shape, str(a.dtype)) for a in state)
        key = (name, cfg, state_key, flags, opts_key)
        hash(key)
    except TypeError:
        return None
    return key


def clear_engine_cache() -> None:
    """Drop every cached engine."""
    _ENGINE_CACHE.clear()


def engine_cache_info() -> dict:
    """``{"size", "maxsize", "hits", "misses", "evictions",
    "superseded"}`` of the engine cache (surfaced as the
    ``engine_cache`` block of ``TMServer.stats()``)."""
    return _ENGINE_CACHE.info()


def evict_engines_for_state(state: TMState) -> int:
    """Evict every cached engine built on ``state`` → count evicted.

    Called by ``TMServer._publish`` with the superseded state so a
    refreshed logical model does not leak its old layouts until LRU
    pressure (see :meth:`KeyedEngineCache.evict_state`).
    """
    return _ENGINE_CACHE.evict_state(state)


def weight_engines_for_state(state: TMState, weight: float) -> None:
    """Register eviction ``weight`` for engines built on ``state``.

    The fleet seam for weighted eviction: ``TMFleet`` registers each
    model's request share here on every publish, so under a shared
    budget a hot model's engines outlive a cold model's regardless of
    which was touched last (see
    :meth:`KeyedEngineCache.set_state_weight`).
    """
    _ENGINE_CACHE.set_state_weight(state, weight)


def set_engine_cache_budget(max_entries: int | None = None,
                            max_bytes: int | None = None) -> dict:
    """Set the process-wide engine-cache budget → fresh cache info.

    ``max_entries`` bounds entry count (default ``ENGINE_CACHE_SIZE``);
    ``max_bytes`` bounds the summed state-array footprint of cached
    layouts (``<= 0`` removes the byte limit).  ``None`` leaves a limit
    unchanged.  Shrinking evicts immediately, minimum-weight first.
    """
    _ENGINE_CACHE.set_budget(max_entries, max_bytes)
    return _ENGINE_CACHE.info()


def state_nbytes(state) -> int:
    """Summed ``nbytes`` over a state pytree's array leaves — the byte
    proxy the engine cache charges per entry, exposed so fleet budget
    math (``set_engine_cache_budget``) can be phrased in model sizes."""
    return sum(int(getattr(a, "nbytes", 0)) for a in state)


class DonatingEngine:
    """Wrap ``infer`` in a jit that donates the literal buffer.

    Safe only when the caller never reuses a literal batch after the call
    (streaming serving).  Donation is input→output aliasing: it only pays
    off when a backend output matches the literal buffer's shape/dtype —
    none of the built-in backends' int32 results do today, so this is a
    forward-compatibility hook (e.g. a backend echoing packed literals),
    not a current-CPU win.  XLA's "donated buffers were not usable"
    trace-time warning is suppressed here because unusable donation is
    this wrapper's documented, harmless fallback.
    """

    def __init__(self, inner: VoteEngine):
        self.inner = inner
        self.cfg = inner.cfg
        self.name = f"{inner.name}+donate"
        self._jit = jax.jit(inner.infer, donate_argnums=0)

    def infer(self, literals: jax.Array) -> EngineResult:
        """``inner.infer`` through the donating jit (same contract)."""
        import warnings
        with warnings.catch_warnings():
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            return self._jit(literals)


def get_engine(name: str, cfg: TMConfig, state: TMState, *,
               shard_batch=False, cache: bool = True,
               donate_literals: bool = False, **opts) -> VoteEngine:
    """Build (or fetch from cache) the named backend's engine.

    ``shard_batch=True`` wraps ``infer`` in a ``shard_map`` over the batch
    axis across all local devices (multi-device serving); a
    ``jax.sharding.Mesh`` serves over that specific 1-D mesh instead
    (``Mesh`` is hashable, so mesh-wrapped engines cache normally — this
    is how a mesh-configured ``TMServer`` keys its sharded bucket
    engines).  Extra ``opts`` are forwarded to the backend constructor
    (e.g. ``pdl=PDLConfig(...)`` or ``device=PDLDevice(...)`` for
    ``time_domain``).

    Tunable backends (``mxu_fused``, ``swar_fused``) whose tile opts are
    not given explicitly get them from the autotune cache
    (:mod:`repro.engine.autotune`) when an entry for this shape exists.

    ``cache=True`` (default) memoizes built engines by (backend, cfg,
    state-array identity, options) in a small LRU, so repeated calls —
    ``tm.predict`` builds an engine per call — skip layout precompile.
    ``donate_literals=True`` wraps ``infer`` to donate the input literal
    buffer to XLA; only safe if callers never reuse a batch after the call.
    """
    from . import backends  # noqa: F401  (import side effect: registration)
    from . import autotune
    for opt, val in autotune.lookup(name, cfg).items():
        opts.setdefault(opt, val)

    key = _cache_key(name, cfg, state, opts, shard_batch, donate_literals) \
        if cache else None
    if key is not None:
        hit = _ENGINE_CACHE.get(key)
        if hit is not None:
            return hit

    # build outside the lock: layout precompile can take milliseconds and
    # must not serialize unrelated threads.  Two threads missing on the
    # same key both build; the second insert wins — benign, both engines
    # are equivalent.
    engine = _VOTE_REGISTRY.build(name, cfg, state, **opts)
    if shard_batch:
        from .sharding import ShardedEngine
        mesh = shard_batch if not isinstance(shard_batch, bool) else None
        engine = ShardedEngine(engine, mesh=mesh)
    if donate_literals:
        engine = DonatingEngine(engine)
    if key is not None:
        _ENGINE_CACHE.insert(key, state, engine)
    return engine


def pad_batch(literals: jax.Array, bucket: int) -> jax.Array:
    """Pad a ``(B, L)`` literal batch with all-zero rows up to ``bucket``.

    Zero rows are *neutral*: every backend's ``infer`` is data-parallel
    over the batch axis, so a padding row can only produce its own
    (discarded) result — it provably cannot flip any real row's argmax or
    perturb its class sums.  ``B == bucket`` returns the input unchanged;
    ``B > bucket`` is an error (the caller picked the wrong bucket).
    """
    b = literals.shape[0]
    if b > bucket:
        raise ValueError(f"batch of {b} rows does not fit bucket {bucket}")
    if b == bucket:
        return literals
    # numpy input pads in numpy: host-side assembly costs no XLA compile
    # per (b, bucket) combination — the serving scheduler depends on this
    # (its engine call is then the *only* traced shape, one per bucket)
    xp = np if isinstance(literals, np.ndarray) else jnp
    pad = xp.zeros((bucket - b,) + literals.shape[1:], literals.dtype)
    return xp.concatenate([literals, pad], axis=0)


def infer_padded(engine: VoteEngine, literals: jax.Array,
                 bucket: int) -> EngineResult:
    """``engine.infer`` at the bucket shape; results sliced to the real rows.

    The backend-agnostic serving seam: one XLA compilation per (engine,
    bucket) regardless of request sizes.  Relies on the two registry
    invariants — batch-axis data parallelism (zero pad rows are inert, see
    :func:`pad_batch`) and batch-leading ``aux`` arrays (so extras slice
    the same way as predictions).  Exact for every deterministic backend;
    a ``time_domain`` engine built with a ``noise_key`` draws jitter
    shaped by the *padded* batch, so its per-sample noise (not its
    layout) differs from an unpadded call.

    Numpy literals mark a host-side caller (serving, its warm-up): the
    result is wanted on the host, so its copy back is asked for at once
    (:func:`_host_result`: one transfer from an engine with
    ``infer_packed``), and it is sliced in numpy, so no per-(bucket, b)
    slice op is ever traced.
    """
    b = literals.shape[0]
    if isinstance(literals, np.ndarray):
        res = _host_result(engine, pad_batch(literals, bucket))
        if b == bucket:
            return res
        return EngineResult(
            np.asarray(res.prediction)[:b], np.asarray(res.class_sums)[:b],
            {k: np.asarray(v)[:b] for k, v in res.aux.items()})
    res = engine.infer(pad_batch(literals, bucket))
    if b == bucket:
        return res
    return EngineResult(res.prediction[:b], res.class_sums[:b],
                        {k: v[:b] for k, v in res.aux.items()})


def _host_result(engine: VoteEngine, literals) -> EngineResult:
    """``engine.infer(literals)`` with the copy to the host asked for at
    once, so the transfer queues behind the device's work and
    ``np.asarray`` of a leaf only waits for it (the first one waits for
    the device).

    An engine with ``infer_packed`` runs that program instead, and its
    leaves are columns of the one ``(B, 1 + C)`` array (:func:`pack_result`):
    one transfer for the whole result, each leaf a numpy view of it.  Any
    other engine's leaves are its own, each jax array's copy asked for
    (numpy leaves, which a cascade's host path returns, are there
    already).  The choice follows what the engine offers, nothing else.
    """
    infer_packed = getattr(engine, "infer_packed", None)
    if infer_packed is None:
        res = engine.infer(literals)
        for leaf in jax.tree_util.tree_leaves(res):
            if isinstance(leaf, jax.Array):
                leaf.copy_to_host_async()
        return res
    buf = infer_packed(literals)
    buf.copy_to_host_async()
    b, w = buf.shape
    return EngineResult(_HostColumns(buf, 0, (b,)),
                        _HostColumns(buf, slice(1, None), (b, w - 1)), {})


class _HostColumns:
    """Columns of a device array whose copy to the host was asked for, as
    an array-like leaf: ``np.asarray`` waits for the copy and gives a
    view.  JAX keeps the copy, so every column view of one array shares
    one transfer."""

    def __init__(self, buf: jax.Array, cols, shape: tuple[int, ...]):
        self._buf, self._cols = buf, cols
        self.shape, self.dtype = shape, np.dtype(buf.dtype)

    def __array__(self, dtype=None, copy=None):
        out = np.asarray(self._buf)[:, self._cols]
        if dtype is not None:
            out = out.astype(dtype, copy=False)
        return out.copy() if copy else out

    def __len__(self) -> int:
        return self.shape[0]

    def __getitem__(self, index):
        return np.asarray(self)[index]


def pack_result(res: EngineResult) -> jax.Array:
    """An extras-free result as one ``(B, 1 + C)`` array: the prediction
    in column 0, the class sums in columns 1..C.

    The layout of a backend's ``infer_packed``: a served batch then comes
    back to the host in one transfer instead of one per leaf.  Both
    leaves keep their dtype (int32 for every built-in backend); a result
    with ``aux`` or with leaves of two dtypes has no packed form."""
    if res.aux:
        raise ValueError(f"a result with aux {sorted(res.aux)} has no "
                         f"packed form")
    if res.prediction.dtype != res.class_sums.dtype:
        raise TypeError(f"prediction {res.prediction.dtype} and class sums "
                        f"{res.class_sums.dtype} do not pack into one array")
    return jnp.concatenate([res.prediction[:, None], res.class_sums], axis=1)


def packed_form(infer_fn: Callable, **jit_opts) -> Callable:
    """The jitted packed form of a backend's jitted infer body: the same
    arguments, one :func:`pack_result` array out.  ``infer_fn`` is traced
    inside it, so both forms share one implementation; ``jit_opts``
    (``static_argnames``) repeat the body's.  The program is named after
    the body with ``_packed`` appended, so a trace tells the two apart."""
    @functools.wraps(infer_fn)
    def packed(*args, **kwargs):
        return pack_result(infer_fn(*args, **kwargs))
    packed.__name__ = packed.__qualname__ = f"{infer_fn.__name__}_packed"
    return jax.jit(packed, **jit_opts)
