"""TrainEngine: one backend-dispatched TM training path.

The inference registry (:mod:`repro.engine.base`) made popcount+argmax a
config knob; this module does the same for the *training* step, so a
production system can learn while it serves (Prescott et al., "An FPGA
Architecture for Online Learning using the Tsetlin Machine") with the
data-parallel batch update of Abeyrathna et al. ("Massively Parallel and
Asynchronous Tsetlin Machine Architecture") running on whichever layout
is fastest for the deployment target:

- :class:`TrainEngine` — the protocol: ``step(state, key, literals,
  labels) -> TMState``.
- a string-keyed registry (:func:`register_train_backend`,
  :func:`get_train_engine`, :func:`available_train_backends`) built on
  the same :class:`repro.engine.base.Registry` /
  :class:`repro.engine.base.KeyedEngineCache` machinery as inference.

Unlike inference engines, train engines precompile **no state-derived
layout** — the state changes on every step, so anything derived from it
(packed include words, clause layouts) is rebuilt inside the jitted step
and the keyed LRU cache keys on (backend, cfg, opts) only.

Delta-exactness contract: every backend consumes the step key through
:func:`repro.core.tm_train.feedback_masks` (identical splits, identical
uniform shapes) and computes bit-identical clause outputs and class sums,
so for a fixed PRNG key all backends return bitwise-identical new states
(property-tested in ``tests/test_train_engine.py``).  Switching backends
is purely a performance decision, exactly like inference.

======================  ====================================================
``reference``           wraps :func:`repro.core.tm_train.train_step` — the
                        dense einsum formulation, the functional oracle.
``packed``              bit-packed literals + SWAR clause evaluation (the
                        ``swar_packed`` inference layout) feeding the
                        shared feedback math — clause eval as word-ANDs.
``fused``               SWAR-fused class sums (the ``swar_fused`` Pallas
                        kernel on TPU) plus one XLA body fusing
                        addressed-class clause eval + Type I/II delta
                        generation + the class-free per-class scatter
                        (:func:`repro.kernels.train_fused.train_deltas`).
``sparse``              clause-indexed: class sums come from the ELL
                        gather path (:mod:`repro.kernels.ell_gather`) on
                        an incrementally-refreshed layout
                        (:class:`repro.engine.sparse.IncrementalEll`),
                        then the fused delta body applies feedback —
                        O(R·K) clause eval instead of O(R·L) at trained
                        include densities.
======================  ====================================================

The one exception to "no state-derived layout" above is ``sparse``: its
ELL index matrix *is* state-derived, so the engine carries an
:class:`~repro.engine.sparse.IncrementalEll` that it refreshes from the
include deltas of each step's input state — O(changed rows), not a
rebuild — before launching the jitted step.  That host-side refresh
needs a concrete state; under a trace (``train_epoch``'s ``lax.scan``)
the engine falls back to the bit-identical ``packed`` step, exactly like
the cascade engine's tracer fallback.
"""

from __future__ import annotations

import functools
from typing import Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.core.popcount import pack_bits
from repro.core.tm import TMConfig, TMState, clause_polarity
from repro.core.tm_train import (feedback_draws, feedback_masks,
                                 feedback_thresholds, feedback_update,
                                 train_step)
from repro.distributed.sharding import data_mesh
from repro.kernels.clause_eval import make_vote_matrix
from repro.kernels.ell_gather import ell_clause_votes
from repro.kernels.ops import on_tpu
from repro.kernels.swar_fused import swar_fused_votes_pallas
from repro.kernels.train_fused import feedback_polarity_masks, train_deltas

from .backends import swar_clauses_votes
from .base import KeyedEngineCache, Registry, _cache_key
from .sparse import (DEFAULT_K_SLACK, DEFAULT_REBUILD_THRESHOLD,
                     IncrementalEll)

__all__ = ["TrainEngine", "register_train_backend", "get_train_engine",
           "available_train_backends", "clear_train_engine_cache",
           "train_engine_cache_info", "DEFAULT_TRAIN_BACKEND",
           "ReferenceTrainEngine", "PackedTrainEngine", "FusedTrainEngine",
           "SparseTrainEngine", "ShardedTrainEngine", "export_key_cursor",
           "import_key_cursor", "train_engine_opts"]

DEFAULT_TRAIN_BACKEND = "reference"
TRAIN_ENGINE_CACHE_SIZE = 8


@runtime_checkable
class TrainEngine(Protocol):
    """A built training engine for one clause geometry (cfg, not state)."""

    name: str
    cfg: TMConfig

    def step(self, state: TMState, key: jax.Array, x_literals: jax.Array,
             y: jax.Array) -> TMState:
        """One batched update: (B, 2F) {0,1} literals + (B,) int32 labels
        → the new ``TMState`` (states clipped to [1, 2N])."""
        ...


_TRAIN_REGISTRY = Registry("TrainEngine")
_TRAIN_CACHE = KeyedEngineCache(TRAIN_ENGINE_CACHE_SIZE)


def register_train_backend(name: str):
    """Class decorator: register a ``TrainEngine`` factory under ``name``."""
    return _TRAIN_REGISTRY.register(name)


def available_train_backends() -> list[str]:
    """Sorted names of all registered training backends."""
    return _TRAIN_REGISTRY.names()


def clear_train_engine_cache() -> None:
    """Drop every cached training engine."""
    _TRAIN_CACHE.clear()


def train_engine_cache_info() -> dict:
    """``{"size", "maxsize", "hits", "misses"}`` of the train-engine cache."""
    return _TRAIN_CACHE.info()


def get_train_engine(name: str, cfg: TMConfig, *, cache: bool = True,
                     **opts) -> TrainEngine:
    """Build (or fetch from cache) the named training backend's engine.

    Extra ``opts`` are forwarded to the backend constructor (e.g.
    ``boost_tpf=False``, or ``k_slack`` for ``sparse``).

    ``cache=True`` (default) memoizes built engines by (backend, cfg,
    options) in a small keyed LRU — no state in the key, because train
    engines derive nothing from the state at build time (the state is a
    per-step argument).
    """
    key = _cache_key(name, cfg, (), opts) if cache else None
    if key is not None:
        hit = _TRAIN_CACHE.get(key)
        if hit is not None:
            return hit
    engine = _TRAIN_REGISTRY.build(name, cfg, **opts)
    if key is not None:
        _TRAIN_CACHE.insert(key, (), engine)
    return engine


def export_key_cursor(key: jax.Array) -> tuple:
    """Serialize an update-key-chain cursor → ``(data, impl)``.

    ``data`` is the raw ``uint32`` key data (an ordinary array leaf a
    checkpoint can shard); ``impl`` is the PRNG implementation name
    (``"threefry2x32"``/``"rbg"``) that :func:`import_key_cursor` needs
    to rebuild a typed key.  Round-tripping through these is bit-exact,
    so a restored server resumes the *same* deterministic chain — update
    ``i+1`` after a restore draws the key the uninterrupted run would
    have drawn.
    """
    import numpy as np
    return (np.asarray(jax.random.key_data(key)),
            str(jax.random.key_impl(key)))


def import_key_cursor(data, impl: str) -> jax.Array:
    """Rebuild a typed PRNG key from :func:`export_key_cursor` output."""
    return jax.random.wrap_key_data(jnp.asarray(data, dtype=jnp.uint32),
                                    impl=impl)


def train_engine_opts(engine: TrainEngine) -> dict:
    """The constructor opts a built engine was resolved with — what a
    checkpoint must persist so a restore on a different host rebuilds the
    *same* engine.  Backends expose this via ``lifecycle_opts``; engines
    without it snapshot nothing."""
    fn = getattr(engine, "lifecycle_opts", None)
    return dict(fn()) if fn is not None else {}


# tile opts of the retired Pallas delta kernel: checkpoints written while
# the fused/sparse/sharded trainers took them still persist them, and a
# restore must drop them rather than pass them to today's constructors
RETIRED_TRAIN_OPTS = ("block_b", "block_m")


def _packed_clauses_votes(cfg, state, x, pos_mask, neg_mask):
    """SWAR clause eval + class sums on the bit-packed word layout.

    Packs include words from the live state, then delegates to the one
    shared word body (:func:`repro.engine.backends.swar_clauses_votes`)
    so training inherits the inference backends' bit-exactness.
    x: (B, 2F) {0,1} literals → (clauses (B, C, M) int8, votes (B, C)
    int32).
    """
    c, m = cfg.n_classes, cfg.n_clauses
    inc = (state.ta > cfg.n_states).astype(jnp.int8)
    inc_words = pack_bits(inc.reshape(c * m, cfg.n_literals))    # (CM, Wl)
    return swar_clauses_votes(inc_words, pos_mask, neg_mask, x, c=c, m=m)


@functools.partial(jax.jit, static_argnames=("cfg", "boost_tpf"))
def _packed_step(cfg, state, key, x, y, pos_mask, neg_mask, *, boost_tpf):
    clauses, votes = _packed_clauses_votes(cfg, state, x, pos_mask, neg_mask)
    return feedback_update(cfg, state, key, x, y, clauses, votes,
                           boost_tpf=boost_tpf)


def _deltas_from_votes(cfg, state, key, x, y, votes, *, boost_tpf):
    """Shared tail of the fused/sparse steps: feedback masks → raw
    uniform words → fused delta body → clipped new state.

    Every input bit downstream of ``votes`` is backend-independent, so
    any two backends that produce bit-identical ``votes`` and share this
    tail return bitwise-identical states for the same key — that is the
    whole delta-exactness argument for ``sparse`` vs ``fused``.
    """
    c, m = cfg.n_classes, cfg.n_clauses
    inc8 = (state.ta > cfg.n_states).astype(jnp.int8)            # (C, M, L)
    y_neg, fb_t, fb_n, k1s, k2s = feedback_masks(cfg, key, votes, y)
    # the raw words jax.random.uniform would float-convert — the kernel
    # compares them against exact integer thresholds instead; generated
    # per row from the per-row keys, the sharding-invariant draw shape
    gen = jax.vmap(lambda k: jax.random.bits(k, (m, cfg.n_literals),
                                             jnp.uint32))
    bits1 = gen(k1s)
    bits2 = gen(k2s)

    pos = (clause_polarity(m) > 0)[None, :]                      # (1, M)
    m1_t, m2_t, m1_n, m2_n = feedback_polarity_masks(fb_t, fb_n, pos)

    p_inc = 1.0 if boost_tpf else (cfg.s - 1.0) / cfg.s
    upd = train_deltas(x, bits1, bits2, inc8[y], inc8[y_neg],
                       m1_t, m2_t, m1_n, m2_n, y, y_neg,
                       n_classes=c, p_inc=p_inc, p_dec=1.0 / cfg.s)
    ta = jnp.clip(state.ta + upd, 1, 2 * cfg.n_states)
    return TMState(ta=ta)


@functools.partial(jax.jit, static_argnames=("cfg", "boost_tpf",
                                             "interpret"))
def _fused_step(cfg, state, key, x, y, vm, pos_mask, neg_mask, *, boost_tpf,
                interpret):
    c, m = cfg.n_classes, cfg.n_clauses
    if interpret:
        # CPU: SWAR word votes as straight-line XLA (the vote kernel's
        # interpreter overhead outweighs its fusion win off-TPU)
        _, votes = _packed_clauses_votes(cfg, state, x, pos_mask, neg_mask)
    else:
        inc8 = (state.ta > cfg.n_states).astype(jnp.int8)        # (C, M, L)
        inc_words = pack_bits(inc8.reshape(c * m, cfg.n_literals))
        not_words = pack_bits((1 - x).astype(jnp.int8))
        votes = swar_fused_votes_pallas(not_words, inc_words, vm,
                                        interpret=False)         # (B, C)
    return _deltas_from_votes(cfg, state, key, x, y, votes,
                              boost_tpf=boost_tpf)


@functools.partial(jax.jit, static_argnames=("cfg", "boost_tpf"))
def _sparse_step(cfg, state, key, x, y, indices, *, boost_tpf):
    """Clause-indexed step: votes from the ELL gather over ``indices``
    (which the caller guarantees matches ``state``'s include mask), then
    the shared fused-delta tail."""
    c, m = cfg.n_classes, cfg.n_clauses
    _, votes = ell_clause_votes(indices, clause_polarity(m), x, c=c, m=m)
    return _deltas_from_votes(cfg, state, key, x, y, votes,
                              boost_tpf=boost_tpf)


@functools.partial(jax.jit, static_argnames=("cfg", "mesh", "boost_tpf"))
def _sharded_step(cfg, state, key, x, y, pos_mask, neg_mask, *, mesh,
                  boost_tpf):
    """Data-parallel train step over a 1-D mesh, bit-identical to
    ``_fused_step`` for any device count.

    The exactness argument has three legs:

    1. **Per-row randomness.**  The small draws (negative-class offsets,
       feedback uniforms, per-row threefry keys) come from one global
       :func:`feedback_draws` call outside the ``shard_map`` — exactly
       the fused backend's splits.  The *large* draw — the (M, 2F)
       Type I uniform words per row — is generated inside the body from
       each row's own key, so a shard generates only its rows' words yet
       every row sees byte-identical randomness under any mesh size.
       (Generating the words globally instead would replicate the full
       (B, M, 2F) generation onto every device: GSPMD cannot partition
       a bulk RNG op, a D× fixed cost that dwarfed the training math.)
    2. **Row-local body.**  Clause eval, class sums, feedback thresholds,
       polarity routing, and per-sample deltas are all row-local, so each
       shard computes exactly the rows the single-host step would.
    3. **Exact reduction.**  Deltas are integers in {−1, 0, 1} summed per
       class; ``jax.lax.psum_scatter`` of the per-shard integer partial
       sums is associative-exact, so the reduction equals the single-host
       segment-sum bitwise.

    The *state* legs are sharded over classes, not rows: each device
    packs the include mask / clause words for its ``Cp/D`` class slice
    and ``all_gather``s the (small, bit-packed) results, and the final
    ``clip`` of the reduce-scattered update runs on the same class slice
    before a tiled gather reassembles the replicated state.  Everything
    O(C·M·L) therefore costs each device 1/D of the single-host step —
    computed replicated, those legs alone would make the shard seam a
    D× slowdown on a simulated (serialised) mesh.  Classes pad to a
    device multiple with never-addressed all-exclude rows (``ta = 1``;
    ``y``/``y_neg`` are always < C).

    Ragged batches pad the *drawn* arrays to a device multiple with
    neutral rows — ``u = 2.0`` (> any activation probability, so the
    feedback masks are all-False), zero literals/labels, and row 0's key
    repeated — whose deltas are provably zero, so padding never perturbs
    real rows.
    """
    b = x.shape[0]
    c, m = cfg.n_classes, cfg.n_clauses
    axis = mesh.axis_names[0]
    d = mesh.shape[axis]

    offs, u, k1s, k2s = feedback_draws(cfg, key, b)

    bp = -(-b // d) * d
    if bp != b:
        pad = bp - b
        x = jnp.pad(x, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad))
        offs = jnp.pad(offs, (0, pad), constant_values=1)
        u = jnp.pad(u, ((0, pad), (0, 0), (0, 0)), constant_values=2.0)
        # padded rows repeat row 0's key — harmless, their u = 2.0 rows
        # yield all-False feedback masks so the drawn words are never used
        padk = jnp.broadcast_to(k1s[:1], (pad,))
        k1s = jnp.concatenate([k1s, padk])
        k2s = jnp.concatenate([k2s, padk])

    pos = (clause_polarity(m) > 0)[None, :]                      # (1, M)
    p_inc = 1.0 if boost_tpf else (cfg.s - 1.0) / cfg.s

    cp = -(-c // d) * d                                          # class pad
    cs = cp // d                                                 # per-device
    ta = state.ta if cp == c else jnp.pad(
        state.ta, ((0, cp - c), (0, 0), (0, 0)), constant_values=1)

    # a literal collects at most one target + one negative contribution
    # per row, so the cross-shard reduction stays exact in int16 while
    # 2B < 2¹⁵ — half the collective payload; absurd batches widen
    narrow = bp < 2 ** 14

    def body(ta_s, pm, nm, x_s, y_s, offs_s, u_s, k1_s, k2_s):
        # class-sharded state prep: pack this device's class slice, then
        # gather the bit-packed words (every shard evals all clauses)
        inc_s = (ta_s > cfg.n_states).astype(jnp.int8)           # (cs, M, L)
        words_s = pack_bits(inc_s.reshape(cs * m, cfg.n_literals))
        inc = jax.lax.all_gather(inc_s, axis, tiled=True)        # (Cp, M, L)
        words = jax.lax.all_gather(words_s, axis, tiled=True)    # (CpM, Wl)

        _, votes = swar_clauses_votes(words, pm, nm, x_s, c=cp, m=m)
        y_neg, fb_t, fb_n = feedback_thresholds(cfg, votes, y_s, offs_s, u_s)
        m1_t, m2_t, m1_n, m2_n = feedback_polarity_masks(fb_t, fb_n, pos)
        # each shard generates only its own rows' uniform words — the
        # per-row threefry draw is bit-identical to the fused backend's
        gen = jax.vmap(lambda k: jax.random.bits(k, (m, cfg.n_literals),
                                                 jnp.uint32))
        upd = train_deltas(x_s, gen(k1_s), gen(k2_s), inc[y_s], inc[y_neg],
                           m1_t, m2_t, m1_n, m2_n, y_s, y_neg,
                           n_classes=cp, p_inc=p_inc, p_dec=1.0 / cfg.s,
                           widen=not narrow)
        # reduce-scatter the class-segmented partials so the O(C·M·L)
        # clip runs on each device's class slice, then reassemble
        upd_s = jax.lax.psum_scatter(upd, axis, scatter_dimension=0,
                                     tiled=True)                 # (cs, M, L)
        return jnp.clip(ta_s + upd_s.astype(jnp.int32),
                        1, 2 * cfg.n_states)

    # ta crosses the boundary class-sharded in *and* out: consecutive
    # sharded steps (the serving loop, the train_epoch scan) hand the
    # state from shard to shard with no broadcast or gather at all —
    # JAX reassembles the replicated view lazily only when a consumer
    # (inference, checkpointing) actually reads it
    rep, sh = P(), P(axis)
    ta = jax.shard_map(body, mesh=mesh,
                       in_specs=(sh, rep, rep, sh, sh, sh, sh, sh, sh),
                       out_specs=sh, check_vma=False)(
        ta, pos_mask, neg_mask, x, y, offs, u, k1s, k2s)
    return TMState(ta=ta[:c])


@register_train_backend("sharded")
class ShardedTrainEngine:
    """Data-parallel training over the batch axis of a ``("data",)`` mesh.

    ``shard_map``s the fused clause-eval + delta body across the mesh and
    ``psum``s the class-free per-shard delta sums — the Abeyrathna et al.
    "massively parallel" batch update made literal.  Bit-identical to the
    single-host ``fused`` backend for *any* device count (the whole
    contract — see :func:`_sharded_step` — is property-tested in
    ``tests/test_multihost.py`` for D ∈ {1, 2, 4, 8}), so mesh size is a
    pure throughput knob and a checkpoint trained on one mesh resumes
    bit-exactly on another (``tests/test_elastic_restore.py``).

    ``mesh=`` shards over an existing 1-D mesh; ``n_devices=`` builds a
    :func:`repro.distributed.sharding.data_mesh` over that many local
    devices (``None`` = all).  Fully traceable — no host callbacks — so
    the ``train_epoch`` ``lax.scan`` path shards each scanned step.
    """

    def __init__(self, cfg: TMConfig, *, boost_tpf: bool = True,
                 n_devices: int | None = None, mesh=None):
        self.cfg = cfg
        self.boost_tpf = boost_tpf
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    f"sharded training needs a 1-D mesh, got "
                    f"{mesh.axis_names}")
            self.mesh = mesh
        else:
            self.mesh = data_mesh(n_devices)
        self.n_devices = self.mesh.shape[self.mesh.axis_names[0]]
        pol = clause_polarity(cfg.n_clauses)
        self._pos_mask = pack_bits((pol > 0).astype(jnp.int8))   # (Wm,)
        self._neg_mask = pack_bits((pol < 0).astype(jnp.int8))

    def step(self, state: TMState, key: jax.Array, x_literals: jax.Array,
             y: jax.Array) -> TMState:
        """One mesh-sharded update (see :class:`TrainEngine`)."""
        return _sharded_step(self.cfg, state, key, x_literals, y,
                             self._pos_mask, self._neg_mask,
                             mesh=self.mesh, boost_tpf=self.boost_tpf)

    def lifecycle_opts(self) -> dict:
        """Constructor opts to persist in a checkpoint (see
        :func:`train_engine_opts`).  Persists the mesh *size*, not the
        mesh: devices are host-local, and a restore host clamps or
        overrides the size (elastic restore) — safe because training is
        mesh-size invariant."""
        return {"boost_tpf": self.boost_tpf, "n_devices": self.n_devices}


@register_train_backend("reference")
class ReferenceTrainEngine:
    """Wraps :func:`repro.core.tm_train.train_step` — the dense oracle."""

    def __init__(self, cfg: TMConfig, *, boost_tpf: bool = True):
        self.cfg = cfg
        self.boost_tpf = boost_tpf

    def step(self, state: TMState, key: jax.Array, x_literals: jax.Array,
             y: jax.Array) -> TMState:
        """One reference update (see :class:`TrainEngine`)."""
        return train_step(self.cfg, state, key, x_literals, y,
                          boost_tpf=self.boost_tpf)

    def lifecycle_opts(self) -> dict:
        """Constructor opts to persist in a checkpoint (see
        :func:`train_engine_opts`)."""
        return {"boost_tpf": self.boost_tpf}


@register_train_backend("packed")
class PackedTrainEngine:
    """Bit-packed SWAR clause eval feeding the shared feedback math.

    Clause evaluation and class sums run on the ``swar_packed`` inference
    layout — include masks and literals as uint32 words, clause outputs
    from word-ANDs, votes from polarity-masked SWAR popcounts — and the
    bit-exact clause/vote bits then drive the reference delta math
    (:func:`repro.core.tm_train.feedback_update`).  Build time packs only
    the state-independent polarity masks; include words repack from the
    live state inside the jitted step.
    """

    def __init__(self, cfg: TMConfig, *, boost_tpf: bool = True):
        self.cfg = cfg
        self.boost_tpf = boost_tpf
        pol = clause_polarity(cfg.n_clauses)
        self._pos_mask = pack_bits((pol > 0).astype(jnp.int8))   # (Wm,)
        self._neg_mask = pack_bits((pol < 0).astype(jnp.int8))

    def step(self, state: TMState, key: jax.Array, x_literals: jax.Array,
             y: jax.Array) -> TMState:
        """One packed-layout update (see :class:`TrainEngine`)."""
        return _packed_step(self.cfg, state, key, x_literals, y,
                            self._pos_mask, self._neg_mask,
                            boost_tpf=self.boost_tpf)

    def lifecycle_opts(self) -> dict:
        """Constructor opts to persist in a checkpoint (see
        :func:`train_engine_opts`)."""
        return {"boost_tpf": self.boost_tpf}


@register_train_backend("fused")
class FusedTrainEngine:
    """Fused training: per-sample deltas never materialize in HBM.

    Class sums come from the SWAR word layout (the ``swar_fused``
    inference kernel on TPU, its straight-line XLA twin on CPU); the
    feedback masks and raw Type I uniform words are sampled via the
    shared PRNG contract; then the fused delta computation
    (``repro.kernels.train_fused.train_deltas``) does addressed-class
    clause eval + Type I/II delta generation + a class-free segment-sum
    scatter in one jitted body instead of the reference's six per-sample
    ``(B, M, 2F)`` delta tensors and one-hot einsums.
    """

    def __init__(self, cfg: TMConfig, *, boost_tpf: bool = True):
        self.cfg = cfg
        self.boost_tpf = boost_tpf
        self._vm = make_vote_matrix(cfg.n_classes, cfg.n_clauses)
        pol = clause_polarity(cfg.n_clauses)
        self._pos_mask = pack_bits((pol > 0).astype(jnp.int8))   # (Wm,)
        self._neg_mask = pack_bits((pol < 0).astype(jnp.int8))

    def step(self, state: TMState, key: jax.Array, x_literals: jax.Array,
             y: jax.Array) -> TMState:
        """One fused-kernel update (see :class:`TrainEngine`)."""
        return _fused_step(self.cfg, state, key, x_literals, y, self._vm,
                           self._pos_mask, self._neg_mask,
                           boost_tpf=self.boost_tpf,
                           interpret=not on_tpu())

    def lifecycle_opts(self) -> dict:
        """Constructor opts to persist in a checkpoint (see
        :func:`train_engine_opts`)."""
        return {"boost_tpf": self.boost_tpf}


@register_train_backend("sparse")
class SparseTrainEngine:
    """Clause-indexed training: ELL-gathered class sums, fused deltas.

    Class sums come from the batch-bit-packed gather over the ELL index
    matrix (:func:`repro.kernels.ell_gather.ell_clause_votes`) — O(R·K)
    per 32-sample word instead of the dense O(R·L) — and the shared
    fused-delta tail (:func:`_deltas_from_votes`) applies feedback, so
    the backend is delta-exact vs ``reference``/``packed``/``fused`` for
    the same key.  The index matrix is state-derived, so the engine
    carries an :class:`~repro.engine.sparse.IncrementalEll` and refreshes
    it from each step's input state by include deltas: O(changed rows)
    host work per step (≤ 2·M rows change per update — only the target
    and negative classes get feedback), with a full vectorized rebuild
    only on K overflow or ``rebuild_threshold`` cumulative drift.

    Meant to win over ``fused`` when include density is low enough that
    clause eval dominates the step (small B, large L); the crossover has
    not been measured on a chip.  Under a trace
    (``train_epoch``'s ``lax.scan``) the host-side refresh is impossible,
    so :meth:`step` falls back to the bit-identical packed step.

    ``k_slack``/``rebuild_threshold`` tune the layout refresh policy.
    """

    def __init__(self, cfg: TMConfig, *, boost_tpf: bool = True,
                 k_slack: int = DEFAULT_K_SLACK,
                 rebuild_threshold: float = DEFAULT_REBUILD_THRESHOLD):
        self.cfg = cfg
        self.boost_tpf = boost_tpf
        self.k_slack = int(k_slack)
        self.rebuild_threshold = float(rebuild_threshold)
        self._ell: IncrementalEll | None = None
        pol = clause_polarity(cfg.n_clauses)
        self._pos_mask = pack_bits((pol > 0).astype(jnp.int8))   # (Wm,)
        self._neg_mask = pack_bits((pol < 0).astype(jnp.int8))

    def _refresh(self, state: TMState) -> jax.Array:
        """Sync the incremental layout to ``state`` → the index matrix."""
        cfg = self.cfg
        inc = (np.asarray(state.ta) > cfg.n_states).reshape(
            cfg.n_classes * cfg.n_clauses, cfg.n_literals)
        if self._ell is None:
            self._ell = IncrementalEll(
                inc, k_slack=self.k_slack,
                rebuild_threshold=self.rebuild_threshold)
        else:
            self._ell.refresh(inc)
        return self._ell.layout.indices

    def step(self, state: TMState, key: jax.Array, x_literals: jax.Array,
             y: jax.Array) -> TMState:
        """One clause-indexed update (see :class:`TrainEngine`)."""
        if isinstance(state.ta, jax.core.Tracer):
            # under scan/jit the host-side layout refresh is impossible;
            # the packed step is bit-identical (same PRNG contract)
            return _packed_step(self.cfg, state, key, x_literals, y,
                                self._pos_mask, self._neg_mask,
                                boost_tpf=self.boost_tpf)
        indices = self._refresh(state)
        return _sparse_step(self.cfg, state, key, x_literals, y, indices,
                            boost_tpf=self.boost_tpf)

    def layout_stats(self) -> dict | None:
        """Refresh counters of the engine's :class:`IncrementalEll`
        (``None`` before the first concrete step)."""
        return None if self._ell is None else self._ell.stats()

    def lifecycle_opts(self) -> dict:
        """Constructor opts to persist in a checkpoint (see
        :func:`train_engine_opts`)."""
        return {"boost_tpf": self.boost_tpf, "k_slack": self.k_slack,
                "rebuild_threshold": self.rebuild_threshold}
