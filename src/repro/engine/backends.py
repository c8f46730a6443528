"""Built-in VoteEngine backends.

Seven interchangeable implementations of the paper's fused popcount+argmax,
one per hardware idea:

======================  ====================================================
``oracle``              einsum violations matmul + ±1 dot + tournament
                        argmax — the functional reference.
``adder_tree``          same clause eval; class sums via pairwise binary
                        adder trees (the "generic" FPGA baseline structure).
``swar_packed``         bit-packed clause storage (``pack_bits``): include
                        masks and clause outputs live as uint32 words;
                        violations are bitwise ANDs, sums are SWAR popcounts
                        of polarity-masked words — memory-optimal layout.
``swar_fused``          the bit-packed layout, fused in one Pallas kernel
                        (``swar_fused_votes_pallas``): blocked word-AND +
                        in-kernel SWAR popcount + vote matmul — the
                        ``(B, C·M, Wl)`` hit tensor never leaves VMEM.
``sparse_csr``          clause-indexed (padded CSR/ELL) layout over only the
                        *included* literals: batch-bit-packed gather + AND
                        reduction — O(density) clause work, the trained-TM
                        sparsity fast path.
``mxu_fused``           the Pallas kernel (``clause_votes_pallas``): two
                        chained MXU matmuls, clause matrix never in HBM.
``time_domain``         the paper's PDL race: chain delays affine in the
                        vote count, arbiter-tree argmin (``race``).
======================  ====================================================

``mxu_fused`` and ``swar_fused`` take ``block_b``/``block_cm`` tile opts;
when not given explicitly, ``get_engine`` consults the autotune cache
(:mod:`repro.engine.autotune`) before falling back to the defaults.

Every backend precompiles its clause-state layout from ``TMState`` at
construction (include masks, packed words, vote matrices, polarity masks),
so ``infer`` does only literal-dependent work.  The jitted compute lives
in *module-level* functions — engines built for the same shapes share one
XLA compilation via JAX's jit cache, so constructing an engine per call
(as ``tm.predict`` does) costs a cache lookup, not a recompile.

All five return bit-exact identical ``prediction`` and ``class_sums``
(property-tested in ``tests/test_engine.py``), including tie cases
(lowest index wins).  Every backend here but ``time_domain`` (whose
``aux`` carries float latencies) also offers ``infer_packed``: its own
jitted body under :func:`~repro.engine.base.packed_form`, the result as
one ``(B, 1 + C)`` int32 array that a server copies back in one transfer.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.popcount import (argmax_tournament, pack_bits,
                                 popcount_adder_tree, popcount_swar,
                                 signed_vote_count, unpack_bits)
from repro.core.time_domain import PDLConfig, PDLDevice, pdl_delays, race
from repro.core.tm import TMConfig, TMState, clause_polarity, include_mask
from repro.kernels.clause_eval import clause_votes_pallas, make_vote_matrix
from repro.kernels.ops import on_tpu
from repro.kernels.swar_fused import swar_fused_votes_pallas

from .base import EngineResult, packed_form, register_backend
from .sparse import ell_from_include, sparse_clause_words

__all__ = ["OracleEngine", "AdderTreeEngine", "SwarPackedEngine",
           "SwarFusedEngine", "SparseCSREngine", "MXUFusedEngine",
           "TimeDomainEngine", "swar_clauses_votes"]


def _clause_bits(inc: jax.Array, literals: jax.Array) -> jax.Array:
    """(C, M, L) int32 include × (B, L) {0,1} literals → (B, C, M) int8.

    Violation-count formulation (matches the MXU kernel bit-exactly):
    a clause fires iff no included literal is 0.
    """
    viol = jnp.einsum("bf,cmf->bcm", (1 - literals).astype(jnp.int32), inc)
    return (viol == 0).astype(jnp.int8)


@jax.jit
def _oracle_infer(inc, pol, literals):
    clauses = _clause_bits(inc, literals)
    sums = signed_vote_count(clauses, pol[None, None, :])
    return EngineResult(argmax_tournament(sums), sums, {})


_oracle_infer_packed = packed_form(_oracle_infer)


@jax.jit
def _adder_tree_infer(inc, pol, literals):
    clauses = _clause_bits(inc, literals)
    pos = (pol > 0).astype(jnp.int8)[None, None, :]
    neg = (pol < 0).astype(jnp.int8)[None, None, :]
    sums = (popcount_adder_tree(clauses * pos) -
            popcount_adder_tree(clauses * neg))
    return EngineResult(argmax_tournament(sums), sums, {})


_adder_tree_infer_packed = packed_form(_adder_tree_infer)


def swar_clauses_votes(inc_words, pos_mask, neg_mask, literals, *, c, m):
    """The SWAR word body shared by inference and training.

    inc_words (C·M, Wl) uint32 packed include masks; pos_mask/neg_mask
    (Wm,) uint32 packed clause polarities; literals (B, 2F) {0,1} →
    (clauses (B, C, M) int8, votes (B, C) int32), bit-exact with the
    dense oracle: a clause fires iff ``include_word & ~literal_word == 0``
    for every word, votes are polarity-masked SWAR popcounts of the
    repacked clause words.  One implementation on purpose — the
    ``swar_packed`` backend and ``PackedTrainEngine``/``FusedTrainEngine``
    all inherit their parity from this body.
    """
    not_words = pack_bits((1 - literals).astype(jnp.int8))       # (B, Wl)
    hit = inc_words[None, :, :] & not_words[:, None, :]          # (B, CM, Wl)
    clauses = jnp.all(hit == 0, axis=-1).reshape(-1, c, m) \
        .astype(jnp.int8)                                        # (B, C, M)
    words = pack_bits(clauses)                                   # (B, C, Wm)
    votes = (popcount_swar(words & pos_mask) -
             popcount_swar(words & neg_mask))
    return clauses, votes


@functools.partial(jax.jit, static_argnames=("c", "m"))
def _swar_infer(inc_words, pos_mask, neg_mask, literals, *, c, m):
    _, sums = swar_clauses_votes(inc_words, pos_mask, neg_mask, literals,
                                 c=c, m=m)
    return EngineResult(argmax_tournament(sums), sums, {})


_swar_infer_packed = packed_form(_swar_infer, static_argnames=("c", "m"))


@functools.partial(jax.jit, static_argnames=("block_b", "block_cm",
                                             "interpret"))
def _swar_fused_infer(inc_words, vm, literals, *, block_b, block_cm,
                      interpret):
    not_words = pack_bits((1 - literals).astype(jnp.int8))       # (B, Wl)
    sums = swar_fused_votes_pallas(not_words, inc_words, vm,
                                   block_b=block_b, block_cm=block_cm,
                                   interpret=interpret)
    return EngineResult(argmax_tournament(sums), sums, {})


_swar_fused_infer_packed = packed_form(
    _swar_fused_infer, static_argnames=("block_b", "block_cm", "interpret"))


@functools.partial(jax.jit, static_argnames=("c", "m"))
def _sparse_csr_infer(indices, pol, literals, *, c, m):
    cw = sparse_clause_words(indices, literals)      # (CM, Wb) uint32
    clauses = unpack_bits(cw, literals.shape[0])     # (CM, B) int8
    cl = clauses.reshape(c, m, -1).astype(jnp.int32)
    sums = jnp.einsum("cmb,m->bc", cl, pol)
    return EngineResult(argmax_tournament(sums), sums, {})


_sparse_csr_infer_packed = packed_form(_sparse_csr_infer,
                                       static_argnames=("c", "m"))


@functools.partial(jax.jit, static_argnames=("block_b", "block_cm",
                                             "interpret"))
def _mxu_infer(inc, vm, literals, *, block_b, block_cm, interpret):
    sums = clause_votes_pallas(literals, inc, vm, block_b=block_b,
                               block_cm=block_cm, interpret=interpret)
    return EngineResult(argmax_tournament(sums), sums, {})


_mxu_infer_packed = packed_form(
    _mxu_infer, static_argnames=("block_b", "block_cm", "interpret"))


@functools.partial(jax.jit, static_argnames=("pdl", "n_neg"))
def _time_domain_infer(inc, pol, device, noise_key, literals, *, pdl, n_neg):
    clauses = _clause_bits(inc, literals)
    pos = (pol > 0)[None, None, :]
    low_sel = jnp.where(pos, clauses, 1 - clauses)               # (B, C, M)
    low_count = low_sel.astype(jnp.int32).sum(-1)                # (B, C)
    sums = low_count - n_neg              # low_count = votes + n_neg
    if device is None:
        delays = (pol.shape[0] * pdl.d_high
                  - pdl.delta * low_count.astype(jnp.float32))
    else:
        delays = pdl_delays(pdl, device, clauses, pol, key=noise_key)
    res = race(pdl, delays)
    aux = {"latency_ps": res.latency, "metastable": res.metastable}
    return EngineResult(res.winner, sums, aux)


@register_backend("oracle")
class OracleEngine:
    """Functional reference: einsum clause eval + ±1 dot + tournament."""

    _infer = staticmethod(_oracle_infer)
    _infer_packed = staticmethod(_oracle_infer_packed)

    def __init__(self, cfg: TMConfig, state: TMState):
        self.cfg = cfg
        self._inc = include_mask(cfg, state).astype(jnp.int32)   # (C, M, L)
        self._pol = clause_polarity(cfg.n_clauses)               # (M,) ±1

    def infer(self, literals: jax.Array) -> EngineResult:
        """(B, 2F) {0,1} literals → :class:`EngineResult` (bit-exact)."""
        return self._infer(self._inc, self._pol, literals)

    def infer_packed(self, literals: jax.Array) -> jax.Array:
        """:meth:`infer` as one ``(B, 1 + C)`` int32 array."""
        return self._infer_packed(self._inc, self._pol, literals)


@register_backend("adder_tree")
class AdderTreeEngine(OracleEngine):
    """Class sums as two pairwise adder trees (+ votes, − votes).

    Mirrors the generic FPGA popcount: depth ``ceil(log2 M)`` per tree,
    which is the critical path the paper's time-domain design removes.
    """

    _infer = staticmethod(_adder_tree_infer)
    _infer_packed = staticmethod(_adder_tree_infer_packed)


@register_backend("swar_packed")
class SwarPackedEngine:
    """Bit-packed clause storage: words all the way down.

    Build time: include masks pack to ``(C·M, ceil(L/32))`` uint32 and the
    clause polarity packs to two ``(ceil(M/32),)`` masks.  Infer: a clause
    violates iff ``include_word & ~literal_word ≠ 0`` for any word; clause
    outputs repack over the M axis and the class sum is
    ``swar(words & pos_mask) − swar(words & neg_mask)``.
    """

    def __init__(self, cfg: TMConfig, state: TMState):
        self.cfg = cfg
        inc = include_mask(cfg, state).reshape(
            cfg.n_classes * cfg.n_clauses, cfg.n_literals)
        self._inc_words = pack_bits(inc)                         # (CM, Wl)
        pol = clause_polarity(cfg.n_clauses)
        self._pos_mask = pack_bits((pol > 0).astype(jnp.int8))   # (Wm,)
        self._neg_mask = pack_bits((pol < 0).astype(jnp.int8))

    def infer(self, literals: jax.Array) -> EngineResult:
        """(B, 2F) {0,1} literals → :class:`EngineResult` (bit-exact)."""
        return _swar_infer(self._inc_words, self._pos_mask, self._neg_mask,
                           literals, c=self.cfg.n_classes,
                           m=self.cfg.n_clauses)

    def infer_packed(self, literals: jax.Array) -> jax.Array:
        """:meth:`infer` as one ``(B, 1 + C)`` int32 array."""
        return _swar_infer_packed(self._inc_words, self._pos_mask,
                                  self._neg_mask, literals,
                                  c=self.cfg.n_classes, m=self.cfg.n_clauses)


@register_backend("swar_fused")
class SwarFusedEngine:
    """Fused bit-packed kernel: word-AND + SWAR popcount + vote matmul.

    Same uint32 layout as ``swar_packed``, but the whole reduction chain
    runs blocked inside one Pallas kernel, so the ``(B, C·M, Wl)`` hit
    tensor only ever exists as a per-tile VMEM block instead of an HBM
    intermediate.  ``block_b``/``block_cm`` are autotunable.
    """

    def __init__(self, cfg: TMConfig, state: TMState, *,
                 block_b: int = 8, block_cm: int = 128):
        self.cfg = cfg
        inc = include_mask(cfg, state).reshape(
            cfg.n_classes * cfg.n_clauses, cfg.n_literals)
        self._inc_words = pack_bits(inc)                         # (CM, Wl)
        self._vm = make_vote_matrix(cfg.n_classes, cfg.n_clauses)
        self._blocks = (block_b, block_cm)

    def infer(self, literals: jax.Array) -> EngineResult:
        """(B, 2F) {0,1} literals → :class:`EngineResult` (bit-exact)."""
        return _swar_fused_infer(self._inc_words, self._vm, literals,
                                 block_b=self._blocks[0],
                                 block_cm=self._blocks[1],
                                 interpret=not on_tpu())

    def infer_packed(self, literals: jax.Array) -> jax.Array:
        """:meth:`infer` as one ``(B, 1 + C)`` int32 array."""
        return _swar_fused_infer_packed(self._inc_words, self._vm, literals,
                                        block_b=self._blocks[0],
                                        block_cm=self._blocks[1],
                                        interpret=not on_tpu())


@register_backend("sparse_csr")
class SparseCSREngine:
    """Clause-indexed sparsity fast path (padded CSR/ELL gather).

    Build time: the include mask compresses to one ``(C·M, K)`` index
    matrix over only the *included* literals (``K`` = max includes per
    clause — ≈ 5% of L for trained machines).  Infer: literals bit-pack
    over the batch axis, each clause gathers its K rows and AND-reduces —
    clause-eval work scales with the include density instead of L.

    ``ell=`` injects a prebuilt layout instead of compressing the state
    here — the ``TMServer`` publish path passes its incrementally
    refreshed :class:`~repro.engine.sparse.IncrementalEll` layout, so a
    publish costs O(changed rows), not a from-scratch build.  The caller
    guarantees the layout matches ``state``'s include mask (only shapes
    are validated); note an ``EllLayout`` holds jax arrays, so an
    ``ell=`` build is unhashable for the keyed engine cache — pass
    ``cache=False`` (the server keeps its own one-slot cache).
    """

    def __init__(self, cfg: TMConfig, state: TMState, *, ell=None):
        self.cfg = cfg
        r = cfg.n_classes * cfg.n_clauses
        if ell is None:
            inc = include_mask(cfg, state).reshape(r, cfg.n_literals)
            ell = ell_from_include(inc)
        elif (ell.indices.shape[0] != r
                or ell.n_literals != cfg.n_literals):
            raise ValueError(
                f"ell layout is ({ell.indices.shape[0]} rows, "
                f"L={ell.n_literals}); cfg needs ({r}, "
                f"L={cfg.n_literals})")
        self.ell = ell
        self._pol = clause_polarity(cfg.n_clauses)

    def infer(self, literals: jax.Array) -> EngineResult:
        """(B, 2F) {0,1} literals → :class:`EngineResult` (bit-exact)."""
        return _sparse_csr_infer(self.ell.indices, self._pol, literals,
                                 c=self.cfg.n_classes,
                                 m=self.cfg.n_clauses)

    def infer_packed(self, literals: jax.Array) -> jax.Array:
        """:meth:`infer` as one ``(B, 1 + C)`` int32 array."""
        return _sparse_csr_infer_packed(self.ell.indices, self._pol,
                                        literals, c=self.cfg.n_classes,
                                        m=self.cfg.n_clauses)


@register_backend("mxu_fused")
class MXUFusedEngine:
    """Fused Pallas kernel: clause-eval matmul chained into the vote matmul
    so the (B, C·M) clause matrix never round-trips through HBM."""

    def __init__(self, cfg: TMConfig, state: TMState, *,
                 block_b: int = 128, block_cm: int = 128):
        self.cfg = cfg
        self._inc = include_mask(cfg, state).reshape(
            cfg.n_classes * cfg.n_clauses, cfg.n_literals)       # (CM, L) int8
        self._vm = make_vote_matrix(cfg.n_classes, cfg.n_clauses)
        self._blocks = (block_b, block_cm)

    def infer(self, literals: jax.Array) -> EngineResult:
        """(B, 2F) {0,1} literals → :class:`EngineResult` (bit-exact)."""
        return _mxu_infer(self._inc, self._vm, literals,
                          block_b=self._blocks[0], block_cm=self._blocks[1],
                          interpret=not on_tpu())

    def infer_packed(self, literals: jax.Array) -> jax.Array:
        """:meth:`infer` as one ``(B, 1 + C)`` int32 array."""
        return _mxu_infer_packed(self._inc, self._vm, literals,
                                 block_b=self._blocks[0],
                                 block_cm=self._blocks[1],
                                 interpret=not on_tpu())


@register_backend("time_domain")
class TimeDomainEngine:
    """The paper's race: PDL chain delays + arbiter-tree argmin.

    Default is the *ideal* device (no variation, no skew): chain delay is
    the affine ``M·d_high − Δ·low_count`` computed from the integer low-net
    count, so equal vote sums race to an exact tie and the arbiter's
    predetermined guess (lowest index) matches the oracle argmax bit-exactly.
    Pass ``device=PDLDevice(...)`` to simulate a physical chip via
    per-element delays — then oracle agreement is physics, not arithmetic.

    ``aux``: per-sample ``latency_ps`` (winning arrival, data-dependent —
    paper §IV-A) and ``metastable`` (any arbiter gap < t_res).
    """

    def __init__(self, cfg: TMConfig, state: TMState, *,
                 pdl: PDLConfig | None = None,
                 device: PDLDevice | None = None,
                 noise_key: jax.Array | None = None):
        self.cfg = cfg
        self.pdl = pdl if pdl is not None else PDLConfig(sigma_elem=0.0,
                                                         sigma_noise=0.0)
        self.device = device
        self.noise_key = noise_key      # per-event jitter (device path only)
        self._inc = include_mask(cfg, state).astype(jnp.int32)
        self._pol = clause_polarity(cfg.n_clauses)
        self._n_neg = cfg.n_clauses // 2        # odd-index (opposing) clauses

    def infer(self, literals: jax.Array) -> EngineResult:
        """(B, 2F) {0,1} literals → :class:`EngineResult`; ``aux`` carries
        per-sample ``latency_ps`` (f32) and ``metastable`` (bool)."""
        return _time_domain_infer(self._inc, self._pol, self.device,
                                  self.noise_key, literals, pdl=self.pdl,
                                  n_neg=self._n_neg)
