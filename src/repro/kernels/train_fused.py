"""Fused TM clause-eval + Type I/II feedback-delta update (one XLA body).

The reference training step (``repro.core.tm_train.feedback_update``)
materializes *six* per-sample ``(B, M, 2F)`` int32 tensors in HBM — two
Type I deltas, two Type II deltas, and the two masked per-class combines —
before reducing them to the ``(C, M, 2F)`` state update with a pair of
dense one-hot einsums (the conceptual ``(B, C·M, 2F)`` scatter tensor,
``O(B·C·M·2F)`` work).  The fused formulation here collapses that chain:

    cl_t[b,m]  = (Σ_f inc_t[b,m,f] · (1 − lit[b,f])) == 0     (clause eval)
    d1         = TypeI(cl_t, lit, bits1)                      (bitwise)
    d2         = TypeII(cl_t, lit, inc_t)
    delta_t    = where(m1_t, d1, 0) + where(m2_t, d2, 0)
    upd[y[b]] += delta_t[b]                                   (segment-sum)

(and the same for the sampled negative class with ``bits2``/``y_neg``,
Type I/II roles swapped by the ``m*_n`` masks).  The per-class scatter is
a *class-free* batch segment-sum — ``O(B·M·2F)`` adds instead of the
reference's ``O(B·C·M·2F)`` one-hot matmuls.

:func:`train_deltas` runs the delta body (``_delta_body``) and the
class-free segment-sum as one jitted XLA computation on every platform.
There is no Pallas kernel for this step: Mosaic has no TPU lowering for
an in-kernel scatter-add.

Delta-exactness: the Type I randomness enters as the *raw* uniform words
(``jax.random.bits`` — the very words ``jax.random.uniform`` converts to
floats; same key ⇒ same words, see ``repro.core.tm_train.feedback_masks``).
The reference compares ``u < p`` on ``u = (bits >> 9) · 2⁻²³``; both
sides are exactly representable in f32, so the comparison is equivalent
to the integer test ``(bits >> 9) < ceil(f32(p) · 2²³)``
(:func:`uniform_threshold`) — the decisions are bitwise identical
(property-tested in ``tests/test_train_engine.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["train_deltas", "uniform_threshold", "feedback_polarity_masks"]


def feedback_polarity_masks(fb_t: jax.Array, fb_n: jax.Array,
                            pos: jax.Array) -> tuple:
    """Route feedback activations to Type I/II by clause polarity.

    fb_t/fb_n (B, M) bool — target/negative-class feedback activations
    (from ``repro.core.tm_train.feedback_thresholds``); pos (1, M) bool —
    positive-polarity clause mask → the four ``(m1_t, m2_t, m1_n, m2_n)``
    masks :func:`train_deltas` consumes: the target class sends Type I to
    positive clauses and Type II to negative ones, the negative class
    swaps the roles.  Row-local, so single-host and per-shard callers
    produce identical masks for identical rows — the one routing table
    both the fused and sharded train steps share.
    """
    m1_t = fb_t & pos
    m2_t = fb_t & ~pos
    m1_n = fb_n & ~pos
    m2_n = fb_n & pos
    return m1_t, m2_t, m1_n, m2_n


def uniform_threshold(p: float) -> int:
    """The uint32 threshold ``t`` with ``uniform_bits >> 9 < t`` ⟺ ``u < p``.

    ``jax.random.uniform`` builds ``u = m · 2⁻²³`` from the top 23 bits
    ``m = bits >> 9``; ``u`` and ``f32(p)`` are both exactly representable,
    so ``u < p`` ⟺ ``m < ceil(f32(p) · 2²³)`` — exactly, for every ``p``.
    """
    return int(math.ceil(float(np.float32(p)) * (1 << 23)))


def _delta_body(lit, bits1, bits2, inc_t, inc_n, m1_t, m2_t, m1_n, m2_n,
                *, t_inc, t_dec):
    """Per-sample Type I/II deltas.

    lit (B, L) {0,1}; bits1/bits2 (B, M, L) uint32; inc_t/inc_n
    (B, M, L) {0,1}; m*_* (B, M) bool → (d_t, d_n), each
    (B, M, L) int16 in {−1, 0, 1} (int16 keeps the delta stream half
    the width of the reference's int32 one; the summed magnitude per
    (class, clause, literal) is ≤ B ≪ 2¹⁵).
    """
    # clause outputs of the addressed classes: violation-count formulation,
    # kept in int8 ({0,1} products) with an int32 reduction
    not_lit = (1 - lit)[:, None, :]                      # (B, 1, L) int8
    cl_t = (jnp.sum(inc_t * not_lit, axis=-1, dtype=jnp.int32)
            == 0)[:, :, None]
    cl_n = (jnp.sum(inc_n * not_lit, axis=-1, dtype=jnp.int32)
            == 0)[:, :, None]

    lit0 = (lit == 0)[:, None, :]                        # (B, 1, L)
    t_i = jnp.uint32(t_inc)
    t_d = jnp.uint32(t_dec)

    def type_i(cl, bits):
        # same decisions as tm_train._type_i_delta: the integer compare on
        # the top 23 uniform bits is exactly the reference's ``u < p``;
        # (cl ∧ ¬lit) ∨ ¬cl simplifies to ¬cl ∨ ¬lit
        m = bits >> 9
        inc_r = cl & ~lit0 & (m < t_i)
        dec = (~cl | lit0) & (m < t_d)
        return inc_r.astype(jnp.int16) - dec.astype(jnp.int16)

    def type_ii(cl, inc_bm):
        return (cl & lit0 & (inc_bm == 0)).astype(jnp.int16)

    # target class: Type I on +polarity clauses, Type II on −polarity;
    # roles swap for the negative class (encoded in the m*_* masks)
    zero = jnp.int16(0)
    d_t = jnp.where(m1_t[:, :, None], type_i(cl_t, bits1), zero) \
        + jnp.where(m2_t[:, :, None], type_ii(cl_t, inc_t), zero)
    d_n = jnp.where(m1_n[:, :, None], type_i(cl_n, bits2), zero) \
        + jnp.where(m2_n[:, :, None], type_ii(cl_n, inc_n), zero)
    return d_t, d_n


@functools.partial(jax.jit, static_argnames=("n_classes", "p_inc", "p_dec",
                                             "widen"))
def train_deltas(literals: jax.Array, bits1: jax.Array, bits2: jax.Array,
                 inc_t: jax.Array, inc_n: jax.Array,
                 m1_t: jax.Array, m2_t: jax.Array,
                 m1_n: jax.Array, m2_n: jax.Array,
                 y: jax.Array, y_neg: jax.Array, *, n_classes: int,
                 p_inc: float, p_dec: float, widen: bool = True) -> jax.Array:
    """Fused Type I/II feedback deltas, summed per class over the batch.

    literals (B, L) {0,1} int8; bits1/bits2 (B, M, L) uint32 — the raw
    target/negative Type I uniform words (``jax.random.bits`` under the
    keys from ``feedback_masks``); inc_t/inc_n (B, M, L) {0,1} int8 —
    the addressed-class include masks (``include[y]`` / ``include[y_neg]``);
    m1_t/m2_t/m1_n/m2_n (B, M) bool — feedback-activation × polarity
    masks selecting Type I/II per (sample, clause); y/y_neg (B,) int32 →
    upd (C, M, L) int32, the summed per-class delta.

    ``p_inc`` is the Type I include-reinforce probability
    (1 if boost_tpf else (s−1)/s) and ``p_dec`` the exclude-reinforce
    probability 1/s; both become exact integer thresholds on the uniform
    bits (:func:`uniform_threshold`).

    ``widen=False`` returns the int16 per-element sums directly (exact
    while 2B < 2¹⁵ — a literal can collect at most one target and one
    negative contribution per row) instead of widening to int32 — the
    sharded trainer reduce-scatters the partials across shards first and
    widens after, halving the collective payload.
    """
    d_t, d_n = _delta_body(literals, bits1, bits2, inc_t, inc_n,
                           m1_t, m2_t, m1_n, m2_n,
                           t_inc=uniform_threshold(p_inc),
                           t_dec=uniform_threshold(p_dec))
    b, m, l = d_t.shape
    # one class-free scatter over the 2B concatenated target/negative
    # streams in int16 (per-element sums are ≤ 2B, far under 2¹⁵ for sane
    # batches) — a single segment_sum zero-inits and walks the (C, M·L)
    # output once instead of twice, which matters when this runs once per
    # shard of a data-parallel mesh
    upd = jax.ops.segment_sum(
        jnp.concatenate([d_t.reshape(b, m * l), d_n.reshape(b, m * l)]),
        jnp.concatenate([y, y_neg]), num_segments=n_classes)
    if not widen:
        return upd.reshape(n_classes, m, l)
    return upd.astype(jnp.int32).reshape(n_classes, m, l)
