"""Vanilla Tsetlin Machine training (Granmo 2018), vectorized in JAX.

Per sample with label ``y``:
- target class ``y`` receives feedback with per-clause probability
  ``(T − clip(v_y)) / 2T``; a uniformly sampled negative class ``ŷ`` with
  probability ``(T + clip(v_ŷ)) / 2T``.
- On the target class, positive-polarity clauses receive Type I feedback and
  negative-polarity clauses Type II; on the negative class the roles swap.

Type I (combats false negatives; drives clauses toward matching patterns):
  clause=1, literal=1 : include-reinforce (+1) w.p. (s−1)/s  (1.0 if boost_tpf)
  clause=1, literal=0 : exclude-reinforce (−1) w.p. 1/s
  clause=0            : exclude-reinforce (−1) w.p. 1/s (all literals)
Type II (combats false positives; adds discriminating literals):
  clause=1, literal=0 : +1 w.p. 1  (only on currently excluded literals)

States clip to [1, 2N].  The batch update sums per-sample deltas before
clipping — the standard data-parallel TM approximation (Abeyrathna et al.,
"massively parallel" TM), which preserves convergence in practice and makes
the update a single ``einsum``-shaped reduction (DP-shardable over batch).

This module is the *functional reference*; :mod:`repro.engine.train`
provides interchangeable ``TrainEngine`` backends (bit-packed SWAR clause
eval, a fused delta body) that are delta-exact with it for the
same PRNG key.  The PRNG contract that makes them exchangeable lives in
:func:`feedback_masks` / :func:`feedback_update`: every backend splits the
step key identically, derives the same per-row threefry keys, and draws
each row's uniforms from that row's key alone, so the sampled feedback
decisions are bitwise identical no matter which layout evaluated the
clauses — or how the batch was sharded across devices.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .tm import TMConfig, TMState, class_sums, clause_outputs, clause_polarity

__all__ = ["feedback_draws", "feedback_thresholds", "feedback_masks",
           "feedback_update", "train_step", "train_epoch", "evaluate"]


def _type_i_delta(keys: jax.Array, clause: jax.Array, literals: jax.Array,
                  s: float, boost_tpf: bool) -> jax.Array:
    """Type I feedback delta for one class block.

    keys: (B,) per-row threefry keys (see :func:`feedback_draws`);
    clause: (B, M) {0,1}; literals: (B, 2F) {0,1} → delta (B, M, 2F) int32.
    """
    b, m = clause.shape
    f2 = literals.shape[-1]
    u = jax.vmap(lambda k: jax.random.uniform(k, (m, f2)))(keys)
    lit = literals[:, None, :]                      # (B, 1, 2F)
    cl = clause[:, :, None]                         # (B, M, 1)
    p_inc = 1.0 if boost_tpf else (s - 1.0) / s
    inc = (cl == 1) & (lit == 1) & (u < p_inc)      # reinforce include
    dec_match = (cl == 1) & (lit == 0) & (u < 1.0 / s)
    dec_nomatch = (cl == 0) & (u < 1.0 / s)
    return inc.astype(jnp.int32) - (dec_match | dec_nomatch).astype(jnp.int32)


def _type_ii_delta(clause: jax.Array, literals: jax.Array,
                   included: jax.Array) -> jax.Array:
    """Type II feedback: +1 on excluded literals that are 0 in firing clauses."""
    lit = literals[:, None, :]                      # (B, 1, 2F)
    cl = clause[:, :, None]                         # (B, M, 1)
    inc = included[None]                            # (1, M, 2F)
    return ((cl == 1) & (lit == 0) & (inc == 0)).astype(jnp.int32)


def feedback_draws(cfg: TMConfig, key: jax.Array, batch: int) -> tuple:
    """The votes-*independent* half of the PRNG contract.

    Draws every random quantity of one training step at the **global**
    batch shape: ``(offs, u, k1s, k2s)`` where ``offs`` (B,) is the
    negative-class offset (1..C−1), ``u`` (B, 2, M) the feedback
    activation uniforms, and ``k1s``/``k2s`` (B,) are *per-row* threefry
    keys for the target/negative Type I draws — row ``i``'s (M, 2F)
    uniforms come from ``k1s[i]``/``k2s[i]`` and nothing else.

    Per-row keys are what make data-parallel sharding exact: a bulk
    (B, M, 2F) draw from one key has no prefix property (a shard could
    never re-create its slice locally), but a per-row draw is trivially
    sharding-invariant — each shard derives its rows' words from its
    rows' keys, bit-identical to the single-host draw.  The row keys are
    always **threefry** regardless of the step key's impl: they are
    wrapped from a (2, B, 2) uint32 ``bits`` draw on the step chain, so
    an ``rbg`` step chain still yields deterministic, vmap- and
    shard_map-stable row draws (raw ``rbg`` generation is *not* stable
    across sharding, which is why it is never used for the row words).
    """
    k_neg, k_fb, k_i = jax.random.split(key, 3)
    offs = jax.random.randint(k_neg, (batch,), 1, cfg.n_classes)
    u = jax.random.uniform(k_fb, (batch, 2, cfg.n_clauses))
    w = jax.random.bits(k_i, (2, batch, 2), jnp.uint32)
    k1s = jax.random.wrap_key_data(w[0], impl="threefry2x32")
    k2s = jax.random.wrap_key_data(w[1], impl="threefry2x32")
    return offs, u, k1s, k2s


def feedback_thresholds(cfg: TMConfig, votes: jax.Array, y: jax.Array,
                        offs: jax.Array, u: jax.Array) -> tuple:
    """The votes-*dependent* half: threshold the pre-drawn uniforms.

    Row-local (no cross-batch reduction), so it can run per shard on row
    slices of ``offs``/``u`` and still match the single-host masks
    bitwise.  Padding contract: a row with ``u = 2.0`` (> any
    probability, which live in [0, 1]) yields all-False masks and
    therefore zero deltas downstream.
    """
    b = y.shape[0]
    v = jnp.clip(votes, -cfg.T, cfg.T).astype(jnp.float32)
    y_neg = (y + offs) % cfg.n_classes
    p_target = (cfg.T - v[jnp.arange(b), y]) / (2.0 * cfg.T)          # (B,)
    p_neg = (cfg.T + v[jnp.arange(b), y_neg]) / (2.0 * cfg.T)         # (B,)
    fb_t = u[:, 0] < p_target[:, None]                                 # (B, M)
    fb_n = u[:, 1] < p_neg[:, None]                                    # (B, M)
    return y_neg, fb_t, fb_n


def feedback_masks(cfg: TMConfig, key: jax.Array, votes: jax.Array,
                   y: jax.Array) -> tuple:
    """Sample everything downstream of the class sums — the PRNG contract.

    votes: (B, C) int32 class sums; y: (B,) int32 labels →
    ``(y_neg, fb_t, fb_n, k1s, k2s)`` where ``y_neg`` (B,) is the
    sampled negative class (≠ y), ``fb_t``/``fb_n`` (B, M) bool are the
    per-clause feedback activations of the target/negative class, and
    ``k1s``/``k2s`` (B,) are the per-row keys a backend must use for the
    target/negative Type I uniform draws (shape ``(M, 2F)`` per row).

    Every ``TrainEngine`` backend calls this with the same key and
    bit-identical votes, so the sampled decisions — and therefore the
    summed deltas — are bitwise identical across backends.  Composed
    from :func:`feedback_draws` + :func:`feedback_thresholds`; the
    ``sharded`` backend calls the halves separately (draws at global
    shape, thresholds per shard) and stays inside the same contract.
    """
    offs, u, k1s, k2s = feedback_draws(cfg, key, y.shape[0])
    y_neg, fb_t, fb_n = feedback_thresholds(cfg, votes, y, offs, u)
    return y_neg, fb_t, fb_n, k1s, k2s


def feedback_update(cfg: TMConfig, state: TMState, key: jax.Array,
                    x_literals: jax.Array, y: jax.Array,
                    clauses: jax.Array, votes: jax.Array,
                    boost_tpf: bool = True) -> TMState:
    """Shared Type I/II delta math: clause outputs + votes → new state.

    clauses: (B, C, M) {0,1} clause outputs; votes: (B, C) int32 class
    sums — however a backend computed them (dense einsum, SWAR words,
    fused kernel), as long as they are bit-exact the resulting ``TMState``
    is too.  Materializes the per-sample (B, M, 2F) delta tensors; the
    ``fused`` backend replaces exactly this function with a Pallas kernel.
    """
    b = x_literals.shape[0]
    c = cfg.n_classes
    y_neg, fb_t, fb_n, k1s, k2s = feedback_masks(cfg, key, votes, y)

    pol = clause_polarity(cfg.n_clauses)                               # (M,)
    pos = (pol > 0)[None, :]                                           # (1, M)

    cl_t = clauses[jnp.arange(b), y]                                   # (B, M)
    cl_n = clauses[jnp.arange(b), y_neg]                               # (B, M)
    inc_t = (state.ta > cfg.n_states)[y].astype(jnp.int8)              # (B, M, 2F)
    inc_n = (state.ta > cfg.n_states)[y_neg].astype(jnp.int8)

    d1_t = _type_i_delta(k1s, cl_t, x_literals, cfg.s, boost_tpf)      # (B, M, 2F)
    d1_n = _type_i_delta(k2s, cl_n, x_literals, cfg.s, boost_tpf)

    # Type II needs the per-sample include mask of the addressed class.
    d2_t = ((cl_t[:, :, None] == 1) & (x_literals[:, None, :] == 0)
            & (inc_t == 0)).astype(jnp.int32)
    d2_n = ((cl_n[:, :, None] == 1) & (x_literals[:, None, :] == 0)
            & (inc_n == 0)).astype(jnp.int32)

    # target class: Type I on positive clauses, Type II on negative clauses
    delta_t = jnp.where((fb_t & pos)[:, :, None], d1_t, 0) \
        + jnp.where((fb_t & ~pos)[:, :, None], d2_t, 0)
    # negative class: Type II on positive clauses, Type I on negative clauses
    delta_n = jnp.where((fb_n & pos)[:, :, None], d2_n, 0) \
        + jnp.where((fb_n & ~pos)[:, :, None], d1_n, 0)

    # scatter-add per-class sums of deltas over the batch
    onehot_t = jax.nn.one_hot(y, c, dtype=jnp.int32)                   # (B, C)
    onehot_n = jax.nn.one_hot(y_neg, c, dtype=jnp.int32)
    upd = jnp.einsum("bc,bmf->cmf", onehot_t, delta_t) \
        + jnp.einsum("bc,bmf->cmf", onehot_n, delta_n)

    ta = jnp.clip(state.ta + upd, 1, 2 * cfg.n_states)
    return TMState(ta=ta)


@partial(jax.jit, static_argnames=("cfg", "boost_tpf"))
def train_step(cfg: TMConfig, state: TMState, key: jax.Array,
               x_literals: jax.Array, y: jax.Array,
               boost_tpf: bool = True) -> TMState:
    """One batched TM update. x_literals: (B, 2F) {0,1}; y: (B,) int32."""
    clauses = clause_outputs(cfg, state, x_literals)          # (B, C, M)
    votes = class_sums(cfg, clauses)                          # (B, C)
    return feedback_update(cfg, state, key, x_literals, y, clauses, votes,
                           boost_tpf)


@partial(jax.jit, static_argnames=("cfg", "batch_size", "backend"))
def train_epoch(cfg: TMConfig, state: TMState, key: jax.Array,
                x_literals: jax.Array, y: jax.Array,
                batch_size: int = 32, backend: str | None = None) -> TMState:
    """Scan over minibatches (drops the ragged tail).

    ``backend`` selects a :mod:`repro.engine.train` ``TrainEngine`` by
    name (``"reference"``, ``"packed"``, ``"fused"``); ``None`` runs the
    in-module reference step directly.  All backends are delta-exact for
    the same key, so the knob is purely a performance decision.
    """
    n = (x_literals.shape[0] // batch_size) * batch_size
    xb = x_literals[:n].reshape(-1, batch_size, x_literals.shape[-1])
    yb = y[:n].reshape(-1, batch_size)
    keys = jax.random.split(key, xb.shape[0])

    if backend is None:
        step = partial(train_step, cfg)
    else:
        from repro.engine.train import get_train_engine
        step = get_train_engine(backend, cfg).step

    def body(st, inp):
        k, xi, yi = inp
        return step(st, k, xi, yi), None

    state, _ = jax.lax.scan(body, state, (keys, xb, yb))
    return state


def evaluate(cfg: TMConfig, state: TMState, x_literals: jax.Array,
             y: jax.Array) -> float:
    from .tm import predict
    pred = predict(cfg, state, x_literals)
    return float(jnp.mean((pred == y).astype(jnp.float32)))
