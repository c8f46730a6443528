"""The benchmark's own Tsetlin machine: weights and inputs from a seed, and
the plain reference that decides ``correct``.

Nothing here imports the system under test.  The machine is a vanilla TM
(Granmo 2018) as the paper's Table I states it: ``C`` classes, ``M``
clauses per class (even clauses vote +1, odd clauses -1), ``F`` Boolean
features with literals ``[x, not x]``, and Tsetlin-automaton states in
``[1, 2N]`` that include a literal when above ``N``.

Weights.  A random include mask at a trained machine's density fires no
clause on random inputs (a clause with ~78 included literals is true for a
random row with probability 2**-78), so every class sum would be 0 and
every answer the same.  The machine here is shaped as a trained one is:
each class has a binary prototype; a positive clause of class ``c``
includes a random ``2 * density`` share of the features (the same count
in every clause), each with the
polarity of ``c``'s prototype (so it never holds a literal and its
negation); a negative clause does the same against another class's
prototype.  Inputs are prototypes with each bit flipped with probability
``noise``, labelled with their class.  Included states are uniform in
``[N+1, 2N]``, excluded ones in ``[1, N]``.

Reference.  :func:`infer` is the clause conjunction as a violation count
(an int8 product with int32 accumulation, exact), the signed vote sum and
an argmax whose ties go to the lowest class.  :func:`train_step` is one
batched vanilla-TM update under the documented key contract of the
served trainer: the step key splits into (negative-class offsets,
feedback uniforms, per-row threefry keys); each row's Type I uniforms come
from its own key; per-sample deltas are summed per class and the state
clipped to ``[1, 2N]``.  ``vote_bits`` and ``draw_dtype`` are the lower
precisions of the control: votes counted in a narrower wrapping integer,
and feedback uniforms compared in a narrower float.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def shapes(cfg: dict) -> tuple[int, int, int, int]:
    """(C, M, F, N) of a configuration file's dict."""
    return (int(cfg["n_classes"]), int(cfg["n_clauses"]),
            int(cfg["n_features"]), int(cfg["n_states"]))


@functools.partial(jax.jit, static_argnames=("c", "m", "f", "n",
                                             "density"))
def _machine(key, *, c, m, f, n, density):
    k_proto, k_feat, k_in, k_out = jax.random.split(key, 4)
    proto = jax.random.bernoulli(k_proto, 0.5, (c, f))
    # every clause includes the same number of features, so the padded
    # clause width (and with it the served programs' shapes) is the same
    # for every seed
    k = max(1, round(2.0 * density * f))
    rank = jnp.argsort(jnp.argsort(jax.random.uniform(k_feat, (c, m, f)),
                                   axis=-1), axis=-1)
    feat = rank < k
    clause = jnp.arange(m)
    other = (jnp.arange(c)[:, None] + 1 + (clause[None, :] // 2)
             % max(c - 1, 1)) % c
    target = jnp.where(clause[None, :] % 2 == 0, jnp.arange(c)[:, None],
                       other)                                   # (C, M)
    bits = proto[target]                                        # (C, M, F)
    inc = jnp.concatenate([feat & bits, feat & ~bits], axis=-1)
    hi = jax.random.randint(k_in, inc.shape, n + 1, 2 * n + 1, jnp.int32)
    lo = jax.random.randint(k_out, inc.shape, 1, n + 1, jnp.int32)
    return jnp.where(inc, hi, lo), proto


def make_machine(cfg: dict, seed: int) -> tuple[jax.Array, jax.Array]:
    """(ta (C, M, 2F) int32 on the device, prototypes (C, F) bool), made in
    one jitted call from ``seed``."""
    c, m, f, n = shapes(cfg)
    return _machine(jax.random.key(seed), c=c, m=m, f=f, n=n,
                    density=float(cfg["include_density"]))


@functools.partial(jax.jit, static_argnames=("rows", "noise"))
def _pool(key, proto, *, rows, noise):
    k_y, k_flip = jax.random.split(key)
    y = jax.random.randint(k_y, (rows,), 0, proto.shape[0], jnp.int32)
    x = proto[y] ^ jax.random.bernoulli(k_flip, noise,
                                        (rows, proto.shape[1]))
    lits = jnp.concatenate([x, ~x], axis=-1).astype(jnp.int8)
    return lits, y


def make_pool(proto: jax.Array, seed: int, rows: int,
              noise: float) -> tuple[np.ndarray, np.ndarray]:
    """(literals (rows, 2F) int8, labels (rows,) int32) on the host: noisy
    prototypes, made on the device in one call."""
    lits, y = _pool(jax.random.key(seed), proto, rows=rows,
                    noise=float(noise))
    return np.asarray(lits), np.asarray(y)


def _wrap(x, bits: int):
    half = 1 << (bits - 1)
    return (x + half) % (2 * half) - half


@functools.partial(jax.jit, static_argnames=("n_states", "vote_bits"))
def _infer(ta, lits, *, n_states, vote_bits):
    c, m, l2 = ta.shape
    inc = (ta > n_states).astype(jnp.int8).reshape(c * m, l2)
    viol = jax.lax.dot_general(
        (1 - lits).astype(jnp.int8), inc, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)                       # (B, C*M)
    fire = (viol == 0).astype(jnp.int32).reshape(-1, c, m)
    sign = jnp.where(jnp.arange(m) % 2 == 0, 1, -1).astype(jnp.int32)
    if vote_bits:
        # the control: each vote counted into a vote_bits-wide register
        # that wraps, as a narrower integer type would
        sums = jnp.zeros(fire.shape[:2], jnp.int32)
        for j in range(m):
            sums = _wrap(sums + fire[:, :, j] * sign[j], vote_bits)
    else:
        sums = (fire * sign).sum(-1)
    return jnp.argmax(sums, axis=-1).astype(jnp.int32), sums


def infer(ta, lits, *, n_states: int, vote_bits: int = 0,
          block: int = 4096) -> tuple[np.ndarray, np.ndarray]:
    """Reference (prediction (B,), class sums (B, C)) on the host, computed
    in fixed blocks of rows so that one compiled program serves any B."""
    lits = np.asarray(lits, np.int8)
    preds, sums = [], []
    for i in range(0, len(lits), block):
        part = lits[i:i + block]
        pad = np.zeros((block, lits.shape[1]), np.int8)
        pad[:len(part)] = part
        p, s = _infer(ta, pad, n_states=n_states, vote_bits=vote_bits)
        preds.append(np.asarray(p)[:len(part)])
        sums.append(np.asarray(s)[:len(part)])
    if not preds:
        c = ta.shape[0]
        return np.zeros((0,), np.int32), np.zeros((0, c), np.int32)
    return np.concatenate(preds), np.concatenate(sums)


@functools.partial(jax.jit, static_argnames=("n_states", "T", "s",
                                             "draw_dtype"))
def _train_step(ta, key, x, y, *, n_states, T, s, draw_dtype):
    c, m, l2 = ta.shape
    b = x.shape[0]
    rows = jnp.arange(b)
    inc = ta > n_states                                         # (C, M, L)
    viol = jax.lax.dot_general(
        (1 - x).astype(jnp.int8), inc.astype(jnp.int8).reshape(c * m, l2),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)
    clause = (viol == 0).reshape(b, c, m)                       # (B, C, M)
    pos = jnp.arange(m) % 2 == 0
    votes = jnp.where(pos, clause, -clause.astype(jnp.int32)).sum(-1)

    k_neg, k_fb, k_rows = jax.random.split(key, 3)
    offs = jax.random.randint(k_neg, (b,), 1, c)
    u = jax.random.uniform(k_fb, (b, 2, m))
    words = jax.random.bits(k_rows, (2, b, 2), jnp.uint32)
    k1 = jax.random.wrap_key_data(words[0], impl="threefry2x32")
    k2 = jax.random.wrap_key_data(words[1], impl="threefry2x32")

    v = jnp.clip(votes, -T, T).astype(jnp.float32)
    y_neg = (y + offs) % c
    p_t = (T - v[rows, y]) / (2.0 * T)
    p_n = (T + v[rows, y_neg]) / (2.0 * T)
    lo = functools.partial(jnp.asarray, dtype=draw_dtype)
    fb_t = lo(u[:, 0]) < lo(p_t)[:, None]                       # (B, M)
    fb_n = lo(u[:, 1]) < lo(p_n)[:, None]

    lit = x[:, None, :] == 1                                    # (B, 1, L)

    def type_i(keys, cl):
        # boosted true-positive feedback: a firing clause always
        # reinforces its true literals; every other draw is w.p. 1/s
        draws = jax.vmap(lambda k: jax.random.uniform(k, (m, l2)))(keys)
        hit = lo(draws) < lo(1.0 / s)
        fired = cl[:, :, None]
        up = fired & lit
        down = (fired & ~lit & hit) | (~fired & hit)
        return up.astype(jnp.int32) - down.astype(jnp.int32)

    def type_ii(cl, cls):
        return (cl[:, :, None] & ~lit & ~inc[cls]).astype(jnp.int32)

    cl_t, cl_n = clause[rows, y], clause[rows, y_neg]
    d_t = jnp.where((fb_t & pos)[:, :, None], type_i(k1, cl_t), 0) \
        + jnp.where((fb_t & ~pos)[:, :, None], type_ii(cl_t, y), 0)
    d_n = jnp.where((fb_n & pos)[:, :, None], type_ii(cl_n, y_neg), 0) \
        + jnp.where((fb_n & ~pos)[:, :, None], type_i(k2, cl_n), 0)
    upd = jnp.zeros_like(ta).at[y].add(d_t).at[y_neg].add(d_n)
    return jnp.clip(ta + upd, 1, 2 * n_states)


def replay(cfg: dict, ta, train_seed: int, batches, *,
           draw_dtype=jnp.float32):
    """Yield the reference states v1..vN of a server whose update key chain
    starts at ``jax.random.key(train_seed)``: update ``i`` uses
    ``split(chain)[1]`` and advances the chain to ``split(chain)[0]``."""
    _, _, _, n = shapes(cfg)
    chain = jax.random.key(train_seed)
    for lits, labels in batches:
        chain, k = jax.random.split(chain)
        ta = _train_step(ta, k, jnp.asarray(lits), jnp.asarray(labels),
                         n_states=n, T=int(cfg["T"]), s=float(cfg["s"]),
                         draw_dtype=draw_dtype)
        yield ta
