"""Pallas TPU kernel: PDL race simulation (delay accumulate + arbiter argmin).

Vectorized simulation of the paper's §III mechanism for large batched
sweeps (Fig. 6 characterization, accuracy-vs-Δ studies): per-class chain
delays are a masked sum over delay elements, then the arbiter tree reduces
to (winner, first-arrival latency, metastability flag) *inside the kernel*,
so per-class delays never leave VMEM — mirroring the race fusing popcount
with comparison.

Tiling: grid ``(B/bb,)``; each step holds a ``(bb, C, M)`` select block
and the full (C, M) delay tables in VMEM (TM scale: C ≤ 128 classes, M ≤ a
few K clauses), computes the (bb, C) delay matrix and reduces it. The
blocks span the whole (C, M) extent, so any C and M compile (no in-kernel
reshape). The arbiter resolution is one scalar in SMEM. Outputs are
(bb, 1)-padded lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["pdl_race_pallas"]


def _pdl_race_kernel(sel_ref, low_ref, high_ref, skew_ref, res_ref,
                     win_ref, lat_ref, meta_ref):
    sel = sel_ref[...].astype(jnp.float32)                  # (bb, C, M)
    low = low_ref[...][None]                                # (1, C, M)
    high = high_ref[...][None]
    per = sel * low + (1.0 - sel) * high
    delays = per.sum(-1) + skew_ref[...]                    # (bb, C)

    lat = jnp.min(delays, axis=-1, keepdims=True)           # (bb, 1)
    win = jnp.argmin(delays, axis=-1, keepdims=True).astype(jnp.int32)
    # metastability: gap between two earliest arrivals below resolution
    masked = jnp.where(delays == lat, jnp.inf, delays)
    second = jnp.min(masked, axis=-1, keepdims=True)
    second = jnp.where(jnp.isinf(second), lat, second)      # duplicate min ⇒ gap 0
    meta = ((second - lat) < res_ref[0, 0]).astype(jnp.int32)

    win_ref[...] = win
    lat_ref[...] = lat
    meta_ref[...] = meta


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def pdl_race_pallas(low_sel: jax.Array, elem_delays: jax.Array,
                    skew: jax.Array, t_res: float, *, block_b: int = 8,
                    interpret: bool = True
                    ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """low_sel (B, C, M) {0,1} int8; elem_delays (C, M, 2) f32; skew (C,) f32
    → (winner (B,) i32, latency (B,) f32, metastable (B,) bool).

    Padded classes get +inf skew (never win); padded batch rows sliced off.
    """
    b, c, m = low_sel.shape
    bp = -(-b // block_b) * block_b
    sel = jnp.pad(low_sel, ((0, bp - b), (0, 0), (0, 0)))
    low = elem_delays[..., 0]
    high = elem_delays[..., 1]
    res = jnp.full((1, 1), t_res, jnp.float32)

    win, lat, meta = pl.pallas_call(
        _pdl_race_kernel,
        grid=(bp // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, c, m), lambda i: (i, 0, 0)),
            pl.BlockSpec((c, m), lambda i: (0, 0)),
            pl.BlockSpec((c, m), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=[
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.int32),
        ],
        interpret=interpret,
    )(sel, low, high, skew.reshape(1, c), res)
    return win[:b, 0], lat[:b, 0], meta[:b, 0].astype(bool)
