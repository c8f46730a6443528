"""Batch-axis sharding for any VoteEngine: multi-device serving.

``ShardedEngine`` wraps an engine's ``infer`` in a jitted ``shard_map``
over a 1-D ``("batch",)`` mesh of all local devices: each device runs the
inner backend on its batch shard, and results concatenate back on the
batch axis; each batch shape compiles once.  Works for every backend
because ``EngineResult`` leaves (prediction, class_sums, aux arrays) are
all batch-leading by contract.

Ragged batches pad to a device multiple with all-zero literal rows (a
valid input — clauses evaluate normally) and slice back after the map,
so callers never see the padding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from .base import EngineResult, VoteEngine

__all__ = ["ShardedEngine"]


class ShardedEngine:
    """Serve ``inner.infer`` data-parallel over the batch axis.

    ``mesh=`` serves over an existing 1-D mesh (e.g.
    :func:`repro.distributed.sharding.data_mesh` — the one a
    mesh-configured ``TMServer`` routes its stage-B buckets through);
    ``devices=`` builds a private ``("batch",)`` mesh over those devices;
    neither takes every local device.
    """

    def __init__(self, inner: VoteEngine, devices=None, *, mesh=None):
        if getattr(inner, "noise_key", None) is not None:
            # every shard would draw the same jitter from the closed-over
            # key, silently diverging from the unsharded engine
            raise ValueError(
                "shard_batch with a noise_key would replicate the same "
                "per-event jitter on every device shard; run unsharded or "
                "drop the noise_key")
        self.inner = inner
        self.cfg = inner.cfg
        self.name = f"{inner.name}+shard_batch"
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    f"ShardedEngine needs a 1-D mesh, got {mesh.axis_names}")
            self.mesh = mesh
        else:
            devs = list(devices) if devices is not None else jax.devices()
            self.mesh = Mesh(np.array(devs), ("batch",))
        axis = self.mesh.axis_names[0]
        self.n_devices = self.mesh.shape[axis]
        self._sharded = jax.jit(jax.shard_map(
            inner.infer, mesh=self.mesh,
            in_specs=P(axis), out_specs=P(axis), check_vma=False))

    def infer(self, literals: jax.Array) -> EngineResult:
        """(B, 2F) literals → the inner engine's result, batch-sharded
        across local devices (ragged batches pad + slice transparently)."""
        b = literals.shape[0]
        bp = -(-b // self.n_devices) * self.n_devices
        if bp != b:
            literals = jnp.pad(literals, ((0, bp - b), (0, 0)))
        res = self._sharded(literals)
        if bp != b:
            res = jax.tree_util.tree_map(lambda x: x[:b], res)
        return res
