"""Compile the main path's kernels and steps for a TPU v5e, without one.

The TPU compiler is installed even where no chip is attached: it
compiles for a described ``v5e:2x2`` topology, and refuses what the chip
would refuse (a Mosaic lowering with no TPU rule, an unaligned block, a
program that does not fit).  Interpret-mode tests cannot see any of that.
Every compile here is at the width of ``tm-mnist-100`` (C=10, M=100,
F=784, so L=1568 literals), the serving buckets 1 and 64, and the label
batch 32; the inference routes also at the width of ``tm-imdb-10k``
(C=2, M=10,000, F=5,000, so L=10,000).  Nothing runs: these tests prove
compilation only.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every xdist
worker imports every test file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.tm import TMConfig, TMState

C, M, F = 10, 100, 784                       # configs/tm_paper.py
L = 2 * F
WL = -(-L // 32)                             # literal words
WM = -(-M // 32)                             # clause-polarity words
# bench/configs/tm-imdb-10k.json: 500 included literals a clause, so the
# server's ELL width is 500 + the 8-slot slack = 512
TEXT_C, TEXT_M, TEXT_L, TEXT_K = 2, 10000, 10000, 512
LABEL_BATCH = 32
CFG = TMConfig(n_classes=C, n_clauses=M, n_features=F, T=5, s=10.0)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            yield topologies.get_topology_desc(platform="tpu",
                                               topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()
    return compiled.as_text()


@pytest.mark.parametrize("bucket", [1, 64])
@pytest.mark.parametrize("backend", ["swar_fused", "mxu_fused"])
def test_vote_kernel_compiles(one_chip, backend, bucket):
    from repro.engine import backends
    s = lambda shape, dt: _shape(one_chip, shape, dt)     # noqa: E731
    vm = s((C * M, C), jnp.int8)
    if backend == "swar_fused":
        fn = backends._swar_fused_infer
        args = (s((C * M, WL), jnp.uint32), vm, s((bucket, L), jnp.int8))
        tiles = dict(block_b=8, block_cm=128)
    else:
        fn = backends._mxu_infer
        args = (s((C * M, L), jnp.int8), vm, s((bucket, L), jnp.int8))
        tiles = dict(block_b=128, block_cm=128)
    hlo = fn.lower(*args, interpret=False, **tiles).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_sparse_csr_serve_route_compiles(one_chip):
    """The route the server takes at a trained machine's ~5% density: the
    packed program it serves by, and the plain ``infer``."""
    from repro.engine import backends
    args = (_shape(one_chip, (C * M, 128), jnp.int32),
            _shape(one_chip, (M,), jnp.int32),
            _shape(one_chip, (64, L), jnp.int8))
    assert backends._sparse_csr_infer.lower(
        *args, c=C, m=M).compile().as_text()
    packed = backends._sparse_csr_infer_packed.lower(
        *args, c=C, m=M).compile()
    assert packed.out_info.shape == (64, 1 + C)
    assert packed.out_info.dtype == jnp.int32


@pytest.mark.parametrize("backend", ["swar_fused", "mxu_fused"])
def test_vote_kernel_compiles_at_text_width(one_chip, backend):
    """The kernel routes at ``tm-imdb-10k`` width, bucket 64, with the
    default tiles: each block fits the chip's scoped VMEM."""
    from repro.engine import backends
    s = lambda shape, dt: _shape(one_chip, shape, dt)     # noqa: E731
    cm = TEXT_C * TEXT_M
    vm = s((cm, TEXT_C), jnp.int8)
    lits = s((64, TEXT_L), jnp.int8)
    if backend == "swar_fused":
        fn = backends._swar_fused_infer_packed
        args = (s((cm, -(-TEXT_L // 32)), jnp.uint32), vm, lits)
        tiles = dict(block_b=8, block_cm=128)
    else:
        fn = backends._mxu_infer_packed
        args = (s((cm, TEXT_L), jnp.int8), vm, lits)
        tiles = dict(block_b=128, block_cm=128)
    compiled = fn.lower(*args, interpret=False, **tiles).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.out_info.shape == (64, 1 + TEXT_C)


@pytest.mark.parametrize("bucket", [1, 64])
def test_sparse_csr_serve_route_compiles_at_text_width(one_chip, bucket):
    """The density route the server takes at ``tm-imdb-10k``: the packed
    program over 20,000 clause rows of 512 ELL slots fits one chip."""
    from repro.engine import backends
    packed = backends._sparse_csr_infer_packed.lower(
        _shape(one_chip, (TEXT_C * TEXT_M, TEXT_K), jnp.int32),
        _shape(one_chip, (TEXT_M,), jnp.int32),
        _shape(one_chip, (bucket, TEXT_L), jnp.int8),
        c=TEXT_C, m=TEXT_M).compile()
    assert packed.out_info.shape == (bucket, 1 + TEXT_C)
    assert packed.memory_analysis().temp_size_in_bytes < 16 * 2**30


def test_popcount_kernel_compiles(one_chip):
    from repro.kernels.popcount import popcount_words_pallas
    hlo = _compile(lambda w: popcount_words_pallas(w, interpret=False),
                   _shape(one_chip, (64 * C, WL), jnp.uint32))
    assert "tpu_custom_call" in hlo


def test_binary_matmul_kernel_compiles(one_chip):
    """bnn-mnist's first layer: 784 → 256 at batch 64."""
    from repro.kernels.binary_matmul import binary_matmul_pallas
    hlo = _compile(lambda x, w: binary_matmul_pallas(x, w, interpret=False),
                   _shape(one_chip, (64, F), jnp.int8),
                   _shape(one_chip, (F, 256), jnp.int8))
    assert "tpu_custom_call" in hlo


def test_pdl_race_kernel_compiles(one_chip):
    from repro.kernels.pdl_race import pdl_race_pallas
    hlo = _compile(
        lambda s, e, k: pdl_race_pallas(s, e, k, 10.0, interpret=False),
        _shape(one_chip, (64, C, M), jnp.int8),
        _shape(one_chip, (C, M, 2), jnp.float32),
        _shape(one_chip, (C,), jnp.float32))
    assert "tpu_custom_call" in hlo


def _step_args(sharding):
    """(state, key, literals, labels, pos_mask, neg_mask) shapes."""
    key = jax.eval_shape(lambda: jax.random.key(0))
    return (TMState(ta=_shape(sharding, (C, M, L), jnp.int32)),
            _shape(sharding, key.shape, key.dtype),
            _shape(sharding, (LABEL_BATCH, L), jnp.int8),
            _shape(sharding, (LABEL_BATCH,), jnp.int32),
            _shape(sharding, (WM,), jnp.uint32),
            _shape(sharding, (WM,), jnp.uint32))


def test_fused_train_step_compiles(one_chip):
    """The serve-while-learn update: ``swar_fused`` votes (a TPU kernel)
    feeding the one-XLA-body delta update."""
    from repro.engine.train import _fused_step
    state, key, x, y, pos, neg = _step_args(one_chip)
    compiled = _fused_step.lower(
        CFG, state, key, x, y, _shape(one_chip, (C * M, C), jnp.int8),
        pos, neg, boost_tpf=True, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**30


def test_sharded_train_step_compiles_on_four_chips(topo):
    """The ``sharded`` trainer over the 2x2 host's four chips: the
    gather of the packed include words and the cross-chip sum of the
    class-segmented partials must be there (the TPU compiler may lower
    the reduce-scatter as an all-reduce)."""
    from jax.sharding import Mesh
    from repro.engine.train import _sharded_step
    mesh = Mesh(np.array(topo.devices), ("data",))
    rep = NamedSharding(mesh, P())
    hlo = _sharded_step.lower(CFG, *_step_args(rep), mesh=mesh,
                              boost_tpf=True).compile().as_text()
    assert "all-gather" in hlo
    assert "reduce-scatter" in hlo or "all-reduce" in hlo


@pytest.mark.parametrize("backend", ["sparse_csr", "swar_fused"])
def test_sharded_serve_compiles_on_four_chips(topo, backend):
    """A batch ``shard_map`` of the inner backends over the 2x2 host at
    bucket 64, written out here as ``ShardedEngine`` builds it (the engine
    itself closes over concrete tables, which a described topology cannot
    hold): the density route (``sparse_csr``) and the kernel route, each
    shard running the inner backend on its 16 rows."""
    from jax.sharding import Mesh
    from repro.engine import backends
    mesh = Mesh(np.array(topo.devices), ("batch",))
    rep = NamedSharding(mesh, P())
    if backend == "sparse_csr":
        inner = lambda lits, idx, pol: backends._sparse_csr_infer(  # noqa: E731
            idx, pol, lits, c=C, m=M)
        tables = (_shape(rep, (C * M, 128), jnp.int32),
                  _shape(rep, (M,), jnp.int32))
    else:
        inner = lambda lits, w, vm: backends._swar_fused_infer(  # noqa: E731
            w, vm, lits, block_b=8, block_cm=128, interpret=False)
        tables = (_shape(rep, (C * M, WL), jnp.uint32),
                  _shape(rep, (C * M, C), jnp.int8))
    sharded = jax.shard_map(inner, mesh=mesh,
                            in_specs=(P("batch"), P(), P()),
                            out_specs=P("batch"), check_vma=False)
    lits = _shape(NamedSharding(mesh, P("batch")), (64, L), jnp.int8)
    hlo = jax.jit(sharded).lower(lits, *tables).compile().as_text()
    assert ("tpu_custom_call" in hlo) == (backend == "swar_fused")


def test_sparse_train_step_compiles(one_chip):
    """The ``sparse`` trainer: ELL-gathered class sums
    (``kernels/ell_gather``) feeding the same delta body, at K=128 slots
    per clause row (a trained machine's ~5% of L=1568, with slack)."""
    from repro.engine.train import _sparse_step
    state, key, x, y, _, _ = _step_args(one_chip)
    hlo = _sparse_step.lower(CFG, state, key, x, y,
                             _shape(one_chip, (C * M, 128), jnp.int32),
                             boost_tpf=True).compile().as_text()
    assert hlo
