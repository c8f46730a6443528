"""Sharding machinery + a miniature dry-run in a subprocess.

The 512-device flag must not leak into this test process (smoke tests see
1 device — brief §MULTI-POD item 0), so the mini dry-run runs via
``subprocess`` with its own XLA_FLAGS, on a (2, 2) host mesh with reduced
configs — validating exactly the code path the full matrix uses.
"""

import json
import os
import subprocess
import sys

import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config
from repro.configs.reduce import reduced
from repro.distributed.sharding import make_rules
from repro.models.model import LM

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_param_specs_use_rules():
    cfg = get_config("qwen1.5-110b")
    lm = LM(cfg, tp=16)   # no mesh: rules resolve to None mesh axes
    specs = lm.param_specs()
    assert specs["embed"] == P(None, None)
    lm16 = LM(reduced(cfg), tp=1)
    # stacked layer param: (layers, embed, heads, head_dim)
    assert lm16.param_specs()["layers"]["attn"]["wq"] == \
        P(None, None, None, None)


def test_rules_overrides_applied():
    cfg = get_config("mamba2-130m")
    rules = make_rules(None, cfg.rules_overrides)
    assert rules["ssm_inner"] is None
    assert rules["mlp"] is None


def test_head_padding_math():
    from repro.models.attention import AttnCfg
    # llama4: 40 q / 8 kv on tp=16 → hq 48, kv replicated, group 5→6
    c = AttnCfg(5120, 40, 8, 128, tp=16)
    assert (c.hq, c.hkv, c.rep, c.g) == (48, 8, 6, 5)
    # qwen4b: 20/20 → both padded to 32
    c = AttnCfg(2560, 20, 20, 128, tp=16)
    assert (c.hq, c.hkv, c.rep) == (32, 32, 1)
    # starcoder2: 36 q / 4 kv → 48, kv replicated
    c = AttnCfg(4608, 36, 4, 128, tp=16)
    assert (c.hq, c.hkv, c.rep, c.g) == (48, 4, 12, 9)
    # no padding when tp=1
    c = AttnCfg(2048, 32, 4, 64, tp=1)
    assert (c.hq, c.hkv) == (32, 4)


def test_head_padding_exactness():
    """Padded-head model output == unpadded model output (zero-masked)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.attention import AttnCfg, attn_apply, attn_defs
    from repro.models.common import init_params
    rng = np.random.default_rng(0)
    cfg1 = AttnCfg(64, 10, 2, 16, tp=1)    # true: 10 q heads, 2 kv
    cfg8 = AttnCfg(64, 10, 2, 16, tp=8)    # padded: hq 16, rep 8 (g=5)
    assert cfg8.hq == 16 and cfg8.rep == 8
    p1 = init_params(attn_defs(cfg1), jax.random.key(0))
    p8 = init_params(attn_defs(cfg8), jax.random.key(1))
    # copy true-head weights into the padded layout (kv-major, group-minor)
    for kv in range(2):
        for g in range(5):
            src = kv * 5 + g
            dst = kv * 8 + g
            p8["wq"] = p8["wq"].at[:, dst].set(p1["wq"][:, src])
            p8["wo"] = p8["wo"].at[dst].set(p1["wo"][src])
    p8["wk"], p8["wv"] = p1["wk"], p1["wv"]
    x = jnp.asarray(rng.normal(0, 1, (2, 12, 64)).astype(np.float32))
    y1, _ = attn_apply(cfg1, p1, x)
    y8, _ = attn_apply(cfg8, p8, x)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y8),
                               rtol=2e-2, atol=2e-3)


MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, dataclasses
import jax
from repro.configs import get_config, SHAPES
from repro.configs.reduce import reduced
from repro.models.model import LM
from repro.launch.dryrun import _lower
from repro.roofline.analysis import collective_bytes

mesh = jax.make_mesh((2, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
out = {}
for arch in ["tinyllama-1.1b", "llama4-scout-17b-a16e", "mamba2-130m"]:
    cfg = dataclasses.replace(reduced(get_config(arch)), name=arch)
    for shape_name in ["train_4k", "decode_32k"]:
        shape = dataclasses.replace(SHAPES[shape_name], seq_len=64,
                                    global_batch=8)
        lm = LM(cfg, tp=2, mesh=mesh, remat=shape.kind == "train")
        co = _lower(lm, shape, mesh).compile()
        ma = co.memory_analysis()
        cb = collective_bytes(co.as_text())
        out[f"{arch}|{shape_name}"] = {
            "temp": ma.temp_size_in_bytes,
            "collectives": sum(cb.values()), "kinds": sorted(cb)}
print(json.dumps(out))
"""


@pytest.mark.slow
def test_mini_dryrun_subprocess():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", MINI_DRYRUN], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(out) == 6
    for key, cell in out.items():
        assert cell["temp"] > 0, key
        # sharded steps must communicate (FSDP gathers / TP reductions)
        assert cell["collectives"] > 0, key


# -- TM batch-dim sharding (data mesh + ShardedEngine) -----------------
#
# The serving half of the multi-host layer (docs/operations.md
# "Multi-host serving"): stage-B buckets route through a ShardedEngine
# over the same 1-D ``data`` mesh the sharded trainer uses, and the
# sharded plane must be bit-exact with the unsharded engine — the mesh
# is a throughput knob, never a numerics knob.


def test_batch_axes_refuses_non_divisible():
    """A global batch that doesn't divide the dp extent must resolve to
    replicated (None) — never silently truncate or mis-shard."""
    from repro.distributed.sharding import batch_axes, data_mesh
    mesh = data_mesh(4)
    rules = {"batch": "data"}
    assert batch_axes(rules, 8, mesh) == "data"
    assert batch_axes(rules, 12, mesh) == "data"
    for bad in (1, 2, 3, 6, 9, 13):
        assert batch_axes(rules, bad, mesh) is None
    assert batch_axes(rules, 8, None) is None          # no mesh → no dp
    assert batch_axes({}, 8, mesh) is None             # no batch rule


def _random_tm(c, m, f, *, density=0.15, seed=0):
    import jax.numpy as jnp
    import numpy as np
    from repro.core.tm import TMConfig, TMState
    cfg = TMConfig(n_classes=c, n_clauses=m, n_features=f)
    rng = np.random.default_rng(seed)
    ta = np.where(rng.random((c, m, 2 * f)) < density,
                  cfg.n_states + 1, cfg.n_states)
    return cfg, TMState(ta=jnp.asarray(ta, jnp.int32))


def _all_inference_backends():
    from repro.engine import available_backends
    return available_backends()


@pytest.mark.parametrize("batch", [16, 13],
                         ids=["divisible", "ragged-pads"])
@pytest.mark.parametrize("backend", _all_inference_backends())
def test_sharded_engine_bit_exact_all_backends(backend, batch):
    """ShardedEngine.infer == unsharded infer, bitwise, for every
    registered backend — including ragged batches whose zero-padded
    rows must be sliced off, not served."""
    import jax.numpy as jnp
    import numpy as np
    from repro.engine import get_engine
    cfg, st = _random_tm(4, 10, 12, seed=7)
    lits = jnp.asarray(np.random.default_rng(8).integers(
        0, 2, (batch, cfg.n_literals), dtype=np.int8))
    ref = get_engine(backend, cfg, st).infer(lits)
    sharded = get_engine(backend, cfg, st, shard_batch=True)
    assert sharded.n_devices > 1, "conftest must simulate 8 devices"
    res = sharded.infer(lits)
    assert res.prediction.shape[0] == batch
    np.testing.assert_array_equal(np.asarray(res.prediction),
                                  np.asarray(ref.prediction))
    np.testing.assert_array_equal(np.asarray(res.class_sums),
                                  np.asarray(ref.class_sums))


@pytest.mark.parametrize("backend", ["oracle", "sparse_csr"])
def test_sharded_engine_compiles_once_per_shape(backend):
    """Serving the same bucket again reuses the compiled program: every
    mesh-served batch used to re-lower and recompile its ``shard_map``."""
    import jax
    import jax.numpy as jnp
    from jax._src.dispatch import BACKEND_COMPILE_EVENT
    from repro.engine import get_engine
    cfg, st = _random_tm(4, 10, 12, seed=9)
    sharded = get_engine(backend, cfg, st, shard_batch=True, cache=False)
    lits = jnp.ones((16, cfg.n_literals), jnp.int8)
    jax.block_until_ready(sharded.infer(lits))
    compiles = []

    def listener(event, duration_secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            compiles.append(duration_secs)

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        for _ in range(3):
            jax.block_until_ready(sharded.infer(lits))
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)
    assert compiles == []
