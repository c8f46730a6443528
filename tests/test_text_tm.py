"""A text-shaped Tsetlin machine on the served path: two classes over many
features at a trained machine's 5% include density, the shape of the
``tm-imdb-10k`` benchmark configuration (C=2, M=10,000, F=5,000) cut to
a CPU test's size (C=2, M=64, F=500).

The machine and its inputs are the benchmark's own
(``bench.reference.make_machine`` / ``make_pool``): class prototypes with
50 included features a clause, and inputs flipped at 2% so that a clause
of the input's class fires on about a third of the rows.  ``TMServer``
must answer exactly as the benchmark's reference and the ``oracle``
engine do, class and both sums.
"""

import asyncio
import json
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:           # the benchmark's package
    sys.path.insert(0, str(ROOT))

from bench import harness, reference, work                 # noqa: E402
from repro.core.tm import TMState                          # noqa: E402
from repro.engine import get_engine                        # noqa: E402
from repro.serve import ServePolicy, TMServer              # noqa: E402

SMALL = {"n_classes": 2, "n_clauses": 64, "n_features": 500,
         "n_states": 128, "T": 80, "s": 27.0, "include_density": 0.05}
NOISE = 0.02
SIZES = (64, 1, 7, 64, 33, 64, 2, 17)


def _machine(seed):
    seeds = harness.seeds(seed)
    ta, proto = reference.make_machine(SMALL, seeds["machine"])
    pool, _ = reference.make_pool(proto, seeds["pool"], 256, NOISE)
    return ta, pool


def _serve(ta, batches):
    cfg = harness.tm_config(SMALL)

    async def go():
        async with TMServer(cfg, TMState(ta=ta),
                            ServePolicy(max_batch=64, max_wait_us=500)
                            ) as srv:
            results = await asyncio.gather(*(srv.submit(b)
                                             for b in batches))
            return results, srv.stats()
    return asyncio.run(go())


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_server_answers_as_the_reference_and_the_oracle(seed):
    ta, pool = _machine(seed)
    starts = np.cumsum((0,) + SIZES[:-1])
    batches = [pool[s:s + n] for s, n in zip(starts, SIZES)]
    results, stats = _serve(ta, batches)
    assert set(stats["routing"].values()) == {"sparse_csr"}
    oracle = get_engine("oracle", harness.tm_config(SMALL), TMState(ta=ta))
    for lits, got in zip(batches, results):
        pred, sums = reference.infer(ta, lits, n_states=SMALL["n_states"])
        np.testing.assert_array_equal(np.asarray(got.prediction), pred)
        np.testing.assert_array_equal(np.asarray(got.class_sums), sums)
        want = oracle.infer(jnp.asarray(lits))
        np.testing.assert_array_equal(np.asarray(got.prediction),
                                      np.asarray(want.prediction))
        np.testing.assert_array_equal(np.asarray(got.class_sums),
                                      np.asarray(want.class_sums))


def test_the_cells_policy_serves_as_the_reference():
    """The cell's own serve policy (every bucket on ``swar_fused``) at the
    small size: answers equal the reference's, no ELL is built."""
    _, cfg, _ = harness.resolve(harness.load_spec(), "imdb10k-bulk")
    ta, pool = _machine(2**31 + 17)
    starts = np.cumsum((0,) + SIZES[:-1])
    batches = [pool[s:s + n] for s, n in zip(starts, SIZES)]

    async def go():
        async with TMServer(harness.tm_config(SMALL), TMState(ta=ta),
                            ServePolicy(**cfg["serve_policy"])) as srv:
            results = await asyncio.gather(*(srv.submit(b)
                                             for b in batches))
            return results, srv.stats()
    results, stats = asyncio.run(go())
    assert set(stats["routing"].values()) == {"swar_fused"}
    assert stats["sparse_layout"] is None
    for lits, got in zip(batches, results):
        pred, sums = reference.infer(ta, lits, n_states=SMALL["n_states"])
        np.testing.assert_array_equal(np.asarray(got.prediction), pred)
        np.testing.assert_array_equal(np.asarray(got.class_sums), sums)


@pytest.mark.parametrize("backend", ["sparse_csr", "swar_fused",
                                     "mxu_fused"])
def test_engine_width_times_the_served_engine(backend):
    """``benchmarks/engine_width.py`` times the engine a server pinned to
    the backend serves the bucket with, and it is exact."""
    from benchmarks import engine_width
    ta, pool = _machine(9)
    cfg, state = harness.tm_config(SMALL), TMState(ta=ta)
    engine = engine_width.build(backend, cfg, state, 64)
    assert type(engine).__name__ == type(get_engine(
        backend, cfg, state)).__name__
    oracle = get_engine("oracle", cfg, state)
    assert engine_width.exact(engine, oracle, ta, [pool[:64], pool[64:96]],
                              SMALL["n_states"], 64)


def test_the_machine_is_not_degenerate():
    """Both classes are predicted and clauses fire on 10-40% of the
    (row, clause) pairs: a random mask at this density would fire none
    and answer class 0 every time."""
    ta, pool = _machine(5)
    pred, sums = reference.infer(ta, pool, n_states=SMALL["n_states"])
    assert set(np.unique(pred)) == {0, 1}
    inc = np.asarray(ta) > SMALL["n_states"]              # (C, M, 2F)
    viol = (1 - pool.astype(np.int32)) @ inc.reshape(
        -1, inc.shape[-1]).T.astype(np.int32)
    fire = float(np.mean(viol == 0))
    assert 0.10 <= fire <= 0.40, fire
    assert np.all(inc.sum(-1) == round(2 * 0.05 * SMALL["n_features"]))


def test_the_cell_resolves_at_the_published_widths():
    spec = harness.load_spec()
    cell, cfg, traffic = harness.resolve(spec, "imdb10k-bulk")
    assert cell["config"] == "tm-imdb-10k" and cell["chips"] == 1
    tm = harness.tm_config(cfg)
    assert (tm.n_classes, tm.n_clauses, tm.n_features) == (2, 10000, 5000)
    assert tm.n_literals == 10000 and tm.T == 8000 and tm.s == 27.0
    assert cfg["reduced"] == [] and "unweighted" in cfg["assumed"]
    assert traffic["noise"] == 0.003
    assert traffic["predict"]["rows"] == {"min": 64, "max": 64}
    # the cell reports the bulk metric and its own per-layer metrics
    e2e = {m["name"] for m in harness.metrics_for(spec, "imdb10k-bulk",
                                                   traced=False)}
    assert e2e == {"served_rows_per_s", "setup_s"}
    layer = {m["name"] for m in harness.metrics_for(spec, "imdb10k-bulk",
                                                     traced=True)}
    assert layer == {"stageB_ms.bulk", "infer_roofline.bulk",
                     "device_idle.bulk", "mfu.bulk", "engine_build_s.imdb"}
    assert cfg["serve_policy"]["backend"] == "swar_fused"
    for name in layer:
        assert callable(harness.reader(name))


def test_least_time_at_the_published_widths():
    """nnz 2 * 10,000 * 500 = 10 M: per 64-row batch 1.28 G operations
    (3.3 us at 393 TOP/s) and 80,000 + 20,000,000 + 512 bytes (24.5 us at
    819 GB/s), so the bytes bind."""
    cfg = json.loads((ROOT / "bench" / "configs"
                      / "tm-imdb-10k.json").read_text())
    nnz = 2 * 10000 * 500
    assert work.ops_per_row(cfg, nnz) == 2 * nnz + 20000
    assert work.batch_bytes(cfg, nnz, 64) == 80_000 + 20_000_000 + 512
    t, bound = work.least_time(cfg, nnz, 64, work.peaks("TPU v5 lite"))
    assert bound == "bytes"
    assert t == pytest.approx(20_080_512 / 819e9)
    assert 24.4e-6 < t < 24.6e-6
