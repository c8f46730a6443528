"""Backend-dispatched engines: inference (VoteEngine) + training (TrainEngine).

>>> from repro.engine import get_engine, get_train_engine
>>> eng = get_engine("mxu_fused", cfg, state)   # or oracle / adder_tree /
>>> eng.infer(literals).prediction              #   swar_packed / time_domain
>>> trainer = get_train_engine("fused", cfg)    # or reference / packed
>>> state = trainer.step(state, key, literals, labels)
"""

from .base import (DEFAULT_BACKEND, EngineResult, ServiceStats, VoteEngine,
                   available_backends, clear_engine_cache, engine_cache_info,
                   evict_engines_for_state, get_engine, infer_padded,
                   nearest_rank, pack_result, pad_batch, register_backend,
                   set_engine_cache_budget, state_nbytes,
                   weight_engines_for_state)
from . import backends  # noqa: F401  (registers the built-in backends)
from . import cascade  # noqa: F401  (registers the early-exit cascade)
from .sharding import ShardedEngine
from .train import (DEFAULT_TRAIN_BACKEND, TrainEngine,
                    available_train_backends, clear_train_engine_cache,
                    export_key_cursor, get_train_engine, import_key_cursor,
                    register_train_backend, train_engine_cache_info,
                    train_engine_opts)

__all__ = ["DEFAULT_BACKEND", "DEFAULT_TRAIN_BACKEND", "EngineResult",
           "ServiceStats", "nearest_rank",
           "VoteEngine", "TrainEngine", "ShardedEngine",
           "available_backends", "available_train_backends",
           "clear_engine_cache", "clear_train_engine_cache",
           "engine_cache_info", "train_engine_cache_info",
           "evict_engines_for_state", "weight_engines_for_state",
           "set_engine_cache_budget", "state_nbytes",
           "get_engine", "get_train_engine", "infer_padded", "pad_batch",
           "pack_result",
           "register_backend", "register_train_backend",
           "export_key_cursor", "import_key_cursor", "train_engine_opts",
           "engine_from_model_config"]


def engine_from_model_config(model_cfg, state, **opts) -> VoteEngine:
    """Build the engine a registered ``family="tm"`` ModelConfig asks for.

    TM configs repurpose LM fields (see ``repro.configs.tm_paper``):
    ``n_heads``=C, ``d_ff``=M (clauses/class), ``d_model``=F,
    ``rope_theta``=T, ``norm_eps``=s; plus the ``backend`` /
    ``shard_batch`` knobs this engine layer dispatches on.
    """
    from repro.core.tm import TMConfig
    cfg = TMConfig(n_classes=model_cfg.n_heads, n_clauses=model_cfg.d_ff,
                   n_features=model_cfg.d_model, T=int(model_cfg.rope_theta),
                   s=model_cfg.norm_eps)
    return get_engine(model_cfg.backend, cfg, state,
                      shard_batch=model_cfg.shard_batch, **opts)
