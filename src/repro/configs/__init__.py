"""Architecture registry: ``get_config(name)`` and per-arch default knobs.

One module per assigned architecture (exact figures from the assignment
table); ``ARCH_REGISTRY`` maps id -> (ModelConfig, default TrainConfig
overrides).
"""
from __future__ import annotations

from typing import Dict

from repro.configs.base import (
    SHAPES,
    SINGLE_POD,
    MeshConfig,
    ModelConfig,
    RunConfig,
    ServeConfig,
    ShardingConfig,
    ShapeConfig,
    TrainConfig,
)

from repro.configs.kimi_k2_1t_a32b import CONFIG as _kimi, TRAIN as _kimi_train
from repro.configs.dbrx_132b import CONFIG as _dbrx
from repro.configs.smollm_135m import CONFIG as _smollm
from repro.configs.qwen3_0_6b import CONFIG as _qwen3
from repro.configs.llama3_2_3b import CONFIG as _llama32
from repro.configs.yi_34b import CONFIG as _yi
from repro.configs.chameleon_34b import CONFIG as _chameleon
from repro.configs.mamba2_370m import CONFIG as _mamba2
from repro.configs.whisper_large_v3 import CONFIG as _whisper
from repro.configs.hymba_1_5b import CONFIG as _hymba
from repro.configs.moonlight_16b_a3b import CONFIG as _moonlight

ARCH_REGISTRY: Dict[str, ModelConfig] = {
    c.name: c
    for c in (
        _kimi,
        _dbrx,
        _smollm,
        _qwen3,
        _llama32,
        _yi,
        _chameleon,
        _mamba2,
        _whisper,
        _hymba,
        _moonlight,
    )
}

# Per-arch TrainConfig overrides (scale-driven): microbatch counts sized
# so per-device saved activations fit a 16 GB v5e chip at train_4k
# (global batch 256 over 16 data shards => 16 sequences/device; the
# >=30B archs additionally sequence-shard saved residuals, see
# _SHARDING_OVERRIDES).
_TRAIN_OVERRIDES: Dict[str, TrainConfig] = {
    "kimi-k2-1t-a32b": _kimi_train,
    "dbrx-132b": TrainConfig(num_microbatches=8),
    "yi-34b": TrainConfig(num_microbatches=8),
    "chameleon-34b": TrainConfig(num_microbatches=8),
    "llama3.2-3b": TrainConfig(num_microbatches=4),
    "whisper-large-v3": TrainConfig(num_microbatches=4),
    "qwen3-0.6b": TrainConfig(num_microbatches=2),
    "mamba2-370m": TrainConfig(num_microbatches=2),
    "hymba-1.5b": TrainConfig(num_microbatches=2),
}

_SHARDING_OVERRIDES: Dict[str, ShardingConfig] = {
    "kimi-k2-1t-a32b": ShardingConfig(seq_shard_activations=True),
    "dbrx-132b": ShardingConfig(seq_shard_activations=True),
    "yi-34b": ShardingConfig(seq_shard_activations=True),
    "chameleon-34b": ShardingConfig(seq_shard_activations=True),
}

ARCH_NAMES = tuple(ARCH_REGISTRY)


def get_config(name: str) -> ModelConfig:
    try:
        return ARCH_REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown arch {name!r}; available: {', '.join(ARCH_NAMES)}"
        ) from None


def get_train_config(name: str) -> TrainConfig:
    return _TRAIN_OVERRIDES.get(name, TrainConfig())


def make_run_config(arch: str, shape: str, **overrides) -> RunConfig:
    cfg = RunConfig(
        model=get_config(arch),
        shape=SHAPES[shape],
        train=get_train_config(arch),
        sharding=_SHARDING_OVERRIDES.get(arch, ShardingConfig()),
    )
    if overrides:
        cfg = cfg.replace(**overrides)
    return cfg


__all__ = [
    "ARCH_REGISTRY",
    "ARCH_NAMES",
    "SHAPES",
    "SINGLE_POD",
    "ModelConfig",
    "ShapeConfig",
    "MeshConfig",
    "RunConfig",
    "TrainConfig",
    "ServeConfig",
    "ShardingConfig",
    "get_config",
    "get_train_config",
    "make_run_config",
]
