from repro.configs.base import (
    SHAPES,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    ParallelConfig,
    ShapeConfig,
    SSMConfig,
    TrainConfig,
)
from repro.configs.registry import ARCH_IDS, all_configs, get_config

__all__ = [
    "SHAPES",
    "MLAConfig",
    "ModelConfig",
    "MoEConfig",
    "ParallelConfig",
    "ShapeConfig",
    "SSMConfig",
    "TrainConfig",
    "ARCH_IDS",
    "all_configs",
    "get_config",
]
