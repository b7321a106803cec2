from repro.configs.base import (
    ARCH_ALIASES,
    ARCH_IDS,
    ModelConfig,
    ShapeConfig,
    all_configs,
    get_config,
    get_reduced,
)

__all__ = [
    "ARCH_ALIASES",
    "ARCH_IDS",
    "ModelConfig",
    "ShapeConfig",
    "all_configs",
    "get_config",
    "get_reduced",
]
