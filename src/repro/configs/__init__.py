"""Architecture registry: ``get_config(arch_id)`` / ``get_reduced(arch_id)``."""
from __future__ import annotations

import importlib

from repro.configs.base import (  # noqa: F401
    EncoderConfig,
    FedRoundSpec,
    InputShape,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    SSMConfig,
    TrainConfig,
)
from repro.configs.shapes import (  # noqa: F401
    LONG_CONTEXT_ARCHS,
    SHAPES,
    default_round_spec,
    supports_shape,
)

_ARCH_MODULES = {
    "llama3.2-3b": "repro.configs.llama3_2_3b",
    "hymba-1.5b": "repro.configs.hymba_1_5b",
    "minicpm3-4b": "repro.configs.minicpm3_4b",
    "whisper-tiny": "repro.configs.whisper_tiny",
    "gemma3-1b": "repro.configs.gemma3_1b",
    "paligemma-3b": "repro.configs.paligemma_3b",
    "deepseek-v3-671b": "repro.configs.deepseek_v3_671b",
    "mamba2-2.7b": "repro.configs.mamba2_2_7b",
    "qwen2-moe-a2.7b": "repro.configs.qwen2_moe_a2_7b",
    "minitron-4b": "repro.configs.minitron_4b",
    "granite-4.0-h-small": "repro.configs.granite_4_0_h_small",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch_id]).CONFIG


def get_reduced(arch_id: str) -> ModelConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_ARCH_MODULES)}")
    return importlib.import_module(_ARCH_MODULES[arch_id]).reduced()
