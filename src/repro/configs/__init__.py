"""Architecture registry: ``get_config(arch_id)`` -> (FULL, SMOKE).

All ten assigned architectures plus the paper's own tasks (see
``repro.models.paper_models``). IDs match the assignment exactly.
"""
from __future__ import annotations

import importlib

from repro.models.config import ModelConfig

_MODULES = {
    "qwen2-7b": "qwen2_7b",
    "minicpm-2b": "minicpm_2b",
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "internvl2-1b": "internvl2_1b",
    "rwkv6-7b": "rwkv6_7b",
    "hymba-1.5b": "hymba_1_5b",
    "whisper-tiny": "whisper_tiny",
    "granite-moe-3b-a800m": "granite_moe_3b",
    "mixtral-8x7b": "mixtral_8x7b",
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch: str) -> tuple[ModelConfig, ModelConfig]:
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.FULL, mod.SMOKE

