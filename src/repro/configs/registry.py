"""--arch <id> resolution for launchers, tests, and benchmarks."""
from __future__ import annotations

import importlib
from typing import Dict

from repro.configs.base import ModelConfig

_ARCH_MODULES = {
    "mistral-nemo-12b": "mistral_nemo_12b",
    "mixtral-8x22b": "mixtral_8x22b",
    "smollm-135m": "smollm_135m",
    "internvl2-2b": "internvl2_2b",
    "rwkv6-7b": "rwkv6_7b",
    "stablelm-3b": "stablelm_3b",
    "stablelm-1.6b": "stablelm_1_6b",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    "recurrentgemma-2b": "recurrentgemma_2b",
    "paper-charlm": "paper_charlm",
}

ALL_ARCHS = tuple(_ARCH_MODULES)


def get_config(name: str) -> ModelConfig:
    try:
        mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    except KeyError:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {name: get_config(name) for name in _ARCH_MODULES}
