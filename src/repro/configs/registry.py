"""Architecture registry: ``get_config(arch_id)`` / ``list_archs()``."""
from __future__ import annotations

import importlib

from repro.configs.base import ModelConfig

_ARCH_MODULES = {
    "starcoder2-15b": "repro.configs.starcoder2_15b",
    "mistral-nemo-12b": "repro.configs.mistral_nemo_12b",
    "tinyllama-1.1b": "repro.configs.tinyllama_1_1b",
    "smollm-360m": "repro.configs.smollm_360m",
    "whisper-base": "repro.configs.whisper_base",
    "arctic-480b": "repro.configs.arctic_480b",
    "kimi-k2-1t-a32b": "repro.configs.kimi_k2_1t",
    "xlstm-350m": "repro.configs.xlstm_350m",
    "pixtral-12b": "repro.configs.pixtral_12b",
    "jamba-v0.1-52b": "repro.configs.jamba_52b",
    "granite-4.0-h-small": "repro.configs.granite_4_0_h_small",
}


def list_archs() -> list[str]:
    return list(_ARCH_MODULES)


def get_config(arch: str) -> ModelConfig:
    if arch not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch!r}; available: {list_archs()}")
    return importlib.import_module(_ARCH_MODULES[arch]).CONFIG
