"""Architecture registry: ``--arch <id>`` -> (full config, smoke config)."""
from __future__ import annotations

from importlib import import_module

from repro.configs.base import ArchConfig

_MODULES = {
    "internvl2-2b": "repro.configs.internvl2_2b",
    "recurrentgemma-2b": "repro.configs.recurrentgemma_2b",
    "olmo-1b": "repro.configs.olmo_1b",
    "qwen2-0.5b": "repro.configs.qwen2_0_5b",
    "granite-3-8b": "repro.configs.granite_3_8b",
    "minicpm-2b": "repro.configs.minicpm_2b",
    "mixtral-8x22b": "repro.configs.mixtral_8x22b",
    "arctic-480b": "repro.configs.arctic_480b",
    "mamba2-780m": "repro.configs.mamba2_780m",
    "granite-4.0-h-micro": "repro.configs.granite_4_0_h_micro",
    "hubert-xlarge": "repro.configs.hubert_xlarge",
}

ARCH_IDS = tuple(_MODULES)


def get_config(arch_id: str, smoke: bool = False) -> ArchConfig:
    if arch_id not in _MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_MODULES)}")
    mod = import_module(_MODULES[arch_id])
    return mod.SMOKE if smoke else mod.FULL


def all_configs(smoke: bool = False):
    return {a: get_config(a, smoke) for a in ARCH_IDS}
