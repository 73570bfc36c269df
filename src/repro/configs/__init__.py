"""Config registry: ``get_config(arch_id)`` and the assigned-shape table."""
from __future__ import annotations

import importlib

from repro.configs.base import (ArchConfig, EncoderConfig, InputShape,
                                INPUT_SHAPES, LayerSpec, MLAConfig,
                                MambaConfig, MoEConfig, Stage, XLSTMConfig,
                                YarnConfig, reduced)
from repro.configs.cnn import CNNConfig, VGG16, VGG_TINY, vgg_for

_ARCH_MODULES = {
    "internlm2-1.8b": "internlm2_1_8b",
    "gemma2-2b": "gemma2_2b",
    "xlstm-125m": "xlstm_125m",
    "whisper-medium": "whisper_medium",
    "gemma3-27b": "gemma3_27b",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b",
    "jamba-v0.1-52b": "jamba_v01_52b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "qwen2-7b": "qwen2_7b",
}

ARCH_IDS = tuple(_ARCH_MODULES)


def get_config(name: str) -> ArchConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.CONFIG


def all_configs():
    return {name: get_config(name) for name in ARCH_IDS}


__all__ = [
    "ArchConfig", "CNNConfig", "EncoderConfig", "InputShape", "INPUT_SHAPES",
    "LayerSpec", "MLAConfig", "MambaConfig", "MoEConfig", "Stage",
    "XLSTMConfig", "YarnConfig", "ARCH_IDS", "get_config", "all_configs", "reduced",
    "VGG16", "VGG_TINY", "vgg_for",
]
