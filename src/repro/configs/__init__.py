"""Architecture config registry: ``get_config("qwen3-8b")`` etc.

Each module defines one ``CONFIG`` with the exact assigned dimensions and a
source citation; ``ArchConfig.reduced()`` derives the CPU smoke variant and
``ArchConfig.long_context_variant()`` the sliding-window variant used for
long_500k on dense architectures.
"""
from __future__ import annotations

from typing import Dict

from repro.models.backbone.config import INPUT_SHAPES, ArchConfig, InputShape

from repro.configs.zamba2_7b import CONFIG as _zamba2
from repro.configs.qwen3_4b import CONFIG as _qwen3_4b
from repro.configs.qwen3_8b import CONFIG as _qwen3_8b
from repro.configs.llama3_2_3b import CONFIG as _llama32
from repro.configs.qwen3_32b import CONFIG as _qwen3_32b
from repro.configs.whisper_base import CONFIG as _whisper
from repro.configs.olmoe_1b_7b import CONFIG as _olmoe
from repro.configs.qwen2_vl_2b import CONFIG as _qwen2vl
from repro.configs.xlstm_1_3b import CONFIG as _xlstm
from repro.configs.phi3_5_moe import CONFIG as _phi35
from repro.configs.nemotron3_nano import CONFIG as _nemotron3

REGISTRY: Dict[str, ArchConfig] = {
    c.name: c
    for c in [
        _zamba2,
        _qwen3_4b,
        _qwen3_8b,
        _llama32,
        _qwen3_32b,
        _whisper,
        _olmoe,
        _qwen2vl,
        _xlstm,
        _phi35,
        _nemotron3,
    ]
}

ARCH_NAMES = tuple(REGISTRY)


def get_config(name: str) -> ArchConfig:
    if name not in REGISTRY:
        raise KeyError(f"unknown arch {name!r}; available: {sorted(REGISTRY)}")
    return REGISTRY[name]


__all__ = ["ARCH_NAMES", "INPUT_SHAPES", "REGISTRY", "ArchConfig", "InputShape", "get_config"]
