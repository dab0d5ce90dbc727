"""Config registry: ``get_config(name)`` / ``--arch <id>`` resolution."""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401  (re-exported)
    DECODE_32K,
    LONG_500K,
    PREFILL_32K,
    SHAPES,
    TRAIN_4K,
    GTRACConfig,
    MeshConfig,
    ModelConfig,
    ShapeConfig,
    TrainConfig,
    shape_applicable,
)

#: arch id -> module name
_ARCH_MODULES: Dict[str, str] = {
    "starcoder2-7b": "starcoder2_7b",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "granite-34b": "granite_34b",
    "smollm-360m": "smollm_360m",
    "phi3.5-moe-42b-a6.6b": "phi35_moe",
    "qwen3-moe-30b-a3b": "qwen3_moe",
    "rwkv6-1.6b": "rwkv6_1_6b",
    "zamba2-2.7b": "zamba2_2_7b",
    "zamba2-7b": "zamba2_7b",
    "whisper-large-v3": "whisper_large_v3",
    "qwen2-vl-7b": "qwen2_vl_7b",
    # the paper's own evaluation model (GPT-2 Large, 36 layers)
    "gpt2-large": "gpt2_large",
}

#: models served by the chip benchmark, beside the assigned grid
SERVED_ONLY = ("gpt2-large", "zamba2-7b")
#: the ten assigned architectures
ASSIGNED_ARCHS: List[str] = [a for a in _ARCH_MODULES
                             if a not in SERVED_ONLY]
ALL_ARCHS: List[str] = list(_ARCH_MODULES)


def get_config(name: str) -> ModelConfig:
    if name not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[name]}")
    return mod.CONFIG


def get_shape(name: str) -> ShapeConfig:
    if name not in SHAPES:
        raise KeyError(f"unknown shape {name!r}; known: {sorted(SHAPES)}")
    return SHAPES[name]


def all_cells(include_paper_model: bool = False):
    """Yield every applicable (arch, shape) cell of the assigned grid."""
    archs = ALL_ARCHS if include_paper_model else ASSIGNED_ARCHS
    for arch in archs:
        cfg = get_config(arch)
        for shape in SHAPES.values():
            if shape_applicable(cfg, shape):
                yield arch, shape.name
