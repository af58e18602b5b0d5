"""Architecture config registry.

``get_config(arch_id)`` returns the full production ``ModelConfig``;
``get_smoke_config(arch_id)`` the reduced same-family variant (<=2 layers,
d_model <= 512, <= 4 experts) used by the CPU smoke tests;
``sized_config(arch_id, size, layers)`` picks one of the two and cuts
its depth (the train CLI's ``--size`` / ``--layers``).

Assigned architectures (public pool, source in each module):
  rwkv6-3b, qwen1.5-4b, yi-9b, musicgen-medium, qwen3-moe-30b-a3b,
  qwen3-4b, internvl2-26b, granite-3-8b, recurrentgemma-9b,
  granite-moe-3b-a800m
plus the paper's own workload: logreg (logistic regression, Section 4).
"""
from __future__ import annotations

import importlib
from typing import Optional

from repro.configs.base import (
    MESHES,
    MeshConfig,
    ModelConfig,
    PodRefreshConfig,
    SHAPES,
    ShapeConfig,
)

ARCH_IDS = (
    "rwkv6-3b",
    "qwen1.5-4b",
    "yi-9b",
    "musicgen-medium",
    "qwen3-moe-30b-a3b",
    "qwen3-4b",
    "internvl2-26b",
    "granite-3-8b",
    "recurrentgemma-9b",
    "granite-moe-3b-a800m",
)


def _module(arch_id: str):
    mod = arch_id.replace("-", "_").replace(".", "_")
    return importlib.import_module(f"repro.configs.{mod}")


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in ARCH_IDS:
        raise ValueError(f"unknown arch {arch_id!r}; options: {ARCH_IDS}")
    return _module(arch_id).config()


def get_smoke_config(arch_id: str) -> ModelConfig:
    if arch_id not in ARCH_IDS:
        raise ValueError(f"unknown arch {arch_id!r}; options: {ARCH_IDS}")
    return _module(arch_id).smoke_config()


def sized_config(arch_id: str, size: str = "smoke",
                 layers: Optional[int] = None) -> ModelConfig:
    """``size="full"``: the published config; ``"smoke"``: the reduced
    variant. ``layers`` cuts depth only — widths, heads, head_dim, LoRA
    ranks and vocabulary are never cut."""
    if size == "full":
        cfg = get_config(arch_id)
    elif size == "smoke":
        cfg = get_smoke_config(arch_id)
    else:
        raise ValueError(f"unknown size {size!r}; options: smoke, full")
    if layers is not None:
        if layers < 1:
            raise ValueError(f"layers must be >= 1, got {layers}")
        cfg = cfg.replace(n_layers=layers)
    return cfg


__all__ = [
    "ModelConfig",
    "MeshConfig",
    "MESHES",
    "PodRefreshConfig",
    "ShapeConfig",
    "SHAPES",
    "ARCH_IDS",
    "get_config",
    "get_smoke_config",
    "sized_config",
]
