"""Architecture registry: one module per assigned architecture."""
from __future__ import annotations

import importlib

from repro.configs.base import (
    MLAConfig, ModelConfig, MoEConfig, ShapeConfig, SHAPES, runnable_shapes)

ARCHS = (
    "nemotron_4_15b",
    "gemma2_9b",
    "qwen2_0_5b",
    "chatglm3_6b",
    "recurrentgemma_9b",
    "qwen2_moe_a2_7b",
    "granite_moe_3b_a800m",
    "moonlight_16b_a3b",
    "xlstm_350m",
    "phi_3_vision_4_2b",
    "seamless_m4t_medium",
)


def canonical(name: str) -> str:
    return name.replace("-", "_").replace(".", "_")


def get(name: str) -> ModelConfig:
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    return mod.CONFIG


def get_smoke(name: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    mod = importlib.import_module(f"repro.configs.{canonical(name)}")
    return mod.SMOKE


__all__ = ["MLAConfig", "ModelConfig", "MoEConfig", "ShapeConfig", "SHAPES", "ARCHS",
           "runnable_shapes", "get", "get_smoke", "canonical"]
