"""Architecture registry: ``get(arch_id)`` / ``get_reduced(arch_id)``."""

from __future__ import annotations

from typing import Callable, Dict, Tuple

from repro.configs import (
    base,
    command_r_35b,
    deepseek_moe_16b,
    deepseek_v2_lite_16b,
    granite_4_0_h_micro,
    hymba_1_5b,
    mistral_nemo_12b,
    musicgen_medium,
    pixtral_12b,
    qwen3_1_7b,
    xlstm_1_3b,
    yi_9b,
)
from repro.configs.base import SHAPES, ModelConfig, ShapeSpec, input_specs

_MODULES = (
    yi_9b,
    qwen3_1_7b,
    mistral_nemo_12b,
    command_r_35b,
    deepseek_v2_lite_16b,
    deepseek_moe_16b,
    musicgen_medium,
    xlstm_1_3b,
    hymba_1_5b,
    pixtral_12b,
    granite_4_0_h_micro,
)

REGISTRY: Dict[str, Tuple[Callable[[], ModelConfig], Callable[[], ModelConfig]]] = {
    m.ARCH_ID: (m.full, m.reduced) for m in _MODULES
}

ARCH_IDS = tuple(REGISTRY)


def get(arch_id: str) -> ModelConfig:
    try:
        return REGISTRY[arch_id][0]()
    except KeyError as e:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}") from e


def get_reduced(arch_id: str) -> ModelConfig:
    try:
        return REGISTRY[arch_id][1]()
    except KeyError as e:
        raise KeyError(f"unknown arch {arch_id!r}; known: {ARCH_IDS}") from e


def cells(cfg: ModelConfig):
    """The assigned (shape) cells for an architecture (with skip notes)."""
    out = []
    for s in SHAPES.values():
        if s.name == "long_500k" and not cfg.supports_long_context_decode:
            out.append((s, "skip: pure full-attention arch (quadratic 500k)"))
        else:
            out.append((s, None))
    return out


__all__ = [
    "REGISTRY", "ARCH_IDS", "get", "get_reduced", "cells",
    "SHAPES", "ModelConfig", "ShapeSpec", "input_specs", "base",
]
