"""Architecture registry: ``--arch <id>`` resolution for every assigned
architecture (+ the paper's own GPT-2 pair).

``get_config(arch_id)`` / ``get_smoke_config(arch_id)`` accept the public
dashed ids; ``ARCHITECTURES`` lists them in the assignment's order.
"""

from __future__ import annotations

import importlib

from repro.configs.base import (
    INPUT_SHAPES, LoRAConfig, MLAConfig, ModelConfig, MoEConfig, ShapeConfig, SSMConfig,
)

__all__ = [
    "ARCHITECTURES",
    "INPUT_SHAPES",
    "LoRAConfig",
    "MLAConfig",
    "ModelConfig",
    "MoEConfig",
    "SSMConfig",
    "ShapeConfig",
    "get_config",
    "get_smoke_config",
]

# arch id -> module name
ARCHITECTURES: dict[str, str] = {
    "mamba2-130m": "mamba2_130m",
    "stablelm-1.6b": "stablelm_1_6b",
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "jamba-1.5-large-398b": "jamba_1_5_large_398b",
    "internvl2-76b": "internvl2_76b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "yi-9b": "yi_9b",
    "moonlight-16b-a3b": "moonlight_16b_a3b",
    "command-r-35b": "command_r_35b",
    "granite-moe-1b-a400m": "granite_moe_1b_a400m",
    # the paper's own models
    "gpt2-paper": "gpt2_paper",
}


def _module(arch_id: str):
    if arch_id not in ARCHITECTURES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(ARCHITECTURES)}")
    return importlib.import_module(f"repro.configs.{ARCHITECTURES[arch_id]}")


def get_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    return _module(arch_id).SMOKE_CONFIG
