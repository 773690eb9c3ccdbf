"""The configuration of the system under test, from a configuration file."""

from __future__ import annotations


def model_config(spec: dict, *, remat: bool = False):
    """The program's ModelConfig for one ``models`` entry of a configuration;
    ``remat`` recomputes each layer's activations in the backward pass."""
    from repro.configs.base import LoRAConfig, ModelConfig

    fields = {k: v for k, v in spec.items() if k != "lora"}
    lora = dict(spec["lora"], targets=tuple(spec["lora"]["targets"]))
    return ModelConfig(lora=LoRAConfig(**lora), remat=remat, **fields)
