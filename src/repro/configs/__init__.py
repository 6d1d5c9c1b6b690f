"""Architecture config registry: ``get_config("<arch-id>")``.

The assigned architectures (public-literature pool) plus the paper's own
experiment models. Every config cites its source in the module docstring and
``ModelConfig.source``.
"""
from __future__ import annotations

import importlib

from repro.models import ModelConfig

ARCH_IDS = [
    "mamba2-130m",
    "recurrentgemma-9b",
    "gemma-7b",
    "minicpm3-4b",
    "internvl2-1b",
    "llama4-scout-17b-a16e",
    "grok-1-314b",
    "granite-3-2b",
    "seamless-m4t-medium",
    "qwen3-14b",
    "moonlight-16b-a3b",
]

PAPER_IDS = ["pythia-14m"]

_MODULES = {arch: "repro.configs." + arch.replace("-", "_") for arch in ARCH_IDS}


def get_config(arch: str) -> ModelConfig:
    if arch in _MODULES:
        return importlib.import_module(_MODULES[arch]).CONFIG
    if arch == "pythia-14m":
        return importlib.import_module("repro.configs.paper_models").PYTHIA_14M
    raise KeyError(f"unknown arch {arch!r}; options: {ARCH_IDS + PAPER_IDS}")


def all_configs() -> dict[str, ModelConfig]:
    return {arch: get_config(arch) for arch in ARCH_IDS}
