"""Assigned-architecture registry: ``get_config(name)`` / ``get_smoke_config(name)``.

Every architecture in ARCHS is selectable via ``--arch <id>`` in the launch
scripts; smoke variants are reduced (2 layers, d_model <= 512, <= 4 experts)
same-family configs for CPU tests.
"""

from __future__ import annotations

import importlib
from typing import List

from repro.models.config import ModelConfig

ARCHS = [
    "minitron-8b",
    "granite-moe-3b-a800m",
    "granite-moe-3b-a800m-1chip",
    "mamba2-130m",
    "phi3-medium-14b",
    "qwen2-vl-2b",
    "dbrx-132b",
    "whisper-medium",
    "minicpm-2b",
    "qwen2-0.5b",
    "zamba2-7b",
]


def _module(name: str):
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {ARCHS}")
    return importlib.import_module(f"repro.configs.{name.replace('-', '_').replace('.', '_')}")


def get_config(name: str) -> ModelConfig:
    return _module(name).config()


def get_smoke_config(name: str) -> ModelConfig:
    return _module(name).smoke_config()


def list_archs() -> List[str]:
    return list(ARCHS)
