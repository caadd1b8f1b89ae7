"""granite-moe-3b-a800m, one chip's share (configs/granite_moe_3b_a800m.py).

The deployment it stands for: each layer's 40 experts expert-parallel over 5
chips, 8 on each, and the 32 layers as 4 pipeline stages of 8.  This chip
holds one stage's 8 layers and, in each, experts 0..7 of the router's 40;
the router, attention, norms and the whole vocabulary are as published.
Every width is the source's.  277.35M parameters: per layer attention
6.29M, 8 experts 18.87M, the router 61k; the tied embedding 75.5M."""

import dataclasses

from repro.configs import granite_moe_3b_a800m as full
from repro.models.config import ModelConfig


def config() -> ModelConfig:
    return dataclasses.replace(full.config(), name="granite-moe-3b-a800m-1chip",
                               n_layers=8, n_experts_held=8)


def smoke_config() -> ModelConfig:
    return dataclasses.replace(full.smoke_config(), name="granite-moe-1chip-smoke",
                               n_experts_held=2)
