"""Architecture registry: ``--arch <id>`` resolution.

Assigned archs use their public ids (hyphenated); the paper's own models are
also registered (the default ``--arch`` of ``launch/serve.py`` and of many
tests).
"""
from __future__ import annotations

from typing import Dict

from repro.config.arch import ArchConfig
from repro.configs.falcon_mamba_7b import CONFIG as FALCON_MAMBA_7B
from repro.configs.gemma2_9b import CONFIG as GEMMA2_9B
from repro.configs.granite_moe_1b import CONFIG as GRANITE_MOE_1B
from repro.configs.grok1_314b import CONFIG as GROK1_314B
from repro.configs.internvl2_26b import CONFIG as INTERNVL2_26B
from repro.configs.paper_models import LLAMA2_13B, LLAMA2_7B, OPT_30B
from repro.configs.qwen2_7b import CONFIG as QWEN2_7B
from repro.configs.qwen2p5_14b import CONFIG as QWEN2P5_14B
from repro.configs.starcoder2_15b import CONFIG as STARCODER2_15B
from repro.configs.whisper_medium import CONFIG as WHISPER_MEDIUM
from repro.configs.zamba2_2p7b import CONFIG as ZAMBA2_2P7B

ASSIGNED: Dict[str, ArchConfig] = {
    c.name: c for c in (
        WHISPER_MEDIUM, ZAMBA2_2P7B, QWEN2_7B, STARCODER2_15B, GEMMA2_9B,
        QWEN2P5_14B, GRANITE_MOE_1B, GROK1_314B, INTERNVL2_26B,
        FALCON_MAMBA_7B,
    )
}

PAPER: Dict[str, ArchConfig] = {
    c.name: c for c in (LLAMA2_7B, LLAMA2_13B, OPT_30B)
}

REGISTRY: Dict[str, ArchConfig] = {**ASSIGNED, **PAPER}


def get_arch(name: str) -> ArchConfig:
    if name not in REGISTRY:
        raise KeyError(
            f"unknown arch {name!r}; available: {sorted(REGISTRY)}")
    return REGISTRY[name]
