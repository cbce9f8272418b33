"""Architecture registry: ``--arch <id>`` resolves here."""
from __future__ import annotations

import importlib
from typing import Dict, Tuple

from repro.configs.base import ModelConfig

# arch id -> module path (ids are the assignment's exact spellings)
_ARCH_MODULES: Dict[str, str] = {
    "minicpm-2b": "repro.configs.minicpm_2b",
    "phi3-medium-14b": "repro.configs.phi3_medium_14b",
    "smollm-135m": "repro.configs.smollm_135m",
    "granite-3-2b": "repro.configs.granite_3_2b",
    "mamba2-2.7b": "repro.configs.mamba2_2p7b",
    "granite-moe-1b-a400m": "repro.configs.granite_moe_1b",
    "deepseek-v2-lite-16b": "repro.configs.deepseek_v2_lite_16b",
    "jamba-1.5-large-398b": "repro.configs.jamba_1p5_large_398b",
    "chameleon-34b": "repro.configs.chameleon_34b",
    "whisper-base": "repro.configs.whisper_base",
}

ARCH_IDS: Tuple[str, ...] = tuple(_ARCH_MODULES)


def get_config(arch_id: str, reduced: bool = False) -> ModelConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_ARCH_MODULES)}")
    mod = importlib.import_module(_ARCH_MODULES[arch_id])
    return mod.REDUCED if reduced else mod.CONFIG


__all__ = ["ModelConfig", "ARCH_IDS", "get_config"]
