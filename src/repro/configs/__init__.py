"""Architecture config registry.

``get_config(arch_id)`` returns the full published config, which
real-mode serving runs on an accelerator (``repro.launch.serve``,
``chip_smoke.py``); ``get_smoke_config(arch_id)`` returns the reduced
same-family variant that CPU tests and CPU real-mode runs serve.
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.configs.base import (  # noqa: F401  (re-exports)
    INPUT_SHAPES,
    SHAPES_BY_NAME,
    FrontendConfig,
    InputShape,
    MLAConfig,
    ModelConfig,
    MoEConfig,
    RWKVConfig,
    SSMConfig,
)

# arch id -> module name
_REGISTRY: Dict[str, str] = {
    "mistral-nemo-12b": "repro.configs.mistral_nemo_12b",
    "rwkv6-1.6b": "repro.configs.rwkv6_1p6b",
    "olmoe-1b-7b": "repro.configs.olmoe_1b_7b",
    "gemma3-12b": "repro.configs.gemma3_12b",
    "zamba2-7b": "repro.configs.zamba2_7b",
    "qwen2-1.5b": "repro.configs.qwen2_1p5b",
    "llava-next-mistral-7b": "repro.configs.llava_next_mistral_7b",
    "llama3.2-3b": "repro.configs.llama3p2_3b",
    "deepseek-v2-236b": "repro.configs.deepseek_v2_236b",
    "whisper-large-v3": "repro.configs.whisper_large_v3",
}


def list_archs() -> List[str]:
    return list(_REGISTRY)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_REGISTRY)}")
    return importlib.import_module(_REGISTRY[arch_id]).CONFIG


def get_smoke_config(arch_id: str) -> ModelConfig:
    if arch_id not in _REGISTRY:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(_REGISTRY)}")
    return importlib.import_module(_REGISTRY[arch_id]).smoke_config()
