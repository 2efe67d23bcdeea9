"""Architecture registry.

Each assigned architecture is a module exporting ``CONFIG: ModelConfig`` (the
full, paper-exact configuration) and ``smoke_config() -> ModelConfig`` (a
reduced same-family configuration used by CPU smoke tests).  Full configs are
only ever lowered via the dry-run (ShapeDtypeStruct — no allocation).
"""
from __future__ import annotations

import importlib
from typing import Dict, List

from repro.config import ModelConfig

# arch-id -> module name
_REGISTRY: Dict[str, str] = {
    "rwkv6-3b": "rwkv6_3b",
    "phi3-mini-3.8b": "phi3_mini_3p8b",
    "qwen3-8b": "qwen3_8b",
    "yi-6b": "yi_6b",
    "granite-34b": "granite_34b",
    "llava-next-34b": "llava_next_34b",
    "seamless-m4t-large-v2": "seamless_m4t_large_v2",
    "grok-1-314b": "grok1_314b",
    "deepseek-v2-lite-16b": "deepseek_v2_lite_16b",
    # one chip's share of deepseek-v2-lite-16b (8-way expert parallelism)
    "deepseek-v2-lite-ep8": "deepseek_v2_lite_ep8",
    "recurrentgemma-2b": "recurrentgemma_2b",
    # the paper's own evaluation models
    "mnist-cnn": "mnist_cnn",
    "cifar-alexnet": "cifar_alexnet",
}

PAPER_ARCHS: List[str] = ["mnist-cnn", "cifar-alexnet"]
# deployment shares of an assigned architecture, not architectures
SHARES: List[str] = ["deepseek-v2-lite-ep8"]
ASSIGNED_ARCHS: List[str] = [k for k in _REGISTRY
                             if k not in PAPER_ARCHS + SHARES]


def _module(arch: str):
    if arch not in _REGISTRY:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_REGISTRY)}")
    return importlib.import_module(f"repro.configs.{_REGISTRY[arch]}")


def get_config(arch: str) -> ModelConfig:
    cfg = _module(arch).CONFIG
    cfg.validate()
    return cfg


def get_smoke_config(arch: str) -> ModelConfig:
    cfg = _module(arch).smoke_config()
    cfg.validate()
    return cfg


def list_archs() -> List[str]:
    return list(_REGISTRY)
