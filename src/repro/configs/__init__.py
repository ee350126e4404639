"""Architecture registry: the 10 assigned archs and SDAR-30B-A3B (+ reduced smoke variants)
and the paper's own CNN layer profiles (GoogleNet / ResNet-50).

``get_config(name)`` returns the full ArchConfig; ``get_reduced(name)`` a
small same-family variant for CPU smoke tests.  Input shapes for the
dry-run matrix live in ``shapes.py``.
"""

from __future__ import annotations

from importlib import import_module

from ..models.common import ArchConfig

_MODULES = {
    "musicgen-large": "musicgen_large",
    "tinyllama-1.1b": "tinyllama_1_1b",
    "starcoder2-7b": "starcoder2_7b",
    "gemma2-2b": "gemma2_2b",
    "starcoder2-3b": "starcoder2_3b",
    "mixtral-8x7b": "mixtral_8x7b",
    "dbrx-132b": "dbrx_132b",
    "rwkv6-7b": "rwkv6_7b",
    "recurrentgemma-9b": "recurrentgemma_9b",
    "qwen2-vl-2b": "qwen2_vl_2b",
    "sdar-30b-a3b": "sdar_30b_a3b",
}

ARCH_NAMES = tuple(_MODULES)


def get_config(name: str, **overrides) -> ArchConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; available: {ARCH_NAMES}")
    cfg = import_module(f".{_MODULES[name]}", __package__).config()
    if overrides:
        import dataclasses

        cfg = dataclasses.replace(cfg, **overrides)
    return cfg


def get_reduced(name: str, **overrides) -> ArchConfig:
    if name not in _MODULES:
        raise KeyError(f"unknown arch {name!r}; available: {ARCH_NAMES}")
    cfg = import_module(f".{_MODULES[name]}", __package__).reduced()
    if overrides:
        import dataclasses

        cfg = dataclasses.replace(cfg, **overrides)
    return cfg
