"""Assigned-architecture registry: ``--arch <id>`` resolution.

Each module exports CONFIG (the exact published dims) and SMOKE (a reduced
same-family config for CPU tests).  `get_config(arch)` / `get_smoke(arch)`
are the public API; `ARCHS` lists every selectable id (11 LM archs
+ the paper's own SNN controller).
"""
from __future__ import annotations

import importlib

ARCHS = [
    "qwen2-72b", "internlm2-20b", "qwen3-4b", "qwen1.5-32b",
    "zamba2-7b", "deepseek-moe-16b", "grok-1-314b",
    "musicgen-medium", "pixtral-12b", "mamba2-1.3b",
    "deepseek-v2-lite", "firefly-snn",
]

_MOD = {a: a.replace("-", "_").replace(".", "_") for a in ARCHS}


def _load(arch: str):
    if arch not in _MOD:
        raise KeyError(f"unknown arch {arch!r}; choose from {ARCHS}")
    return importlib.import_module(f"repro.configs.{_MOD[arch]}")


def get_config(arch: str):
    return _load(arch).CONFIG


def get_smoke(arch: str):
    return _load(arch).SMOKE
