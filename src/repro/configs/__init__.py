"""Architecture registry: the assigned configs (+ the paper's own
Qwen2-VL-7B proxy), each with a FULL config (exact published numbers, dry-run
only) and a SMOKE config (reduced, runs a real step on CPU)."""
from __future__ import annotations

import importlib

from repro.configs.shapes import (SHAPES, SMOKE_SHAPES, ShapeSpec,
                                  applicable_shapes)

ARCH_IDS = [
    "jamba-v0.1-52b",
    "qwen2-moe-a2.7b",
    "llama4-scout-17b-a16e",
    "falcon-mamba-7b",
    "chatglm3-6b",
    "phi4-mini-3.8b",
    "qwen3-32b",
    "qwen2-7b",
    "qwen2-vl-72b",
    "musicgen-medium",
    "jamba2-3b",
]

EXTRA_IDS = ["qwen2-vl-7b"]  # paper-native proxy (benchmarks only)

_MODULES = {
    "jamba-v0.1-52b": "jamba_v0_1_52b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "llama4-scout-17b-a16e": "llama4_scout_17b_a16e",
    "falcon-mamba-7b": "falcon_mamba_7b",
    "chatglm3-6b": "chatglm3_6b",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "qwen3-32b": "qwen3_32b",
    "qwen2-7b": "qwen2_7b",
    "qwen2-vl-72b": "qwen2_vl_72b",
    "musicgen-medium": "musicgen_medium",
    "qwen2-vl-7b": "qwen2_vl_7b",
    "jamba2-3b": "jamba2_3b",
}


def get_config(arch: str, smoke: bool = False):
    if arch not in _MODULES:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULES)}")
    mod = importlib.import_module(f"repro.configs.{_MODULES[arch]}")
    return mod.SMOKE if smoke else mod.CONFIG


__all__ = ["ARCH_IDS", "EXTRA_IDS", "get_config", "SHAPES", "SMOKE_SHAPES",
           "ShapeSpec", "applicable_shapes"]
