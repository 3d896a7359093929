"""Compiled-artifact introspection (memory / cost analysis)."""
from __future__ import annotations


def peak_memory_bytes(compiled) -> int:
    """Peak device memory of a compiled executable's execution-time
    allocations (temps and outputs), NOT the resident argument buffers —
    call sites that want a total footprint add ``argument_size_in_bytes -
    alias_size_in_bytes`` themselves."""
    return int(compiled.memory_analysis().peak_memory_in_bytes)


def cost_analysis_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` ("flops", "bytes accessed", ...)."""
    return dict(compiled.cost_analysis())
