"""Pallas entry points: the ``pl`` / ``pltpu`` modules and the interpret
switch.

Kernels build their TPU compiler params as ``pltpu.CompilerParams(...)``
directly, so an argument the installed Pallas does not know raises at
trace time instead of being dropped.
"""
from __future__ import annotations

from jax.experimental import pallas as pl  # noqa: F401
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from repro.compat import probes


def resolve_interpret(interpret: bool | None) -> bool:
    """None → probe: interpret mode everywhere except a real TPU backend
    (where Mosaic compiles the kernel)."""
    if interpret is None:
        return not probes.is_tpu()
    return bool(interpret)
