"""Backend probes the dispatch table keys on.

A backend that fails to start raises here: a host without a working TPU
backend must never read as "cpu" and quietly run the Pallas interpreter
or the eager tier in place of the compiled kernels. :func:`why_unavailable`
carries the reason string for error messages ("tier 'tpu' forced but
unavailable: ...").
"""
from __future__ import annotations

import functools

import jax


@functools.lru_cache(maxsize=None)
def backend_platform() -> str:
    """The default JAX backend platform ("cpu" | "tpu" | "gpu")."""
    return jax.default_backend()


def is_tpu() -> bool:
    """True when Pallas kernels can be *compiled* (Mosaic), i.e. the host
    has a TPU backend — interpret mode does not need this."""
    return backend_platform() == "tpu"


def why_unavailable(tier_name: str) -> str:
    """Human-readable reason a kernel tier cannot run on this host."""
    if tier_name == "tpu":
        return (f"backend is {backend_platform()!r}, not 'tpu' "
                f"(Mosaic compilation needs a TPU)")
    return f"tier {tier_name!r} is always available"
