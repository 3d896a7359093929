"""Norm assembly kernel (paper §3.3, App. C kernel 3).

Fuses Eq. 5:  w_norm = sqrt(max(base_sq + two_s*cross + s2*ba_sq, 0))

over fp32 [d_out] vectors. The two scalars two_s = 2s and s2 = s² are
precomputed in fp64 and passed as compile-time constants. The paper's
store-reload barriers and inline-PTX ``sqrt.rn.f32`` exist to reproduce
PyTorch's separate-kernel evaluation order on CUDA; on TPU, XLA/Mosaic lowers
``jnp.sqrt`` on fp32 to the correctly-rounded op and the kernel expresses the
multiply-adds in the pinned order, so no equivalent hack is needed (see
DESIGN.md §2). max() propagates NaNs (IEEE 754, matching torch.clamp_min).

The magnitude division g = m / max(w_norm, eps) stays *outside* (paper §4) so
both norm paths share the same precision context.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.compat.pallas import pl

_F32 = jnp.float32


def _assembly_kernel(base_ref, cross_ref, ba_ref, out_ref,
                     *, two_s: float, s2: float):
    base = base_ref[...]
    # Pinned evaluation order: (base + two_s*cross) then (+ s2*ba).
    acc = base + jnp.asarray(two_s, _F32) * cross_ref[...]
    acc = acc + jnp.asarray(s2, _F32) * ba_ref[...]
    out_ref[...] = jnp.sqrt(jnp.maximum(acc, 0.0))


def assemble_norm_pallas(base_sq, cross, ba_sq, s: float, *,
                         block: int = 256, interpret: bool = False):
    """base_sq/cross/ba_sq: fp32 [d_out] → w_norm fp32 [d_out]."""
    (d_out,) = base_sq.shape
    # fp64 precompute of the scalars (paper App. C), then fp32 constants.
    s64 = float(s)
    kern = functools.partial(_assembly_kernel, two_s=2.0 * s64, s2=s64 * s64)
    vecs = [v.reshape(1, d_out) for v in (base_sq, cross, ba_sq)]
    block = min(block, d_out)
    spec = pl.BlockSpec((1, block), lambda i: (0, i))
    out = pl.pallas_call(
        kern,
        grid=(pl.cdiv(d_out, block),),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, d_out), _F32),
        interpret=interpret,
        metadata={"kernel": "assemble_norm_pallas"},
    )(*vecs)
    return out[0]
