"""Fused factored-norm kernel (paper §2, Algorithm 1) as a Pallas-TPU kernel.

Computes the two d_in-dependent factored-norm terms in a single VMEM-resident
pass over W:

    base_sq_j = Σ_k W_jk²                      (base term)
    cross_j   = Σ_l B_jl · U_jl,  U = W @ Aᵀ   (cross term)

Grid: (d_out tiles  ×  d_in chunks), with the chunk dimension sequential
("arbitrary") so the [1, block_rows] output blocks accumulate across chunk
steps — the TPU analogue of the paper's chunked fp32 accumulation, with the
chunk budget expressed as a BlockSpec instead of an allocator budget.

TPU-specific win vs. the eager factored path: W is read from HBM **once** for
both terms (the jnp path reads W twice — once for the row-square reduce, once
for the U matmul), and U_c lives only in VMEM/registers (never an HBM
round-trip). The Gram term G = A·Aᵀ and ba_sq = rowsum((B·G)⊙B) are O(r²)
and stay in jnp (they are rank-dependent but tiny: G ≤ 2.4 MB at r = 768).

The norm is detached (DoRA §4.3) so no backward kernel exists by design.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.compat.pallas import pl, pltpu

_F32 = jnp.float32


def _norm_terms_kernel(w_ref, a_ref, b_ref, base_ref, cross_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        base_ref[...] = jnp.zeros_like(base_ref)
        cross_ref[...] = jnp.zeros_like(cross_ref)

    w = w_ref[...].astype(_F32)                    # [bm, bk]
    a = a_ref[...].astype(_F32)                    # [r, bk]
    b = b_ref[...].astype(_F32)                    # [bm, r]
    base_ref[...] += jnp.sum(w * w, axis=1)[None, :]
    u = jax.lax.dot_general(                       # U_c = W_c @ A_cᵀ  (MXU)
        w, a, (((1,), (1,)), ((), ())), preferred_element_type=_F32)
    cross_ref[...] += jnp.sum(b * u, axis=1)[None, :]


def norm_terms_pallas(W, A, B, *, block_rows: int, block_k: int,
                      interpret: bool = False):
    """Return (base_sq, cross) fp32 [d_out] for W [d_out, d_in], A [r, d_in],
    B [d_out, r]. d_out and d_in must be multiples of the block shape (the
    ops wrapper pads)."""
    d_out, d_in = W.shape
    r = A.shape[0]
    grid = (pl.cdiv(d_out, block_rows), pl.cdiv(d_in, block_k))
    out_shape = jax.ShapeDtypeStruct((1, d_out), _F32)
    base_sq, cross = pl.pallas_call(
        _norm_terms_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, block_k), lambda i, k: (i, k)),  # W
            pl.BlockSpec((r, block_k), lambda i, k: (0, k)),           # A
            pl.BlockSpec((block_rows, r), lambda i, k: (i, 0)),        # B
        ],
        out_specs=(
            pl.BlockSpec((1, block_rows), lambda i, k: (0, i)),
            pl.BlockSpec((1, block_rows), lambda i, k: (0, i)),
        ),
        out_shape=(out_shape, out_shape),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "norm_terms_pallas"},
    )(W, A, B)
    return base_sq[0], cross[0]
