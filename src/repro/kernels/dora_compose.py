"""Fused DoRA compose kernels (paper §3.1, §3.2) as Pallas-TPU kernels.

TPU adaptation of the paper's Triton kernels. The composition

    delta = (g - 1) ⊙ base + g ⊙ s ⊙ lora

is element-wise with a row-broadcast of g along the output feature dim. In
eager form it is four kernel launches / ~12 HBM passes; fused it is a single
pass: 2 tensor reads (base, lora) + small vector reads + 1 write. On TPU the
blocks are VMEM tiles shaped (block_rows, block_cols) with the lane dim a
multiple of 128.

The forward takes the fp32 *vector* gm1 = g - 1 instead of g: this pins the
stable form — (g - 1) is computed once in fp32 outside the kernel and never
reconstructed in low precision — and all paths share the canonical
evaluation order ``s * lora`` first, then ``g · (·)`` (paper §3.1). The
forward optionally dual-outputs ``inner = s*lora + base`` (paper §4 Tier 1),
the tensor saved for the magnitude gradient, eliminating the separate
forward-pass materialization.

The backward kernel emits d_lora = (g*s)*dY and d_base = (g-1)*dY in one pass
(paper §3.2). d_mag uses a separate jnp reduction — the exact analogue of the
paper's choice of a separate ``.sum()`` over ``tl.atomic_add`` (deterministic
reduction order).

Shape constraint (paper App. C): d_out must be divisible by 128; the ops
wrapper pads rows and enforces/falls back on the feature dim.

Matmul-fused variant (one fusion deeper than the paper): the forward takes
``h = x @ Aᵀ [M, r]`` and ``B [d_out, r]`` instead of the materialized
``lora = h @ Bᵀ`` — the LoRA up-projection runs on the MXU inside the same
pass that composes the delta, so the ``[M, d_out]`` ``lora`` tensor is never
written to (or re-read from) HBM: 3 full-matrix passes become 2. The matching
backward emits ``d_h = (g·s)·dY @ B`` fused with ``d_base = (g-1)·dY`` in a
single pass over dY, accumulating the ``[bm, r]`` d_h tile across the
sequential d_out-chunk grid dimension (same accumulation pattern as the
factored-norm kernel). r is zero-padded to the 128-lane width by the ops
wrapper; zero columns perturb neither contraction.

Under SPMD the same kernels run SHARD-LOCAL inside shard_map (the ops
wrapper takes a ``ComposeSharding`` plan): each device composes its
``[rows_local, d_out_local]`` tile from a rank-replicated ``h`` shard, with
block specs derived from the mesh axis sizes via :func:`local_block_shape`
(row-sharded d_out shrinks block_n to the local shard; r stays replicated).
The forward needs no collectives; the backward psums the accumulated d_h
tile over the d_out axes — the one collective a contraction over a sharded
d_out cannot avoid — and d_B/d_g over the row axes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.compat.pallas import pl, pltpu
from repro.core.config import shrink_block_rows

_F32 = jnp.float32


def pick_block_n(n: int, cap: int) -> int:
    """Largest multiple of 128 (the lane width) that divides n, at most
    cap — the feature-dim block every compose/norm grid uses."""
    if n % 128 != 0:
        raise ValueError(f"feature dim {n} not divisible by 128 "
                         "(paper App. C shape constraint)")
    for t in range(max(1, cap // 128), 0, -1):
        if n % (128 * t) == 0:
            return 128 * t
    return 128


def _fwd_kernel(base_ref, lora_ref, gm1_ref, delta_ref, *, s: float):
    b = base_ref[...].astype(_F32)
    l = lora_ref[...].astype(_F32)
    gm1 = gm1_ref[...].astype(_F32)        # (1, bn) broadcasts over rows
    t = jnp.asarray(s, _F32) * l           # canonical order: s*lora first
    delta_ref[...] = (gm1 * b + (gm1 + 1.0) * t).astype(delta_ref.dtype)


def _fwd_kernel_dual(base_ref, lora_ref, gm1_ref, delta_ref, inner_ref,
                     *, s: float):
    b = base_ref[...].astype(_F32)
    l = lora_ref[...].astype(_F32)
    gm1 = gm1_ref[...].astype(_F32)
    t = jnp.asarray(s, _F32) * l
    delta_ref[...] = (gm1 * b + (gm1 + 1.0) * t).astype(delta_ref.dtype)
    inner_ref[...] = (b + t).astype(inner_ref.dtype)


def _bwd_kernel(dy_ref, gm1_ref, gs_ref, dbase_ref, dlora_ref):
    dy = dy_ref[...].astype(_F32)
    gm1 = gm1_ref[...].astype(_F32)
    gs = gs_ref[...].astype(_F32)
    dbase_ref[...] = (gm1 * dy).astype(dbase_ref.dtype)
    dlora_ref[...] = (gs * dy).astype(dlora_ref.dtype)


def _row_specs(block_m: int, block_n: int):
    mat = pl.BlockSpec((block_m, block_n), lambda i, j: (i, j))
    vec = pl.BlockSpec((1, block_n), lambda i, j: (0, j))
    return mat, vec


def compose_fwd_pallas(base, lora, gm1, s: float, *,
                       save_inner: bool,
                       block_m: int, block_n: int,
                       interpret: bool = False):
    """base, lora: [M, N]; gm1: fp32 [1, N]. Returns delta (+ inner)."""
    m, n = base.shape
    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n))
    mat, vec = _row_specs(block_m, block_n)
    out_shape = jax.ShapeDtypeStruct((m, n), base.dtype)
    if save_inner:
        kern = functools.partial(_fwd_kernel_dual, s=float(s))
        return pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[mat, mat, vec],
            out_specs=(mat, mat),
            out_shape=(out_shape, out_shape),
            interpret=interpret,
            metadata={"kernel": "compose_fwd_pallas"},
        )(base, lora, gm1)
    kern = functools.partial(_fwd_kernel, s=float(s))
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[mat, mat, vec],
        out_specs=mat,
        out_shape=out_shape,
        interpret=interpret,
        metadata={"kernel": "compose_fwd_pallas"},
    )(base, lora, gm1)


def compose_bwd_pallas(dy, gm1, gs, *, block_m: int, block_n: int,
                       interpret: bool = False):
    """dy: [M, N]; gm1, gs: fp32 [1, N]. Returns (d_base, d_lora) fused."""
    m, n = dy.shape
    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n))
    mat, vec = _row_specs(block_m, block_n)
    out_shape = jax.ShapeDtypeStruct((m, n), dy.dtype)
    return pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[mat, vec, vec],
        out_specs=(mat, mat),
        out_shape=(out_shape, out_shape),
        interpret=interpret,
        metadata={"kernel": "compose_bwd_pallas"},
    )(dy, gm1, gs)


# ---------------------------------------------------------------------------
# Matmul-fused compose: the LoRA up-projection h @ Bᵀ never leaves VMEM.
# ---------------------------------------------------------------------------

def local_block_shape(m: int, n: int, *, row_shards: int = 1,
                      dout_shards: int = 1, block_m: int = 256,
                      block_n: int = 1024) -> tuple[int, int]:
    """Block specs for a shard-local kernel invocation, derived from the
    mesh axis sizes: the grid tiles the LOCAL ``[m/row_shards,
    n/dout_shards]`` shard, so the caps shrink to the shard before the
    usual largest-divisible-multiple-of-128 (lanes) / row rules apply.
    ``row_shards``/``dout_shards`` are the products of the mesh axis sizes
    sharding the row and feature dims (1 = unsharded — the trivial mesh).

    Shares one derivation with the dispatch crossover and the bench bytes
    model: the row rule is :func:`repro.core.config.shrink_block_rows`
    (the same one ``DoRAConfig.resolve_mm_block_rows`` applies) and the
    feature rule is :func:`pick_block_n` — so the crossover guard, the
    kernel, and the bench all price the same tiles.
    """
    if n % dout_shards != 0 or (n // dout_shards) % 128 != 0:
        raise ValueError(
            f"d_out={n} over {dout_shards} shards breaks the 128-lane "
            f"block constraint (paper App. C, applied per shard)")
    n_local = n // dout_shards
    m_local = -(-m // row_shards)
    return (shrink_block_rows(block_m, m_local),
            pick_block_n(n_local, block_n))


def _mm_fwd_kernel(base_ref, h_ref, b_ref, gm1_ref, delta_ref, *, s: float):
    b = base_ref[...].astype(_F32)                 # [bm, bn]
    h = h_ref[...].astype(_F32)                    # [bm, rp]
    bm_ = b_ref[...].astype(_F32)                  # [bn, rp]
    gm1 = gm1_ref[...].astype(_F32)                # (1, bn)
    lora = jax.lax.dot_general(                    # h @ B_tileᵀ on the MXU
        h, bm_, (((1,), (1,)), ((), ())), preferred_element_type=_F32)
    t = jnp.asarray(s, _F32) * lora                # canonical order (§3.1)
    delta_ref[...] = (gm1 * b + (gm1 + 1.0) * t).astype(delta_ref.dtype)


def _mm_bwd_kernel(dy_ref, b_ref, gm1_ref, gs_ref, dbase_ref, dh_ref):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        dh_ref[...] = jnp.zeros_like(dh_ref)

    dy = dy_ref[...].astype(_F32)                  # [bm, bn]
    gm1 = gm1_ref[...].astype(_F32)                # (1, bn)
    gs = gs_ref[...].astype(_F32)                  # (1, bn)
    dbase_ref[...] = (gm1 * dy).astype(dbase_ref.dtype)
    t = gs * dy                                    # (g·s)·dY tile
    dh_ref[...] += jax.lax.dot_general(            # accumulate over d_out
        t, b_ref[...].astype(_F32), (((1,), (0,)), ((), ())),
        preferred_element_type=_F32)


def compose_mm_fwd_pallas(base, h, B, gm1, s: float, *,
                          block_m: int, block_n: int,
                          interpret: bool = False):
    """base: [M, N]; h: [M, rp]; B: [N, rp]; gm1: fp32 [1, N].

    Returns delta [M, N] = (g-1)⊙base + g⊙s⊙(h @ Bᵀ) with the up-projection
    computed per-tile in VMEM. rp (the padded rank) must be a lane multiple;
    callers pad through the ops wrapper.
    """
    m, n = base.shape
    rp = h.shape[1]
    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n))
    mat, vec = _row_specs(block_m, block_n)
    return pl.pallas_call(
        functools.partial(_mm_fwd_kernel, s=float(s)),
        grid=grid,
        in_specs=[
            mat,                                            # base (i, j)
            pl.BlockSpec((block_m, rp), lambda i, j: (i, 0)),   # h (i, ·)
            pl.BlockSpec((block_n, rp), lambda i, j: (j, 0)),   # B (j, ·)
            vec,                                            # gm1 (·, j)
        ],
        out_specs=mat,
        out_shape=jax.ShapeDtypeStruct((m, n), base.dtype),
        interpret=interpret,
        metadata={"kernel": "compose_mm_fwd_pallas"},
    )(base, h, B, gm1)


def compose_mm_bwd_pallas(dy, B, gm1, gs, *, block_m: int, block_n: int,
                          interpret: bool = False):
    """dy: [M, N]; B: [N, rp]; gm1, gs: fp32 [1, N].

    Returns (d_base [M, N], d_h fp32 [M, rp]) in ONE pass over dY: the d_h
    tile accumulates across the sequential d_out-chunk grid dimension
    (paper §3.2 extended one matmul deeper — dY is read once for both
    cotangents instead of once for d_base and once for the d_lora @ B
    matmul).
    """
    m, n = dy.shape
    rp = B.shape[1]
    grid = (pl.cdiv(m, block_m), pl.cdiv(n, block_n))
    mat, vec = _row_specs(block_m, block_n)
    return pl.pallas_call(
        _mm_bwd_kernel,
        grid=grid,
        in_specs=[
            mat,                                            # dy (i, j)
            pl.BlockSpec((block_n, rp), lambda i, j: (j, 0)),   # B (j, ·)
            vec, vec,                                       # gm1, gs (·, j)
        ],
        out_specs=(
            mat,                                            # d_base (i, j)
            pl.BlockSpec((block_m, rp), lambda i, j: (i, 0)),   # d_h (i, ·)
        ),
        out_shape=(jax.ShapeDtypeStruct((m, n), dy.dtype),
                   jax.ShapeDtypeStruct((m, rp), _F32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "compose_mm_bwd_pallas"},
    )(dy, B, gm1, gs)
