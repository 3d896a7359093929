"""Pallas-TPU selective-scan kernel (Mamba-1 recurrence).

    h_t = exp(dt_t ⊙ A) ⊙ h_{t-1} + (dt_t·x_t) ⊗ B_t
    y_t = Σ_n h_t ⊙ C_t

The TPU adaptation of Mamba's hardware-aware scan: the recurrent state h
lives in VMEM scratch across sequence chunks; the discretized terms
a = exp(dt⊙A) and b = (dt·x)⊗B are computed in-register per token and
never touch HBM. Per-layer HBM traffic = read dt/dtx ([B,S,di]) + B/C
([B,S,n]) once + write y once — the roofline minimum — versus the
associative-scan XLA lowering's ~550x per-tensor traffic (EXPERIMENTS.md
§Perf cell 1).

Layout: the feature dim di is the 128-lane axis everywhere; the SSM state
dim n (=16) sits on sublanes, so h is carried as [n, block_di]. Grid =
(B, di_tiles, seq_chunks) with the chunk dim sequential ("arbitrary") —
for a fixed (batch, tile) the chunks iterate consecutively and the VMEM
scratch carries h; ``@pl.when(k == 0)`` reloads h0 at each new tile.

The within-chunk loop is a ``fori_loop`` over tokens: each step is a few
[n, block_di] VPU ops — exactly the unrolled-recurrence schedule the
``fused_chunk`` XLA path expresses, minus the loop-carry HBM round trips.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.compat.pallas import pl, pltpu, resolve_interpret

_F32 = jnp.float32


def _scan_kernel(dt_ref, dtx_ref, b_ref, c_ref, at_ref, h0_ref,
                 y_ref, hout_ref, h_scr, *, chunk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        h_scr[...] = h0_ref[0]                      # [n, bd]

    at = at_ref[...]                                 # [n, bd]  (= A^T)

    def step(j, h):
        dt_j = dt_ref[0, j][None, :]                 # [1, bd]
        a_j = jnp.exp(dt_j * at)                     # [n, bd]
        b_j = dtx_ref[0, j][None, :] * b_ref[0, j][:, None]
        h = a_j * h + b_j
        y_ref[0, j] = jnp.sum(h * c_ref[0, j][:, None], axis=0)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h
    hout_ref[0] = h


def selective_scan_pallas(dt, dtx, Bm, Cm, A_t, h0_t, *,
                          block_di: int = 512, chunk: int = 64,
                          interpret: bool | None = None):
    """dt, dtx: [B, S, di]; Bm, Cm: [B, S, n]; A_t: [n, di];
    h0_t: [B, n, di] — all fp32, S % chunk == 0, di % block_di == 0.
    Returns (y [B, S, di], h_final [B, n, di])."""
    B, S, di = dt.shape
    n = A_t.shape[0]
    interpret = resolve_interpret(interpret)
    grid = (B, di // block_di, S // chunk)
    kern = functools.partial(_scan_kernel, chunk=chunk)
    y, h_f = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, block_di), lambda b, i, k: (b, k, i)),
            pl.BlockSpec((1, chunk, block_di), lambda b, i, k: (b, k, i)),
            pl.BlockSpec((1, chunk, n), lambda b, i, k: (b, k, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, i, k: (b, k, 0)),
            pl.BlockSpec((n, block_di), lambda b, i, k: (0, i)),
            pl.BlockSpec((1, n, block_di), lambda b, i, k: (b, 0, i)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, block_di), lambda b, i, k: (b, k, i)),
            pl.BlockSpec((1, n, block_di), lambda b, i, k: (b, 0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, S, di), _F32),
            jax.ShapeDtypeStruct((B, n, di), _F32),
        ),
        scratch_shapes=[pltpu.VMEM((n, block_di), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "selective_scan_pallas"},
    )(dt, dtx, Bm, Cm, A_t, h0_t)
    return y, h_f
