"""Pallas-TPU selective-scan kernels (Mamba-1 recurrence), forward and
backward.

    h_t = exp(dt_t ⊙ A) ⊙ h_{t-1} + (dt_t·x_t) ⊗ B_t
    y_t = Σ_n h_t ⊙ C_t

The TPU adaptation of Mamba's hardware-aware scan: the recurrent state h
lives in VMEM scratch across sequence chunks; the discretized terms
a = exp(dt⊙A) and b = (dt·x)⊗B are computed in-register per token and
never touch HBM. Per-layer HBM traffic = read dt/dtx ([B,S,di]) + B/C
([B,S,n]) once + write y once — the roofline minimum. An XLA lowering of
the same recurrence as an associative scan materializes a and b at
[B, S, di, n] and several tree levels of that size.

Layout: the feature dim di is the 128-lane axis of dt/dtx/y; the SSM
state dim n (=16) sits on sublanes, so h is carried as [n, block_di].
B and C enter transposed, [B, n, S], one [n, chunk] block per grid step
(tokens on lanes); a token's column is picked out with a lane mask and
a lane reduction, which keeps every index in the loop dynamic. Grid =
(B, di_tiles, seq_chunks) with the chunk dim sequential ("arbitrary"):
for a fixed (batch, tile) the chunks iterate consecutively and the VMEM
scratch carries h; ``@pl.when(k == 0)`` reloads h0 at each new tile.

Backward (:func:`selective_scan_bwd_pallas`): the forward also writes
the state at the start of every chunk, [B, S/chunk, n, di]. The backward
grid runs the chunks in reverse; each step recomputes its chunk's states
into VMEM from the saved start state and then carries the state
cotangent back through the chunk:

    g_t  = dh_t + C_t ⊗ dy_t                   (dh_t: from the future)
    d(dtx)_t = Σ_n g_t B_t,   dB_t = Σ_di g_t dtx_t,   dC_t = Σ_di h_t dy_t
    q_t = g_t ⊙ h_{t-1} ⊙ a_t,   d(dt)_t = Σ_n q_t A,   dA += q_t dt_t
    dh_{t-1} = a_t ⊙ g_t

dB and dC sum over d_inner, so each di tile writes its partial sums and
XLA adds the tiles. :func:`selective_scan` ties the pair together as a
``jax.custom_vjp`` in the model's layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.compat.pallas import pl, pltpu, resolve_interpret

_F32 = jnp.float32


def _column(block, lane, j):
    """Column ``j`` of an [n, chunk] block as [n, 1] (mask + lane sum)."""
    return jnp.sum(jnp.where(lane == j, block, 0.0), axis=1, keepdims=True)


def _scan_kernel(dt_ref, dtx_ref, b_ref, c_ref, at_ref, h0_ref,
                 y_ref, hout_ref, *rest, chunk: int):
    hs_ref, h_scr = (rest if len(rest) == 2 else (None,) + rest)
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        h_scr[...] = h0_ref[0]                      # [n, bd]

    if hs_ref is not None:
        hs_ref[0, 0] = h_scr[...]                   # state at chunk start
    at = at_ref[...]                                 # [n, bd]  (= A^T)
    bt, ct = b_ref[0], c_ref[0]                      # [n, chunk]
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def step(j, h):
        dt_j = dt_ref[0, pl.ds(j, 1), :]             # [1, bd]
        b_j = dtx_ref[0, pl.ds(j, 1), :] * _column(bt, lane, j)
        h = jnp.exp(dt_j * at) * h + b_j
        y_ref[0, pl.ds(j, 1), :] = jnp.sum(h * _column(ct, lane, j),
                                           axis=0, keepdims=True)
        return h

    h = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h
    hout_ref[0] = h


def _block_di(di: int, block_di: int) -> int:
    """The largest of ``block_di`` and its halves down to 128 that divides
    ``di``; ``di`` itself when none does (interpret-mode sizes)."""
    b = block_di
    while b >= 128:
        if di % b == 0:
            return b
        b //= 2
    return di


def selective_scan_pallas(dt, dtx, Bm, Cm, A_t, h0_t, *,
                          block_di: int = 512, chunk: int = 128,
                          save_states: bool = False,
                          interpret: bool | None = None):
    """dt, dtx: [B, S, di]; Bm, Cm: [B, S, n]; A_t: [n, di];
    h0_t: [B, n, di] — all fp32, S % chunk == 0. Returns (y [B, S, di],
    h_final [B, n, di]) and, with ``save_states``, the state at the start
    of every chunk [B, S/chunk, n, di] (what the backward kernel needs)."""
    B, S, di = dt.shape
    n = A_t.shape[0]
    bd = _block_di(di, block_di)
    nc = S // chunk
    interpret = resolve_interpret(interpret)
    grid = (B, di // bd, nc)
    row = pl.BlockSpec((1, chunk, bd), lambda b, i, k: (b, k, i))
    col = pl.BlockSpec((1, n, chunk), lambda b, i, k: (b, 0, k))
    state = pl.BlockSpec((1, n, bd), lambda b, i, k: (b, 0, i))
    out_specs = [row, state]
    out_shape = [jax.ShapeDtypeStruct((B, S, di), _F32),
                 jax.ShapeDtypeStruct((B, n, di), _F32)]
    if save_states:
        out_specs.append(pl.BlockSpec((1, 1, n, bd),
                                      lambda b, i, k: (b, k, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((B, nc, n, di), _F32))
    outs = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk),
        grid=grid,
        in_specs=[row, row, col, col,
                  pl.BlockSpec((n, bd), lambda b, i, k: (0, i)), state],
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        scratch_shapes=[pltpu.VMEM((n, bd), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "selective_scan_pallas"},
    )(dt, dtx, jnp.swapaxes(Bm, 1, 2), jnp.swapaxes(Cm, 1, 2), A_t, h0_t)
    return tuple(outs)


def _scan_bwd_kernel(dt_ref, dtx_ref, b_ref, c_ref, at_ref, hs_ref,
                     dy_ref, dhf_ref, ddt_ref, ddtx_ref, db_ref, dc_ref,
                     da_ref, dh0_ref, hist, g_scr, da_scr, *, chunk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        g_scr[...] = dhf_ref[0]                     # d h_final
        da_scr[...] = jnp.zeros_like(da_scr)

    at = at_ref[...]                                 # [n, bd]
    bt, ct = b_ref[0], c_ref[0]                      # [n, chunk]
    lane = jax.lax.broadcasted_iota(jnp.int32, bt.shape, 1)

    def forward(j, h):                               # hist[j] = h_{j-1}
        hist[j] = h
        dt_j = dt_ref[0, pl.ds(j, 1), :]
        b_j = dtx_ref[0, pl.ds(j, 1), :] * _column(bt, lane, j)
        return jnp.exp(dt_j * at) * h + b_j

    jax.lax.fori_loop(0, chunk, forward, hs_ref[0, 0])

    def backward(i, carry):
        g, db, dc = carry
        j = chunk - 1 - i
        dt_j = dt_ref[0, pl.ds(j, 1), :]             # [1, bd]
        u_j = dtx_ref[0, pl.ds(j, 1), :]
        dy_j = dy_ref[0, pl.ds(j, 1), :]
        b_j, c_j = _column(bt, lane, j), _column(ct, lane, j)   # [n, 1]
        h_prev = hist[j]
        a = jnp.exp(dt_j * at)
        h = a * h_prev + u_j * b_j
        dc = jnp.where(lane == j,
                       jnp.sum(h * dy_j, axis=1, keepdims=True), dc)
        g = g + c_j * dy_j
        ddtx_ref[0, pl.ds(j, 1), :] = jnp.sum(g * b_j, axis=0,
                                              keepdims=True)
        db = jnp.where(lane == j,
                       jnp.sum(g * u_j, axis=1, keepdims=True), db)
        q = g * h_prev * a
        ddt_ref[0, pl.ds(j, 1), :] = jnp.sum(q * at, axis=0, keepdims=True)
        da_scr[...] += q * dt_j
        return a * g, db, dc

    zeros = jnp.zeros(bt.shape, _F32)
    g, db, dc = jax.lax.fori_loop(0, chunk, backward,
                                  (g_scr[...], zeros, zeros))
    g_scr[...] = g
    db_ref[0, 0] = db
    dc_ref[0, 0] = dc
    da_ref[0] = da_scr[...]
    dh0_ref[0] = g


def selective_scan_bwd_pallas(dt, dtx, Bm, Cm, A_t, h_starts, dy, dh_f, *,
                              block_di: int = 512, chunk: int = 128,
                              interpret: bool | None = None):
    """Cotangents of :func:`selective_scan_pallas` (layouts as there):
    ``h_starts`` [B, S/chunk, n, di] is its saved chunk-start states,
    ``dy`` [B, S, di] and ``dh_f`` [B, n, di] the cotangents of y and
    h_final. Returns (d dt, d dtx [B, S, di]; dB, dC [B, S, n];
    dA_t [n, di]; dh0_t [B, n, di]), all fp32."""
    B, S, di = dt.shape
    n = A_t.shape[0]
    bd = _block_di(di, block_di)
    nc, nt = S // chunk, di // bd
    interpret = resolve_interpret(interpret)
    rev = lambda k: nc - 1 - k
    row = pl.BlockSpec((1, chunk, bd), lambda b, i, k: (b, rev(k), i))
    col = pl.BlockSpec((1, n, chunk), lambda b, i, k: (b, 0, rev(k)))
    state = pl.BlockSpec((1, n, bd), lambda b, i, k: (b, 0, i))
    part = pl.BlockSpec((1, 1, n, chunk), lambda b, i, k: (b, i, 0, rev(k)))
    ddt, ddtx, db, dc, da, dh0 = pl.pallas_call(
        functools.partial(_scan_bwd_kernel, chunk=chunk),
        grid=(B, nt, nc),
        in_specs=[row, row, col, col,
                  pl.BlockSpec((n, bd), lambda b, i, k: (0, i)),
                  pl.BlockSpec((1, 1, n, bd),
                               lambda b, i, k: (b, rev(k), 0, i)),
                  row, state],
        out_specs=(row, row, part, part, state, state),
        out_shape=(jax.ShapeDtypeStruct((B, S, di), _F32),
                   jax.ShapeDtypeStruct((B, S, di), _F32),
                   jax.ShapeDtypeStruct((B, nt, n, S), _F32),
                   jax.ShapeDtypeStruct((B, nt, n, S), _F32),
                   jax.ShapeDtypeStruct((B, n, di), _F32),
                   jax.ShapeDtypeStruct((B, n, di), _F32)),
        scratch_shapes=[pltpu.VMEM((chunk, n, bd), _F32),
                        pltpu.VMEM((n, bd), _F32),
                        pltpu.VMEM((n, bd), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "selective_scan_bwd_pallas"},
    )(dt, dtx, jnp.swapaxes(Bm, 1, 2), jnp.swapaxes(Cm, 1, 2), A_t,
      h_starts, dy, dh_f)
    dB = jnp.swapaxes(jnp.sum(db, axis=1), 1, 2)
    dC = jnp.swapaxes(jnp.sum(dc, axis=1), 1, 2)
    return ddt, ddtx, dB, dC, jnp.sum(da, axis=0), dh0


@functools.lru_cache(maxsize=None)
def _make_scan(chunk: int, block_di: int, interpret: bool):
    kw = dict(chunk=chunk, block_di=block_di, interpret=interpret)

    @jax.custom_vjp
    def scan(dt, dtx, Bm, Cm, A_t, h0_t):
        y, h_f = selective_scan_pallas(dt, dtx, Bm, Cm, A_t, h0_t, **kw)
        return y, h_f

    def fwd(dt, dtx, Bm, Cm, A_t, h0_t):
        y, h_f, hs = selective_scan_pallas(dt, dtx, Bm, Cm, A_t, h0_t,
                                           save_states=True, **kw)
        return (y, h_f), (dt, dtx, Bm, Cm, A_t, hs)

    def bwd(res, cts):
        dy, dh_f = cts
        return selective_scan_bwd_pallas(*res, dy, dh_f, **kw)

    scan.defvjp(fwd, bwd)
    return scan


def selective_scan(dt, dtx, Bm, Cm, A, h0, *, chunk: int = 128,
                   block_di: int = 512, interpret: bool | None = None):
    """The selective scan in the model's layout, differentiable through
    the kernel pair: dt, dtx [B, S, di], Bm, Cm [B, S, n], A [di, n],
    h0 [B, di, n], all fp32. Returns (y [B, S, di], h_final [B, di, n]).
    S is padded to a multiple of ``chunk`` with no-op tokens (dt = 0 →
    a = 1, dtx = 0 → b = 0)."""
    S = dt.shape[1]
    pad = -S % chunk
    if pad:
        tail = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        dt, dtx, Bm, Cm = tail(dt), tail(dtx), tail(Bm), tail(Cm)
    fn = _make_scan(int(chunk), int(block_di), resolve_interpret(interpret))
    y, h_f = fn(dt, dtx, Bm, Cm, A.T, jnp.swapaxes(h0, 1, 2))
    return y[:, :S], jnp.swapaxes(h_f, 1, 2)
