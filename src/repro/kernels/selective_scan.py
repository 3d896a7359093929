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
(tokens on lanes). Grid = (B, di_tiles, seq_chunks) with the chunk dim
sequential ("arbitrary"): for a fixed (batch, tile) the chunks iterate
consecutively and the VMEM scratch carries h; ``@pl.when(k == 0)``
reloads h0 at each new tile.

Schedule inside a chunk: only ``h = a ⊙ h + b`` is serial, so everything
else is batched over groups of 8 tokens (one fp32 sublane tile; the
whole chunk where it is shorter). The group loop is a ``fori_loop``;
the tokens of a group are unrolled with static indices. A group loads
dt and dtx as one [8, block_di] tile and stores y as one; it rotates the
[n, chunk] B and C blocks by its start once, so that each token's [n, 1]
column is a static lane slice that broadcasts across the lanes; and it
computes its 8 ``a_t = exp(dt_t ⊙ Aᵀ)`` and ``b_t`` ahead of its chain,
so their EUP and XLU latencies overlap the dependent updates. Each
token's ``y_t`` is a sublane sum, gathered into the group's tile.

Tile width: ``block_di`` 1024 lanes, so each op of a token's chain
spans 16 independent vregs (n 16): on a v5e at the hybrid cell's shapes
that measured 1.17 ms a forward and 3.17 ms a backward call, against
1.48 and 5.14 ms at 512 and 2.65 and 9.31 ms at 256. The backward's
``hist`` is then (chunk+1)·n·1024·4 B = 8.4 MiB at n 16 and chunk 128,
inside the 16 MiB of scoped VMEM with the double-buffered blocks.

Why: the first form of this kernel walked the chunk one dynamic token at
a time, with two one-row loads, a select over the whole [n, chunk] block
plus a lane sum for each of B's and C's columns, a sublane sum and a
one-row store per token, none of it overlapped. On a v5e, in the hybrid
fine-tuning cell (d_inner 5120, n 16, 4096-token calls), that was 4.22
ms a forward call and 11.35 ms a backward one, about 103 ns (~150
cycles) a token and 512-lane tile for ~8 vregs of state: 43% of the
train step, at 7.6% and 4.7% of the kernels' HBM rooflines.

Backward (:func:`selective_scan_bwd_pallas`): the forward also writes
the state at the start of every chunk, [B, S/chunk, n, di]. The backward
grid runs the chunks in reverse; each step recomputes its chunk's states
into VMEM from the saved start state and then carries the state
cotangent back through the chunk:

    g_t  = dh_t + C_t ⊗ dy_t                   (dh_t: from the future)
    d(dtx)_t = Σ_n g_t B_t,   dB_t = Σ_di g_t dtx_t,   dC_t = Σ_di h_t dy_t
    q_t = g_t ⊙ h_{t-1} ⊙ a_t,   d(dt)_t = Σ_n q_t A,   dA += q_t dt_t
    dh_{t-1} = a_t ⊙ g_t

The backward walks the chunk's groups twice: forward to refill the
chunk's states into VMEM (``hist``, [chunk+1, n, block_di], so h_{t-1}
and h_t are both loads), then in reverse, carrying g and the dA
accumulator in registers across the whole chunk (dA reaches VMEM once a
chunk). d(dt), d(dtx) leave as [8, block_di] tiles. dB and dC are lane
sums, one [n] vector a token; a group stacks its 8 as an [8, n] tile
with tokens on rows, and the chunk's [chunk, n] is transposed to the
output's [n, chunk] once. dB and dC sum over d_inner, so each di tile
writes its partial sums and XLA adds the tiles. :func:`selective_scan`
ties the pair together as a ``jax.custom_vjp`` in the model's layout.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.compat.pallas import pl, pltpu, resolve_interpret

_F32 = jnp.float32
_ROWS = 8            # sublanes of one fp32 vreg tile


def _group(chunk: int) -> int:
    """Tokens per group: gcd(chunk, 8), so one sublane tile of fp32 rows
    wherever 8 divides the chunk."""
    return math.gcd(chunk, _ROWS)


def _columns(block_ref, s, grp: int):
    """Tokens ``s .. s+grp-1`` of an [n, chunk] Bᵀ/Cᵀ block as ``grp``
    [n, 1] columns: one lane rotation brings the group to lanes 0..grp-1,
    then each column is a static lane slice."""
    chunk = block_ref.shape[2]
    blk = pltpu.roll(block_ref[0], (chunk - s) % chunk, 1)
    return [blk[:, t:t + 1] for t in range(grp)]


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
    grp = _group(chunk)

    def group(i, h):
        s = pl.multiple_of(i * grp, grp)
        dt = dt_ref[0, pl.ds(s, grp), :]             # [grp, bd]
        dtx = dtx_ref[0, pl.ds(s, grp), :]
        bc, cc = _columns(b_ref, s, grp), _columns(c_ref, s, grp)
        a = [jnp.exp(dt[t:t + 1] * at) for t in range(grp)]
        u = [dtx[t:t + 1] * bc[t] for t in range(grp)]
        ys = []
        for t in range(grp):                         # the serial chain
            h = a[t] * h + u[t]
            ys.append(jnp.sum(h * cc[t], axis=0, keepdims=True))
        y_ref[0, pl.ds(s, grp), :] = jnp.concatenate(ys, axis=0)
        return h

    h = jax.lax.fori_loop(0, chunk // grp, group, h_scr[...])
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
                          block_di: int = 1024, chunk: int = 128,
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
                     da_ref, dh0_ref, hist, g_scr, da_scr, db_scr, dc_scr,
                     *, chunk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        g_scr[...] = dhf_ref[0]                     # d h_final
        da_scr[...] = jnp.zeros_like(da_scr)

    at = at_ref[...]                                 # [n, bd]
    grp = _group(chunk)
    ng = chunk // grp

    def forward(i, h):                               # hist[j] = h_{j-1}
        s = pl.multiple_of(i * grp, grp)
        dt = dt_ref[0, pl.ds(s, grp), :]
        dtx = dtx_ref[0, pl.ds(s, grp), :]
        bc = _columns(b_ref, s, grp)
        a = [jnp.exp(dt[t:t + 1] * at) for t in range(grp)]
        u = [dtx[t:t + 1] * bc[t] for t in range(grp)]
        for t in range(grp):
            hist[s + t] = h
            h = a[t] * h + u[t]
        return h

    hist[chunk] = jax.lax.fori_loop(0, ng, forward, hs_ref[0, 0])

    def backward(i, carry):
        g, da = carry
        s = pl.multiple_of((ng - 1 - i) * grp, grp)
        rows = pl.ds(s, grp)
        dt, u = dt_ref[0, rows, :], dtx_ref[0, rows, :]
        dy = dy_ref[0, rows, :]
        bc, cc = _columns(b_ref, s, grp), _columns(c_ref, s, grp)
        a = [jnp.exp(dt[t:t + 1] * at) for t in range(grp)]
        ddt, ddtx, db, dc = ([None] * grp for _ in range(4))
        for t in reversed(range(grp)):               # the serial chain
            h_prev, h = hist[s + t], hist[s + t + 1]
            dc[t] = jnp.sum(h * dy[t:t + 1], axis=1)
            g = g + cc[t] * dy[t:t + 1]
            ddtx[t] = jnp.sum(g * bc[t], axis=0, keepdims=True)
            db[t] = jnp.sum(g * u[t:t + 1], axis=1)
            q = g * h_prev * a[t]
            ddt[t] = jnp.sum(q * at, axis=0, keepdims=True)
            da = da + q * dt[t:t + 1]
            g = a[t] * g
        ddt_ref[0, rows, :] = jnp.concatenate(ddt, axis=0)
        ddtx_ref[0, rows, :] = jnp.concatenate(ddtx, axis=0)
        db_scr[rows, :] = jnp.stack(db)          # [grp, n]: tokens on rows
        dc_scr[rows, :] = jnp.stack(dc)
        return g, da

    g, da = jax.lax.fori_loop(0, ng, backward, (g_scr[...], da_scr[...]))
    g_scr[...] = g
    da_scr[...] = da
    db_ref[0, 0] = db_scr[...].T
    dc_ref[0, 0] = dc_scr[...].T
    da_ref[0] = da
    dh0_ref[0] = g


def selective_scan_bwd_pallas(dt, dtx, Bm, Cm, A_t, h_starts, dy, dh_f, *,
                              block_di: int = 1024, chunk: int = 128,
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
        scratch_shapes=[pltpu.VMEM((chunk + 1, n, bd), _F32),
                        pltpu.VMEM((n, bd), _F32),
                        pltpu.VMEM((n, bd), _F32),
                        pltpu.VMEM((chunk, n), _F32),
                        pltpu.VMEM((chunk, n), _F32)],
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
                   block_di: int = 1024, interpret: bool | None = None):
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
