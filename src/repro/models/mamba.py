"""Mamba-1 selective SSM block (falcon-mamba / Jamba mamba layers).

TPU adaptation notes:
  - The depthwise causal conv (k=4) is expressed as a sum of shifted slices
    (4 adds) instead of a grouped convolution — elementwise in d_inner, so it
    shards cleanly over the model axis.
  - The selective scan: on a TPU (the DoRA kernel dispatch's compiled or
    interpret tier) the Pallas kernel pair of ``kernels/selective_scan.py``,
    forward and backward, which keeps states in VMEM and saves only the
    chunk-start states for the backward. Elsewhere ``_ssm_scan_fused``: a
    ``jax.lax.scan`` over chunks of ``ssm_unroll`` tokens whose body runs
    the recurrence unrolled. ``ssm_impl="assoc"`` keeps the
    associative-scan formulation as a baseline.
  - Jamba's mixer (``ssm_inner_norm``) RMS-normalizes dt, B and C after
    ``x_proj``, each with a learned scale, before ``dt_proj``.
  - Training over a long sequence runs the mixer between its projections
    in checkpointed blocks of ``SEQ_BLOCK`` tokens, carrying the state and
    the conv's inputs across blocks, so the backward holds one block's
    float32 intermediates at a time.
  - Everything between in_proj and out_proj is elementwise (or contracts only
    dt_rank/state dims), so d_inner is the natural TP axis: in_proj
    row-sharded, out_proj col-sharded (psum), scan state sharded on di.

Decode carries {"h": [B, di, n], "conv": [B, k-1, di]} per layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import DoRAConfig
from repro.core.dispatch import plan_scan
from repro.kernels.selective_scan import selective_scan
from repro.models import layers as L
from repro.models.config import ModelConfig

_F32 = jnp.float32
# Training over more than SEQ_BLOCK tokens runs the mixer's core (conv to
# gated scan output) in checkpointed blocks of this many tokens
# (``_core_in_blocks``).
SEQ_BLOCK = 4096


def _causal_conv(x, w, b, cache=None):
    """Depthwise causal conv over seq via shifted adds.

    x [B, S, di]; w [k, di]; b [di]; cache [B, k-1, di] (decode) or None.
    Returns (y [B, S, di], new_cache [B, k-1, di]).
    """
    k = w.shape[0]
    if cache is None:
        ctx = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    else:
        ctx = jnp.concatenate([cache.astype(x.dtype), x], axis=1)
    S = x.shape[1]
    y = jnp.zeros_like(x, dtype=_F32)
    for j in range(k):
        y = y + w[j].astype(_F32) * ctx[:, j:j + S].astype(_F32)
    y = y + b.astype(_F32)
    new_cache = ctx[:, -(k - 1):] if k > 1 else ctx[:, :0]
    return y.astype(x.dtype), new_cache


def _ssm_scan_fused(dt, dtx, Bm, Cm, A, h0, w: int):
    """Fused chunked selective scan: h_t = exp(dt_t A) ⊙ h_{t-1} +
    (dt_t x_t) ⊗ B_t;  y_t = Σ_n h_t C_t.

    dt, dtx: [B, S, di] fp32; Bm, Cm: [B, S, n] fp32; A: [di, n];
    h0: [B, di, n]. Returns (y [B, S, di], h_final).

    Traffic-optimal XLA formulation: a ``lax.scan`` over S/w chunks whose
    body runs w UNROLLED recurrence steps — one fusion that reads the
    [B, w, di] / [B, w, n] slices once, keeps h and the [B, di, n]
    discretized terms in registers, and writes y once. The full-sequence
    [B, S, di, n] tensors a/b are never materialized (the associative-scan
    formulation materialized them plus O(log chunk) tree levels of the
    same size — ~550x the per-tensor bytes in HBM traffic). This is the
    same schedule the Pallas selective-scan kernel pins on TPU
    (kernels/selective_scan.py); the XLA version keeps the dry-run honest
    on CPU.
    """
    B, S, di = dt.shape
    n = A.shape[1]
    if S == 1:  # decode fast path
        a = jnp.exp(dt[:, 0][..., None] * A)
        b = dtx[:, 0][..., None] * Bm[:, 0][:, None, :]
        h = a * h0 + b
        y = jnp.einsum("bdn,bn->bd", h, Cm[:, 0])
        return y[:, None], h

    nc = -(-S // w)
    pad = nc * w - S
    if pad:
        # dt=0 -> a=1 (h unchanged); dtx=0 -> b=0: pads are no-ops.
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        dtx = jnp.pad(dtx, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))

    def to_chunks(x):
        return jnp.moveaxis(x.reshape(B, nc, w, *x.shape[2:]), 1, 0)

    def body(h, xs):
        dt_c, dtx_c, B_c, C_c = xs            # [B, w, di] / [B, w, n]
        ys = []
        for j in range(w):                     # unrolled: one XLA fusion
            a_j = jnp.exp(dt_c[:, j][..., None] * A)
            b_j = dtx_c[:, j][..., None] * B_c[:, j][:, None, :]
            h = a_j * h + b_j
            ys.append(jnp.einsum("bdn,bn->bd", h, C_c[:, j]))
        return h, jnp.stack(ys, axis=1)        # [B, w, di]

    h_f, yc = jax.lax.scan(
        body, h0, (to_chunks(dt), to_chunks(dtx), to_chunks(Bm),
                   to_chunks(Cm)))
    y = jnp.moveaxis(yc, 0, 1).reshape(B, nc * w, di)
    return y[:, :S], h_f


def _ssm_scan(a, b, C, h0, chunk: int):
    """h_t = a_t ⊙ h_{t-1} + b_t;  y_t = Σ_n h_t[:, :, n] C_t[:, n].

    a, b: [B, S, di, n] fp32; C: [B, S, n] fp32; h0: [B, di, n] fp32.
    Chunked: lax.scan over S/chunk chunks, associative_scan inside.
    Returns (y [B, S, di] fp32, h_final [B, di, n]).

    NOTE: kept as the ``ssm_impl="assoc"`` baseline; the default path is
    ``_ssm_scan_fused`` (this formulation's associative-scan tree
    materializes several [B, S, di, n] levels under XLA's lowering).
    """
    B, S, di, n = a.shape
    if S == 1:  # decode fast path
        h = a[:, 0] * h0 + b[:, 0]
        y = jnp.einsum("bdn,bn->bd", h, C[:, 0])
        return y[:, None], h

    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=1.0)
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    ac = jnp.moveaxis(a.reshape(B, nc, chunk, di, n), 1, 0)
    bc = jnp.moveaxis(b.reshape(B, nc, chunk, di, n), 1, 0)
    Cc = jnp.moveaxis(C.reshape(B, nc, chunk, n), 1, 0)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, bl * ar + br

    def body(h, inp):
        ai, bi, Ci = inp
        # fold the carried state into the first step: b'_0 = a_0 h0 + b_0
        bi = bi.at[:, 0].add(ai[:, 0] * h)
        _, hs = jax.lax.associative_scan(combine, (ai, bi), axis=1)
        y = jnp.einsum("bsdn,bsn->bsd", hs, Ci)
        return hs[:, -1], y

    h_f, ys = jax.lax.scan(body, h0, (ac, bc, Cc))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nc * chunk, di)
    return y[:, :S], h_f


def _mixer_core(xz, p, cache, *, mcfg, dcfg):
    """The mixer between its two projections: xz [B, S, 2di] (in_proj's
    output) → (y [B, S, di] gated, h_final, the conv's last inputs), from
    the state and conv inputs in ``cache`` (None: from zero)."""
    B, S = xz.shape[:2]
    di, n, dtr = mcfg.d_inner, mcfg.ssm_state, mcfg.dt_rank
    xi, z = jnp.split(xz, 2, axis=-1)

    conv_cache = cache["conv"] if cache is not None else None
    xi, new_conv = _causal_conv(xi, jax.lax.stop_gradient(p["conv_w"]),
                                jax.lax.stop_gradient(p["conv_b"]),
                                conv_cache)
    xi = jax.nn.silu(xi)

    sg = jax.lax.stop_gradient
    bcdt = xi @ sg(p["x_proj"]).T                              # [B,S,dtr+2n]
    dt_in, Bm, Cm = jnp.split(bcdt.astype(_F32), [dtr, dtr + n], axis=-1)
    if mcfg.ssm_inner_norm:
        eps = mcfg.norm_eps
        dt_in = L.rms_norm(dt_in, sg(p["dt_norm"]), eps)
        Bm = L.rms_norm(Bm, sg(p["b_norm"]), eps)
        Cm = L.rms_norm(Cm, sg(p["c_norm"]), eps)
    dt = jax.nn.softplus(dt_in @ sg(p["dt_proj"]).astype(_F32).T
                         + sg(p["dt_bias"]).astype(_F32))      # [B,S,di]

    A = -jnp.exp(sg(p["A_log"]).astype(_F32))                  # [di, n]
    h0 = (cache["h"].astype(_F32) if cache is not None
          else jnp.zeros((B, di, n), _F32))
    if mcfg.ssm_impl == "assoc":
        a = jnp.exp(dt[..., None] * A)                         # [B,S,di,n]
        b = (dt * xi.astype(_F32))[..., None] * Bm[:, :, None, :]
        y, h_f = _ssm_scan(a, b, Cm, h0, mcfg.ssm_chunk)
    else:
        dtx = dt * xi.astype(_F32)                             # [B,S,di]
        plan = plan_scan(dcfg, d_inner=di)
        if S > 1 and plan.fused:
            y, h_f = selective_scan(dt, dtx, Bm, Cm, A, h0,
                                    interpret=plan.interpret)
        else:
            y, h_f = _ssm_scan_fused(dt, dtx, Bm, Cm, A, h0,
                                     mcfg.ssm_unroll)
    y = y + sg(p["skip_d"]).astype(_F32) * xi.astype(_F32)
    y = y * jax.nn.silu(z.astype(_F32))
    y = y.astype(xz.dtype)

    return y, h_f, new_conv


def _core_in_blocks(core, xz, p, mcfg):
    """``core`` over the sequence in blocks of SEQ_BLOCK tokens, each
    checkpointed, the state and the conv's last inputs carried from block
    to block: the backward holds one block's float32 [B, SEQ_BLOCK, di]
    intermediates at a time, not the whole sequence's."""
    B = xz.shape[0]
    carry = (jnp.zeros((B, mcfg.d_inner, mcfg.ssm_state), _F32),
             jnp.zeros((B, mcfg.ssm_conv - 1, mcfg.d_inner), xz.dtype))
    block = jax.checkpoint(core)
    ys = []
    for start in range(0, xz.shape[1], SEQ_BLOCK):
        y, h, conv = block(xz[:, start:start + SEQ_BLOCK], p,
                           {"h": carry[0], "conv": carry[1]})
        carry = (h, conv)
        ys.append(y)
    return jnp.concatenate(ys, axis=1), carry[0], carry[1]


def mamba_block(x, p, dora, mcfg: ModelConfig, dcfg: DoRAConfig | None, *,
                cache=None, training: bool = True, constrain=None):
    """x [B, S, D] → (y [B, S, D], new_cache).

    p: {"in_proj": [2di, D], "conv_w": [k, di], "conv_b": [di],
        "x_proj": [dtr+2n, di], "dt_proj": [di, dtr], "dt_bias": [di],
        "A_log": [di, n], "skip_d": [di], "out_proj": [D, di]}, and with
        ``ssm_inner_norm`` the scales "dt_norm": [dtr], "b_norm": [n],
        "c_norm": [n].
    """
    S = x.shape[1]
    dora = dora or {}

    xz = L.maybe_dora(x, p["in_proj"], dora.get("in_proj"), dcfg,
                      training=training)                       # [B,S,2di]
    core = functools.partial(_mixer_core, mcfg=mcfg, dcfg=dcfg)
    if cache is None and training and S > SEQ_BLOCK and S % SEQ_BLOCK == 0:
        y, h_f, new_conv = _core_in_blocks(core, xz, p, mcfg)
    else:
        y, h_f, new_conv = core(xz, p, cache)

    # row-parallel projection: constrain output to SP sharding (H1.4)
    out = L.maybe_dora(y, p["out_proj"], dora.get("out_proj"), dcfg,
                       training=training, constrain=constrain)
    new_cache = None
    if cache is not None:
        new_cache = {"h": h_f.astype(cache["h"].dtype), "conv": new_conv}
    return out, new_cache
