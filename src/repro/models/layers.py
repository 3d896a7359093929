"""Shared NN layers: RMSNorm, RoPE variants, attention (GQA / qk-norm /
QKV-bias / M-RoPE / partial-rotary), SwiGLU MLP — with first-class DoRA
adaptation of every linear via ``maybe_dora``.

Weight convention follows the paper: [d_out, d_in], y = x @ Wᵀ, so the DoRA
row-norm is over dim 1.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import DoRAConfig
from repro.core.adapter import dora_linear
from repro.core.dispatch import plan_gather
from repro.kernels.paged_gather import (paged_gather, paged_gather_ref,
                                        paged_scatter)

_F32 = jnp.float32


def linear(x, w, bias=None):
    y = x @ w.T
    if bias is not None:
        y = y + bias
    return y


def maybe_dora(x, w, dora: dict | None, cfg: DoRAConfig | None, *,
               bias=None, training: bool = True, constrain=None,
               base_sq_cache=None, tenant_groups=None):
    """Adapted linear if a DoRA adapter is present, frozen linear otherwise.

    Base weights are *always* stop-gradiented here: in this framework the
    base model is frozen and only adapters train (PEFT semantics).
    ``constrain``: sharding for row-parallel outputs (H1.4) — a
    ``ComposeSharding`` plan or a plan-carrying/bare row-constraint
    callable; adapted linears pin the rank-space LoRA intermediate under
    it so the matmul-fused compose keeps firing under SPMD (no y_lora
    materialization — see ``repro.core.sharding``).
    ``base_sq_cache``: precomputed ||W||²_row (paper §2.3 future work —
    implemented here; see H3.2): skips the rank-independent base-norm
    term, the only part of the norm that re-reads W.
    ``tenant_groups``: multi-tenant serving — static (start, size) row
    blocks grouping the batch by adapter, with ``dora`` leaves carrying a
    leading tenant dim (see ``repro.core.dora_linear_grouped``). The base
    weight is shared across tenants, so the unadapted branch ignores it.
    """
    if dora is None:
        y = linear(x, jax.lax.stop_gradient(w), bias)
        return constrain(y) if constrain is not None else y
    return dora_linear(x, w, dora, cfg, bias=bias, training=training,
                       constrain=constrain, base_sq_cache=base_sq_cache,
                       tenant_groups=tenant_groups)


def rms_norm(x, scale, eps: float = 1e-5):
    x32 = x.astype(_F32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(_F32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard / partial / M-RoPE) + sinusoidal.
# ---------------------------------------------------------------------------

def _rope_freqs(dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=_F32) / dim))


def rope_cos_sin(positions, dim: int, theta: float):
    """positions [..., S] int → cos/sin [..., S, dim//2] fp32."""
    freqs = _rope_freqs(dim, theta)
    angles = positions.astype(_F32)[..., None] * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x [..., S, H, hd]; cos/sin [..., S, hd//2] (broadcast over heads).
    Rotates interleaved pairs (x_even, x_odd)."""
    x32 = x.astype(_F32)
    x1 = x32[..., 0::2]
    x2 = x32[..., 1::2]
    c = cos[..., None, :]
    s = sin[..., None, :]
    o1 = x1 * c - x2 * s
    o2 = x2 * c + x1 * s
    out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def apply_rope_partial(x, cos, sin, rotary_dim: int):
    """ChatGLM-style 2D/partial RoPE: rotate only the first ``rotary_dim``
    channels of each head; pass the rest through."""
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    rot = apply_rope(rot, cos, sin)
    return jnp.concatenate([rot, rest], axis=-1)


def mrope_cos_sin(positions3, dim: int, theta: float,
                  sections: tuple[int, int, int]):
    """M-RoPE (Qwen2-VL): three position streams (t, h, w) each rotating a
    section of the head-dim pairs. positions3: [3, B, S].

    For pure-text (and our stub frontends) the three streams coincide and
    M-RoPE degenerates to standard RoPE — but the section plumbing is real.
    """
    assert sum(sections) == dim // 2, (sections, dim)
    cos_t, sin_t = rope_cos_sin(positions3[0], dim, theta)
    cos_h, sin_h = rope_cos_sin(positions3[1], dim, theta)
    cos_w, sin_w = rope_cos_sin(positions3[2], dim, theta)
    s0, s1, _ = sections
    pick = lambda t, h, w: jnp.concatenate(
        [t[..., :s0], h[..., s0:s0 + s1], w[..., s0 + s1:]], axis=-1)
    return pick(cos_t, cos_h, cos_w), pick(sin_t, sin_h, sin_w)


def sinusoidal_embedding(positions, dim: int):
    """MusicGen-style fixed sinusoidal embeddings added to the input."""
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=_F32) / half)
    angles = positions.astype(_F32)[..., None] * freqs
    return jnp.concatenate([jnp.cos(angles), jnp.sin(angles)], axis=-1)


# ---------------------------------------------------------------------------
# Attention (GQA) with optional qk-norm, bias, rope variants and KV cache.
# ---------------------------------------------------------------------------

def _causal_mask_bias(q_len: int, kv_len: int, offset, dtype):
    """Causal mask as an additive fp32 bias; ``offset`` = absolute position
    of the first query row (0 for training, cache length for decode) — a
    scalar, or a ``[B]`` vector of per-row cache lengths (continuous
    batching: every slot stands at its own position, so every row gets its
    own causal frontier). Returns ``[q_len, kv_len]`` for a scalar offset,
    ``[B, q_len, kv_len]`` for a vector one."""
    offset = jnp.asarray(offset)
    q_pos = offset[..., None] + jnp.arange(q_len)      # [q] or [B, q]
    k_pos = jnp.arange(kv_len)
    ok = k_pos <= q_pos[..., None]                     # [..., q, kv]
    return jnp.where(ok, 0.0, -1e30).astype(_F32)


# Self-attention over more than LONG_SEQ positions (training) runs in
# query blocks of Q_BLOCK rows (see ``attention_core``).
LONG_SEQ = 4096
Q_BLOCK = 512


def attention_core(q, k, v, *, offset=0, chunk: int | None = None):
    """Grouped-query attention core. q: [B,S,Hq,hd]; k/v: [B,T,Hkv,hd].
    KV heads are never materialized repeated: queries are reshaped to
    [B,S,Hkv,group,hd] and contracted against the shared KV head.

    ``chunk``: online-softmax over KV chunks (memory-efficient attention)
    for long sequences — the S×T score matrix is never materialized whole.
    """
    b, s, hq, hd = q.shape
    _, t, hkv, _ = k.shape
    group = hq // hkv
    # Mixed-precision attention (H3.2 cell 3): tensors stay in the input
    # dtype (bf16); every contraction accumulates in fp32
    # (preferred_element_type) and the softmax statistics are fp32 — the
    # flash-attention precision discipline. Materializing K/V/probs in
    # fp32 doubled the dominant HBM + all-to-all traffic of long-seq
    # cells.
    qg = q.reshape(b, s, hkv, group, hd)
    scale = 1.0 / math.sqrt(hd)

    if chunk is None or t <= chunk:
        scores = jnp.einsum("bskgh,btkh->bkgst", qg, k,
                            preferred_element_type=_F32) * scale
        bias = _causal_mask_bias(s, t, offset, _F32)
        # scalar offset: one [s, t] mask for every row; per-row offsets
        # ([B]): a [B, s, t] mask broadcast over the (kv-head, group) dims.
        scores = scores + (bias[None, None, None] if bias.ndim == 2
                           else bias[:, None, None])
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgst,btkh->bskgh", probs.astype(q.dtype), v,
                         preferred_element_type=_F32)
        return out.reshape(b, s, hq, hd).astype(q.dtype)

    if isinstance(offset, int) and offset == 0 and s == t \
            and s > LONG_SEQ and s % Q_BLOCK == 0:
        # Long self-attention (training): query blocks of Q_BLOCK rows,
        # each over the keys up to its own end (fully masked keys are
        # skipped) in the online-softmax chunks below, and recomputed in
        # the backward. A block's softmax carries are [Q_BLOCK, hd] per
        # key chunk, where one pass over all rows would keep [S, hd] per
        # key chunk for the whole backward.
        outs = []
        for start in range(0, s, Q_BLOCK):
            end = start + Q_BLOCK
            block = jax.checkpoint(functools.partial(
                attention_core, offset=start, chunk=chunk))
            outs.append(block(q[:, start:end], k[:, :end], v[:, :end]))
        return jnp.concatenate(outs, axis=1)

    # Online softmax over KV chunks (flash-style, lax.scan over chunks).
    if jnp.ndim(offset) != 0:
        raise NotImplementedError(
            "per-row cache offsets are only supported on the dense "
            "attention path (decode s==1 and short prefills); the chunked "
            "online-softmax scan assumes one causal frontier per batch")
    nchunks = -(-t // chunk)
    pad = nchunks * chunk - t
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = k.reshape(b, nchunks, chunk, hkv, hd)
    vc = v.reshape(b, nchunks, chunk, hkv, hd)
    q_pos = offset + jnp.arange(s)

    def body(carry, inp):
        m_prev, l_prev, acc = carry
        kci, vci, ci = inp
        k_pos = ci * chunk + jnp.arange(chunk)
        valid = (k_pos[None, :] <= q_pos[:, None]) & (k_pos < t)[None, :]
        bias = jnp.where(valid, 0.0, -1e30).astype(_F32)
        sc = jnp.einsum("bskgh,btkh->bkgst", qg, kci,
                        preferred_element_type=_F32) * scale
        sc = sc + bias[None, None, None]
        m_new = jnp.maximum(m_prev, sc.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new[..., None])
        l_new = l_prev * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkh->bkgsh", p.astype(q.dtype), vci,
            preferred_element_type=_F32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, group, s), -jnp.inf, _F32)
    l0 = jnp.zeros((b, hkv, group, s), _F32)
    acc0 = jnp.zeros((b, hkv, group, s, hd), _F32)
    # Flash-style backward (H3.3): remat the chunk body so the backward
    # recomputes scores/probs per chunk from q/k instead of stacking the
    # [nchunks, ..., s, chunk] probs as scan residuals — the probs stack
    # was the single largest HBM item of long-sequence training cells.
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, acc0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0),
         jnp.arange(nchunks)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(b, s, hq, hd)
    return out.astype(q.dtype)


def attention(x, params, dora, mcfg, dcfg: DoRAConfig | None, *,
              positions, cache=None, training=True, constrain=None,
              tenant_groups=None):
    """Full attention block: QKV (DoRA-adapted), rope, core, O-proj.

    Returns (out, new_cache). ``cache`` = {"k","v","len"} for decode; when
    provided, new K/V rows are written at position ``len`` and attention
    runs over the cache prefix. ``tenant_groups``: multi-tenant serving —
    forwarded to every adapted projection (the attention core itself is
    row-local and adapter-free).
    """
    b, s, _ = x.shape
    hq, hkv, hd = mcfg.num_heads, mcfg.num_kv_heads, mcfg.head_dim

    def proj(name, d_out):
        w = params[name]
        bias = params.get(name + "_bias")
        return maybe_dora(x, w, (dora or {}).get(name), dcfg,
                          bias=bias, training=training,
                          tenant_groups=tenant_groups)

    q = proj("wq", hq * hd).reshape(b, s, hq, hd)
    k = proj("wk", hkv * hd).reshape(b, s, hkv, hd)
    v = proj("wv", hkv * hd).reshape(b, s, hkv, hd)

    if mcfg.qk_norm:
        q = rms_norm(q, params["q_norm"], mcfg.norm_eps)
        k = rms_norm(k, params["k_norm"], mcfg.norm_eps)

    if mcfg.pos_mode == "rope":
        cos, sin = rope_cos_sin(positions, hd, mcfg.rope_theta)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    elif mcfg.pos_mode == "rope_partial":
        rd = mcfg.rotary_dim
        cos, sin = rope_cos_sin(positions, rd, mcfg.rope_theta)
        q = apply_rope_partial(q, cos, sin, rd)
        k = apply_rope_partial(k, cos, sin, rd)
    elif mcfg.pos_mode == "mrope":
        pos3 = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        cos, sin = mrope_cos_sin(pos3, hd, mcfg.rope_theta,
                                 mcfg.mrope_sections)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    # "sinusoidal": absolute embeddings added at the input; nothing here.

    # NOTE (H3.4, refuted): pinning q/k/v to head-parallel sharding here
    # was measured to INCREASE collective time — with kv_heads < tp the
    # replicated K/V gradients partial-sum over the model axis and the
    # gathered-sequence backward adds a second reshard. GSPMD's own choice
    # (a2a on score tiles) is cheaper.

    if cache is None:
        out = attention_core(q, k, v, offset=0, chunk=mcfg.attn_chunk)
        new_cache = None
    else:
        pos = jnp.asarray(cache["len"])
        pages = cache.get("pages")
        if pages is not None and pos.ndim != 1:
            raise ValueError("paged K/V requires per-row lengths "
                             "(cache_shapes(..., row_lens=True))")
        if pages is not None:
            # Block-paged cache (launch/engine.py paged=True): gather the
            # per-layer block pools [n_blocks, bs, Hkv, hd] through the
            # per-row block table into the logical [B, max_len, Hkv, hd]
            # view, run the UNCHANGED per-row-frontier path below, and
            # scatter the written view back. Bitwise parity with the
            # rectangular cache is by construction: unallocated blocks
            # read as exact zeros, and every such position sits at/past
            # its row's causal frontier where the -1e30 bias already
            # drives the softmax weight to exactly 0.0. The table is a
            # traced operand — paging never recompiles.
            plan = plan_gather(dcfg, head_elems=hkv * hd)
            gather = (functools.partial(paged_gather,
                                        interpret=plan.interpret)
                      if plan.fused else paged_gather_ref)
            buf_k = gather(cache["k"], pages)
            buf_v = gather(cache["v"], pages)
        else:
            buf_k, buf_v = cache["k"], cache["v"]
        if pos.ndim == 1:
            # Continuous batching (launch/engine.py): "len" is a [B] vector
            # of per-row cache lengths — every slot writes its new K/V at
            # ITS OWN position, so requests at different depths share one
            # fixed-shape decode step.
            def _row_write(buf, new, p):
                zero = jnp.zeros((), p.dtype)
                return jax.lax.dynamic_update_slice(
                    buf, new, (p, zero, zero))

            ck = jax.vmap(_row_write)(
                buf_k, k.astype(buf_k.dtype), pos)
            cv = jax.vmap(_row_write)(
                buf_v, v.astype(buf_v.dtype), pos)
        else:
            zero = jnp.zeros((), pos.dtype)  # match index dtypes (x64-safe)
            ck = jax.lax.dynamic_update_slice(
                buf_k, k.astype(buf_k.dtype),
                (zero, pos, zero, zero))
            cv = jax.lax.dynamic_update_slice(
                buf_v, v.astype(buf_v.dtype),
                (zero, pos, zero, zero))
        # mask out unwritten cache rows via the causal offset: rows beyond
        # pos+s have k_pos > q_pos and are excluded by causality. Decode
        # (s == 1) always takes the dense-over-cache path: its score matrix
        # is [B, 1, Hq, T] — small — and chunking would only add scan steps.
        # Per-row offsets (pos.ndim == 1) also force the dense path: the
        # chunked scan assumes one causal frontier per batch, and every
        # per-row window (decode s==1, speculative verify s==k+1) is short.
        dense = s == 1 or pos.ndim == 1
        out = attention_core(q, ck, cv, offset=pos,
                             chunk=None if dense else mcfg.attn_chunk)
        if pages is not None:
            new_cache = {"k": paged_scatter(cache["k"], pages, ck),
                         "v": paged_scatter(cache["v"], pages, cv),
                         "len": pos + s}
        else:
            new_cache = {"k": ck, "v": cv, "len": pos + s}

    out = out.reshape(b, s, hq * hd)
    wo = params["wo"]
    # row-parallel projection: constrain output to SP sharding (H1.4)
    y = maybe_dora(out, wo, (dora or {}).get("wo"), dcfg,
                   training=training, constrain=constrain,
                   tenant_groups=tenant_groups)
    return y, new_cache


def mlp_swiglu(x, params, dora, dcfg: DoRAConfig | None, *, training=True,
               act=jax.nn.silu, constrain=None, tenant_groups=None):
    d = dora or {}
    gate = maybe_dora(x, params["w_gate"], d.get("w_gate"), dcfg,
                      training=training, tenant_groups=tenant_groups)
    up = maybe_dora(x, params["w_up"], d.get("w_up"), dcfg,
                    training=training, tenant_groups=tenant_groups)
    h = act(gate) * up
    # row-parallel projection: constrain output to SP sharding (H1.4)
    return maybe_dora(h, params["w_down"], d.get("w_down"), dcfg,
                      training=training, constrain=constrain,
                      tenant_groups=tenant_groups)
