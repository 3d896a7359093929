"""Plain float32 reference of the configurations' model, loss and optimizer.

A dense GQA transformer with RMSNorm, rotary positions on the first
``rotary_dim`` channels of each head (interleaved pairs), SwiGLU, and DoRA on
all seven projections, written straight from the DoRA paper's definition:

    y = g ⊙ (x Wᵀ + s (x Aᵀ) Bᵀ) + b,   g = m / ||W + s B A||_row

with the row norm detached (DoRA §4.3), and AdamW with global-norm clipping
and a linear-warmup cosine schedule. Everything is computed in float32 with
``jax.default_matmul_precision("highest")``; the weights come from
:func:`model.make_weights`, made again from the seed. Nothing here imports
the program under test.

``quant="fp8"`` is the control: every matmul operand is rounded to float8
(e4m3, one scale per row) before the float32 product, the next precision
below the bf16 the configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from model import Dims

F32 = jnp.float32
FP8_MAX = 448.0       # largest finite float8_e4m3fn


def q8(x, axis: int = -1):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the slice's largest magnitude maps to 448), back in float32. The
    rounding passes no gradient of its own (straight-through)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant):
    """a [..., k] @ b[n, k]ᵀ in float32, operands rounded under ``quant``."""
    if quant == "fp8":
        a, b = q8(a, -1), q8(b, -1)
    return jnp.einsum("...k,nk->...n", a, b)


def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(F32)


def rope(x, positions, rotary_dim: int, theta: float):
    """Rotate interleaved channel pairs (2i, 2i+1) of the first
    ``rotary_dim`` channels of each head by position · theta^(-2i/dim)."""
    freqs = 1.0 / (theta ** (jnp.arange(0, rotary_dim, 2, dtype=F32)
                             / rotary_dim))
    ang = positions.astype(F32)[:, None] * freqs           # [S, dim/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    rot, rest = x[..., :rotary_dim], x[..., rotary_dim:]
    x1, x2 = rot[..., 0::2], rot[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([out.reshape(rot.shape), rest], axis=-1)


def dora(x, W, ad, s: float, bias, quant):
    """One adapted projection: g ⊙ (x Wᵀ + s (x Aᵀ) Bᵀ) + b."""
    W, A, B = (t.astype(F32) for t in (W, ad["A"], ad["B"]))
    norm = jnp.sqrt(jnp.sum(jnp.square(W + s * (B @ A)), axis=1))
    g = ad["m"].astype(F32) / jax.lax.stop_gradient(norm)
    y = g * (_mm(x, W, quant) + s * _mm(_mm(x, A, quant), B, quant))
    return y if bias is None else y + bias.astype(F32)


def attention(q, k, v, quant, block: int = 512):
    """Causal GQA over one sequence: q [S, H, hd], k/v [S, KV, hd]. Query
    blocks of ``block`` rows, each recomputed in the backward pass."""
    S, H, hd = q.shape
    group = H // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    if quant == "fp8":
        q, k, v = q8(q), q8(k), q8(v)
    nb = -(-S // block)
    qp = jnp.pad(q, ((0, nb * block - S), (0, 0), (0, 0)))

    @jax.checkpoint
    def one(args):
        qb, start = args
        sc = jnp.einsum("qhd,khd->hqk", qb, k) / math.sqrt(hd)
        rows = start + jnp.arange(block)[:, None]
        sc = jnp.where(jnp.arange(S)[None, :] <= rows, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if quant == "fp8":
            p = q8(p)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = jax.lax.map(one, (qp.reshape(nb, block, H, hd),
                            jnp.arange(nb) * block))
    return out.reshape(nb * block, H, hd)[:S]


def hidden(d: Dims, params, adapters, tokens, quant=None):
    """Final-norm hidden states [S, D] of one sequence ``tokens`` [S]."""
    s = d.scaling
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = params["embed"][tokens].astype(F32)
    p_stack = params["stack"]["l0"]
    a_stack = adapters["stack"]["l0"]

    @jax.checkpoint
    def layer(x, pa):
        p, a = pa
        mx, ma = p["mixer"], a["mixer"]
        h = rms_norm(x, p["ln1"]["scale"], d.norm_eps)
        q = dora(h, mx["wq"], ma["wq"], s, mx.get("wq_bias"), quant)
        k = dora(h, mx["wk"], ma["wk"], s, mx.get("wk_bias"), quant)
        v = dora(h, mx["wv"], ma["wv"], s, mx.get("wv_bias"), quant)
        q = rope(q.reshape(S, d.heads, d.head_dim), pos, d.rotary_dim,
                 d.rope_theta)
        k = rope(k.reshape(S, d.kv_heads, d.head_dim), pos, d.rotary_dim,
                 d.rope_theta)
        v = v.reshape(S, d.kv_heads, d.head_dim)
        o = attention(q, k, v, quant).reshape(S, d.heads * d.head_dim)
        x = x + dora(o, mx["wo"], ma["wo"], s, None, quant)
        f, fa = p["ffn"], a["ffn"]
        h = rms_norm(x, p["ln2"]["scale"], d.norm_eps)
        gate = dora(h, f["w_gate"], fa["w_gate"], s, None, quant)
        up = dora(h, f["w_up"], fa["w_up"], s, None, quant)
        x = x + dora(jax.nn.silu(gate) * up, f["w_down"], fa["w_down"], s,
                     None, quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, (p_stack, a_stack))
    return rms_norm(x, params["final_norm"]["scale"], d.norm_eps)


def head_stats(params, x, targets, quant=None, block: int = 8192):
    """The head over the vocabulary in blocks of ``block`` rows, so neither
    a float32 copy of the head nor the [T, V] logits is ever whole. For
    each row of ``x`` [T, D]: (logsumexp of its logits, the logit of its
    ``targets`` entry, its largest logit, the index of that logit)."""
    head = params["head"]
    V = head.shape[0]
    block = min(block, V)
    T = x.shape[0]

    @jax.checkpoint
    def one(carry, i):
        m, s, gold, best, arg = carry
        start = jnp.minimum(i * block, V - block)    # last block overlaps
        w = jax.lax.dynamic_slice_in_dim(head, start, block, 0)
        lg = _mm(x, w.astype(F32), quant)                  # [T, block]
        cols = start + jnp.arange(block)
        lg_new = jnp.where(cols >= i * block, lg, -jnp.inf)
        bm = lg_new.max(axis=-1)
        new_m = jnp.maximum(m, bm)
        s = s * jnp.exp(m - new_m) + jnp.sum(
            jnp.exp(lg_new - new_m[:, None]), axis=-1)
        hit = (targets >= start) & (targets < start + block)
        at = jnp.take_along_axis(
            lg, jnp.clip(targets - start, 0, block - 1)[:, None], axis=-1)
        gold = jnp.where(hit, at[:, 0], gold)
        pick = (start + jnp.argmax(lg_new, axis=-1)).astype(jnp.int32)
        arg = jnp.where(bm > best, pick, arg)
        return (new_m, s, gold, jnp.maximum(best, bm), arg), None

    neg = jnp.full((T,), -jnp.inf, F32)
    init = (neg, jnp.zeros((T,), F32), jnp.zeros((T,), F32), neg,
            jnp.zeros((T,), jnp.int32))
    (m, s, gold, best, arg), _ = jax.lax.scan(one, init,
                                              jnp.arange(-(-V // block)))
    return m + jnp.log(s), gold, best, arg


# ---------------------------------------------------------------------------
# Serving: the logits of each position of a prompt and its served tokens.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 5))
def _gaps(d, params, adapters, tokens, targets, quant):
    """Per position of ``tokens`` [S]: how far the reference's logit of
    ``targets`` lies below its best, and (under ``quant``) how far the
    reference's logit of the token ``quant``'s forward puts first lies below
    the reference's best."""
    with jax.default_matmul_precision("highest"):
        x = hidden(d, params, adapters, tokens)
        _, at, best, _ = head_stats(params, x, targets)
        if quant is None:
            return best - at, jnp.zeros_like(best)
        xl = hidden(d, params, adapters, tokens, quant)
        _, _, _, pick = head_stats(params, xl, targets, quant)
        _, low_at, _, _ = head_stats(params, x, pick)
        return best - at, best - low_at


def served_gaps(d: Dims, params, adapters, prompt, served, *, pad_to: int,
                quant=None):
    """For a prompt and the tokens served after it, at every position that
    produced a served token: the gap of the served token below the
    reference's best logit, and (under ``quant``) the gap of the token the
    lower-precision forward would have put first. numpy [n] each (the
    second None without ``quant``). Sequences are padded to ``pad_to`` so
    one program serves every request."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n, P = len(served), len(prompt)
    toks = np.zeros((pad_to,), np.int32)
    toks[:P + n - 1] = np.concatenate([prompt, served[:-1]])
    targets = np.zeros((pad_to,), np.int32)
    targets[P - 1:P - 1 + n] = served
    g_served, g_low = (np.asarray(t)[P - 1:P - 1 + n] for t in
                       _gaps(d, params, adapters, toks, targets, quant))
    return g_served, (g_low if quant is not None else None)


# ---------------------------------------------------------------------------
# Fine-tuning: loss, gradients and AdamW, as the traffic file states them.
# ---------------------------------------------------------------------------

def loss_fn(d: Dims, adapters, params, tokens, labels, loss_tokens: int,
            quant=None, stride: int = 1):
    """Mean next-token NLL over the last ``loss_tokens`` positions of each
    row of ``tokens`` [B, S] (every ``stride``-th of them: 2 is the fault
    of a loss taken over half the tokens)."""
    def row(t, lbl):
        x = hidden(d, params, adapters, t, quant)[-loss_tokens:][::stride]
        lse, gold, _, _ = head_stats(params, x, lbl[-loss_tokens:][::stride],
                                     quant)
        return jnp.mean(lse - gold)
    return jnp.mean(jax.vmap(row)(tokens, labels))


def lr_at(opt: dict, count):
    """Linear warmup to ``lr`` over ``warmup_steps``, then cosine decay to
    ``min_lr_ratio`` · lr at ``total_steps``."""
    count = count.astype(F32)
    warm = jnp.minimum(count / max(opt["warmup_steps"], 1), 1.0)
    span = max(opt["total_steps"] - opt["warmup_steps"], 1)
    prog = jnp.clip((count - opt["warmup_steps"]) / span, 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(math.pi * prog))
    return opt["lr"] * warm * (opt["min_lr_ratio"]
                               + (1.0 - opt["min_lr_ratio"]) * cos)


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8, 9),
                   donate_argnums=(1, 2))
def train_step(d, adapters, moments, params, tokens, labels, loss_tokens,
               opt_items, quant, stride=1):
    """One AdamW step of the float32 adapters. Returns (adapters',
    moments', loss, the clipped gradient as the optimizer takes it)."""
    opt = dict(opt_items)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn, argnums=1)(
            d, adapters, params, tokens, labels, loss_tokens, quant, stride)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-12))
    grads = jax.tree.map(lambda g: g * clip, grads)
    count = moments["count"] + 1
    b1, b2 = opt["beta1"], opt["beta2"]
    c1 = 1.0 - b1 ** count.astype(F32)
    c2 = 1.0 - b2 ** count.astype(F32)
    lr = lr_at(opt, count)

    def upd(path, p, g, mu, nu):
        mu = b1 * mu + (1 - b1) * g
        nu = b2 * nu + (1 - b2) * g * g
        step = (mu / c1) / (jnp.sqrt(nu / c2) + opt["eps"])
        if jax.tree_util.keystr(path[-1:]) != "['m']":
            step = step + opt["weight_decay"] * p
        return p - lr * step, mu, nu

    out = jax.tree_util.tree_map_with_path(upd, adapters, grads,
                                           moments["mu"], moments["nu"])
    is_t = lambda t: isinstance(t, tuple)
    new = jax.tree.map(lambda t: t[0], out, is_leaf=is_t)
    mu = jax.tree.map(lambda t: t[1], out, is_leaf=is_t)
    nu = jax.tree.map(lambda t: t[2], out, is_leaf=is_t)
    return new, {"mu": mu, "nu": nu, "count": count}, loss, grads


def start_training(adapters):
    """Float32 copy of the adapters and zero moments."""
    a32 = jax.tree.map(lambda t: jnp.array(t, F32, copy=True), adapters)
    zeros = lambda t: jnp.zeros(t.shape, F32)
    return a32, {"mu": jax.tree.map(zeros, a32),
                 "nu": jax.tree.map(zeros, a32),
                 "count": jnp.zeros((), jnp.int32)}
