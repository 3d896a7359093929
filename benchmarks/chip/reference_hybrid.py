"""Plain float32 reference of a Mamba-1 + attention hybrid (Jamba), its loss
and its optimizer.

Layer ``i`` is causal multi-query attention with no positional encoding
where ``i % attn_layer_period == attn_layer_offset``, and otherwise
Jamba's Mamba-1 mixer (HF ``JambaMambaMixer``):

    x, z   = split(in_proj(h))
    x      = silu(causal depthwise conv(x) + conv bias)
    dt, B, C = split(x_proj(x));  dt, B, C = RMSNorm(dt), RMSNorm(B),
                                             RMSNorm(C)   (learned scales)
    dt     = softplus(dt_proj(dt) + dt_bias),  A = -exp(A_log)
    h_t    = exp(dt_t A) ⊙ h_{t-1} + (dt_t x_t) ⊗ B_t,   y_t = h_t C_t
    out    = out_proj((y + D ⊙ x) ⊙ silu(z))

with the state run token by token from zero, every layer followed by a
dense SwiGLU FFN, pre-norm RMSNorm throughout, and DoRA on the adapted
projections as :mod:`reference` writes it. The mixer, the FFN and the
attention's queries run over the sequence in blocks of tokens, each
recomputed in the backward, so that the autodiff of a 16k-token step fits
on one chip beside the weights. Everything is computed in float32 with
``jax.default_matmul_precision("highest")``; the weights come
from :func:`hybrid.make_weights`, made again from the seed. Nothing here
imports the program under test.

``quant="fp8"`` is the control, as in :mod:`reference`: every matmul
operand is rounded to float8 e4m3 before the float32 product.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import reference as R
from hybrid import Dims

F32 = jnp.float32
# Tokens per checkpointed block: the Mamba mixer and the FFN run over the
# sequence in blocks of this many tokens, each recomputed in the backward,
# so that no [S, d_inner] or [S, d_ff] float32 intermediate is ever whole.
TOKEN_BLOCK = 256
UNROLL = 8


def dora_scale(W, ad, s: float):
    """g = m / ||W + s B A||_row, the row norm detached (DoRA §4.3)."""
    W, A, B = (t.astype(F32) for t in (W, ad["A"], ad["B"]))
    norm = jnp.sqrt(jnp.sum(jnp.square(W + s * (B @ A)), axis=1))
    return ad["m"].astype(F32) / jax.lax.stop_gradient(norm)


def dora(x, W, ad, s: float, g, quant):
    """One adapted projection with its scale ``g`` given:
    g ⊙ (x Wᵀ + s (x Aᵀ) Bᵀ), as :func:`reference.dora`."""
    W, A, B = (t.astype(F32) for t in (W, ad["A"], ad["B"]))
    return g * (R._mm(x, W, quant) + s * R._mm(R._mm(x, A, quant), B, quant))


def ssm_scan(h, dt, u, Bm, Cm, A, t, *, reset: int = 0):
    """The recurrence one token at a time from state h [di, n]: dt, u
    [T, di]; Bm, Cm [T, n]; A [di, n]; t [T] the tokens' positions.
    Returns (the last state, y [T, di]). ``reset`` > 0 (traced or not) is
    a planted fault: the state starts again from zero at every
    ``reset``-th position."""
    def token(h, xs):
        dt_t, u_t, b_t, c_t, t_t = xs
        h = jnp.where((reset > 0) & (t_t % jnp.maximum(reset, 1) == 0),
                      0.0, h)
        h = jnp.exp(dt_t[:, None] * A) * h + u_t[:, None] * b_t[None, :]
        return h, jnp.sum(h * c_t[None, :], axis=-1)
    return jax.lax.scan(token, h, (dt, u, Bm, Cm, t), unroll=UNROLL)


def _blocks(x, block: int | None = None):
    """x [S, ...] as [S / block, block, ...] (``block`` defaults to
    TOKEN_BLOCK), the tail padded."""
    block = min(block or TOKEN_BLOCK, x.shape[0])
    pad = -x.shape[0] % block
    x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x.reshape((-1, block) + x.shape[1:])


def mamba(d: Dims, h, p, a, quant, reset: int = 0):
    """Jamba's Mamba-1 mixer over one sequence h [S, D], block by block of
    tokens; the state and the conv's last inputs carry across blocks."""
    s, S = d.scaling, h.shape[0]
    di, n, dtr, k = d.d_inner, d.d_state, d.dt_rank, d.d_conv
    g_in = dora_scale(p["in_proj"], a["in_proj"], s)
    g_out = dora_scale(p["out_proj"], a["out_proj"], s)
    w = p["conv_w"].astype(F32)                              # [k, di]
    A = -jnp.exp(p["A_log"].astype(F32))

    @jax.checkpoint
    def block(carry, xs):
        state, tail = carry
        hb, t = xs
        xz = dora(hb, p["in_proj"], a["in_proj"], s, g_in, quant)
        x, z = xz[:, :di], xz[:, di:]
        ctx = jnp.concatenate([tail, x])
        T = x.shape[0]
        x = sum(w[j] * ctx[j:j + T] for j in range(k)) \
            + p["conv_b"].astype(F32)
        x = jax.nn.silu(x)
        bcdt = R._mm(x, p["x_proj"].astype(F32), quant)      # [T, dtr+2n]
        dt = R.rms_norm(bcdt[:, :dtr], p["dt_norm"], d.norm_eps)
        Bm = R.rms_norm(bcdt[:, dtr:dtr + n], p["b_norm"], d.norm_eps)
        Cm = R.rms_norm(bcdt[:, dtr + n:], p["c_norm"], d.norm_eps)
        dt = jax.nn.softplus(R._mm(dt, p["dt_proj"].astype(F32), quant)
                             + p["dt_bias"].astype(F32))
        state, y = ssm_scan(state, dt, dt * x, Bm, Cm, A, t, reset=reset)
        y = (y + p["skip_d"].astype(F32) * x) * jax.nn.silu(z)
        out = dora(y, p["out_proj"], a["out_proj"], s, g_out, quant)
        return (state, ctx[T:]), out

    start = (jnp.zeros((di, n), F32), jnp.zeros((k - 1, di), F32))
    _, out = jax.lax.scan(block, start, (_blocks(h), _blocks(jnp.arange(S))))
    return out.reshape(-1, h.shape[1])[:S]


def attention(d: Dims, h, p, a, quant, block: int = 512):
    """Causal multi-query attention with no positional encoding over one
    sequence h [S, D]: keys and values for every position, then blocks of
    ``block`` query rows, each from its projection to its output
    projection and recomputed in the backward. Each KV head is shared by
    heads / kv_heads query heads without being repeated; under ``quant``
    q, k, v and the probabilities are rounded as :func:`reference.attention`
    rounds them."""
    s, S = d.scaling, h.shape[0]
    kv, hd = d.kv_heads, d.head_dim
    g = {n: dora_scale(p[n], a[n], s) for n in ("wq", "wk", "wv", "wo")}
    proj = lambda x, n: dora(x, p[n], a[n], s, g[n], quant)
    k = proj(h, "wk").reshape(S, kv, hd)
    v = proj(h, "wv").reshape(S, kv, hd)
    if quant == "fp8":
        k, v = R.q8(k), R.q8(v)
    blocks = _blocks(h, block)
    T = blocks.shape[1]

    @jax.checkpoint
    def one(args):
        hb, start = args
        qb = proj(hb, "wq").reshape(T, kv, d.heads // kv, hd)
        if quant == "fp8":
            qb = R.q8(qb)
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k) / math.sqrt(hd)
        rows = start + jnp.arange(T)[:, None]
        sc = jnp.where(jnp.arange(S)[None, :] <= rows, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        if quant == "fp8":
            pr = R.q8(pr)
        o = jnp.einsum("kgqt,tkd->qkgd", pr, v)
        return proj(o.reshape(T, d.heads * hd), "wo")

    out = jax.lax.map(one, (blocks, jnp.arange(blocks.shape[0]) * T))
    return out.reshape(-1, h.shape[1])[:S]


def ffn(d: Dims, h, p, a, quant):
    """SwiGLU over one sequence h [S, D], block by block of tokens."""
    s = d.scaling
    g = {n: dora_scale(p[n], a[n], s) for n in ("w_gate", "w_up", "w_down")}
    proj = lambda x, n: dora(x, p[n], a[n], s, g[n], quant)

    @jax.checkpoint
    def block(_, hb):
        return None, proj(jax.nn.silu(proj(hb, "w_gate"))
                          * proj(hb, "w_up"), "w_down")

    _, out = jax.lax.scan(block, None, _blocks(h))
    return out.reshape(-1, h.shape[1])[:h.shape[0]]


def hidden(d: Dims, params, adapters, tokens, quant=None, reset: int = 0):
    """Final-norm hidden states [S, D] of one sequence ``tokens`` [S]."""
    x = params["embed"][tokens].astype(F32)

    def layer(kind, j, x, p, a):
        # the weights enter stacked and are sliced here, inside the
        # checkpoint, so that no copy of a slice lives into the backward
        p, a = jax.tree.map(lambda leaf: leaf[j], (p, a))
        h = R.rms_norm(x, p["ln1"]["scale"], d.norm_eps)
        x = x + (attention(d, h, p["mixer"], a["mixer"], quant)
                 if kind == "attn" else
                 mamba(d, h, p["mixer"], a["mixer"], quant, reset))
        h = R.rms_norm(x, p["ln2"]["scale"], d.norm_eps)
        return x + ffn(d, h, p["ffn"], a["ffn"], quant)

    for i in range(d.layers):
        u = f"l{i % d.period}"
        x = jax.checkpoint(functools.partial(layer, d.kind(i),
                                             i // d.period))(
            x, params["stack"][u], adapters["stack"][u])
    return R.rms_norm(x, params["final_norm"]["scale"], d.norm_eps)


# ---------------------------------------------------------------------------
# Fine-tuning: loss, gradients and AdamW, as the traffic file states them.
# ---------------------------------------------------------------------------

def loss_fn(d: Dims, adapters, params, tokens, labels, loss_tokens: int,
            quant=None, stride=1, reset=0):
    """Mean next-token NLL over the last ``loss_tokens`` positions of each
    row of ``tokens`` [B, S] (every ``stride``-th of them: 2 is the fault
    of a loss taken over half the tokens)."""
    keep = (jnp.arange(loss_tokens) % stride == 0).astype(F32)

    def row(t, lbl):
        x = hidden(d, params, adapters, t, quant, reset)[-loss_tokens:]
        lse, gold, _, _ = R.head_stats(params, x, lbl[-loss_tokens:], quant)
        return jnp.sum(keep * (lse - gold)) / jnp.sum(keep)
    return jnp.mean(jax.vmap(row)(tokens, labels))


@functools.partial(jax.jit, static_argnums=(0, 6, 7, 8),
                   donate_argnums=(1, 2))
def train_step(d, adapters, moments, params, tokens, labels, loss_tokens,
               opt_items, quant, stride=1, reset=0):
    """One AdamW step of the float32 adapters, as
    :func:`reference.train_step` takes it. Returns (adapters', moments',
    loss, the clipped gradient as the optimizer takes it). ``stride`` and
    ``reset`` (the planted faults of :func:`loss_fn` and :func:`ssm_scan`)
    are traced, so the faults run the plain reference's program."""
    opt = dict(opt_items)
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(loss_fn, argnums=1)(
            d, adapters, params, tokens, labels, loss_tokens, quant, stride,
            reset)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    clip = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-12))
    grads = jax.tree.map(lambda g: g * clip, grads)
    count = moments["count"] + 1
    b1, b2 = opt["beta1"], opt["beta2"]
    c1 = 1.0 - b1 ** count.astype(F32)
    c2 = 1.0 - b2 ** count.astype(F32)
    lr = R.lr_at(opt, count)

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


start_training = R.start_training


def logits(d: Dims, params, adapters, tokens):
    """[S, V] float32 logits of one sequence (small sizes: the head is
    applied whole)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(d, params, adapters, tokens)
        return R._mm(x, params["head"].astype(F32), None)

