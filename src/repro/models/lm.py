"""LM assembly: parameter specs/init, scan-over-layers forward, KV caches.

The layer stack is represented as ONE scan unit (the repeating layer pattern
— a single layer for uniform archs, 8 layers for Jamba's 1:7 interleave)
whose params are stacked along a leading scan dim. ``jax.lax.scan`` over the
stack keeps trace/compile size O(period), independent of depth — essential
for 64-80L configs lowered against 512 devices.

Adapters mirror the param tree: every adapted linear leaf holds
{"A","B","m"} stacked the same way, so the same scan slices both.

Caches: attention {"k","v"} [T]-indexed ring + mamba {"h","conv"} states,
stacked per scan unit; "len" is carried outside the scan — a scalar for
training/static serving, or a [B] per-row length vector for the
continuous-batching engine (``init_cache(row_lens=True)``), where every
batch row stands at its own position and requests join/leave mid-decode.
"""
from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.compat import tree as ctree
from repro.core import DoRAConfig
from repro.core.adapter import init_dora_params
from repro.models import layers as L
from repro.models import mamba as M
from repro.models import moe as MOE
from repro.models.config import ModelConfig

_F32 = jnp.float32

DEFAULT_DORA_TARGETS = ("wq", "wk", "wv", "wo",
                        "w_gate", "w_up", "w_down",
                        "in_proj", "out_proj")


# ---------------------------------------------------------------------------
# Parameter spec construction. Leaves are (init_kind, shape) tuples turned
# into ShapeDtypeStructs (dry-run) or initialized arrays (smoke/train).
# ---------------------------------------------------------------------------

def _norm_spec(mcfg, D=None):
    D = D or mcfg.d_model
    s = {"scale": ("ones", (D,))}
    if mcfg.norm_kind == "layer":
        s["bias"] = ("zeros", (D,))
    return s


def _attn_spec(mcfg: ModelConfig):
    D, qd, kvd = mcfg.d_model, mcfg.q_dim, mcfg.kv_dim
    s = {"wq": ("linear", (qd, D)), "wk": ("linear", (kvd, D)),
         "wv": ("linear", (kvd, D)), "wo": ("linear", (D, qd))}
    if mcfg.qkv_bias:
        s["wq_bias"] = ("zeros", (qd,))
        s["wk_bias"] = ("zeros", (kvd,))
        s["wv_bias"] = ("zeros", (kvd,))
    if mcfg.qk_norm:
        s["q_norm"] = ("ones", (mcfg.head_dim,))
        s["k_norm"] = ("ones", (mcfg.head_dim,))
    return s


def _mamba_spec(mcfg: ModelConfig):
    D, di, n = mcfg.d_model, mcfg.d_inner, mcfg.ssm_state
    dtr, k = mcfg.dt_rank, mcfg.ssm_conv
    s = {"in_proj": ("linear", (2 * di, D)),
         "conv_w": ("conv", (k, di)), "conv_b": ("zeros", (di,)),
         "x_proj": ("linear", (dtr + 2 * n, di)),
         "dt_proj": ("linear", (di, dtr)), "dt_bias": ("dt_bias", (di,)),
         "A_log": ("a_log", (di, n)), "skip_d": ("ones", (di,)),
         "out_proj": ("linear", (D, di))}
    if mcfg.ssm_inner_norm:
        s.update(dt_norm=("ones", (dtr,)), b_norm=("ones", (n,)),
                 c_norm=("ones", (n,)))
    return s


def _mlp_spec(mcfg: ModelConfig, ff: int):
    D = mcfg.d_model
    if mcfg.mlp_kind == "swiglu":
        return {"w_gate": ("linear", (ff, D)), "w_up": ("linear", (ff, D)),
                "w_down": ("linear", (D, ff))}
    return {"w_up": ("linear", (ff, D)), "w_up_bias": ("zeros", (ff,)),
            "w_down": ("linear", (D, ff)), "w_down_bias": ("zeros", (D,))}


def _moe_spec(mcfg: ModelConfig):
    D, E, F = mcfg.d_model, mcfg.num_experts, mcfg.moe_d_ff
    s = {"router": ("linear", (E, D)),
         "gate": ("linear3", (E, F, D)), "up": ("linear3", (E, F, D)),
         "down": ("linear3", (E, D, F))}
    if mcfg.num_shared_experts:
        s["shared"] = _mlp_spec(mcfg, mcfg.shared_d_ff)
        s["shared_gate"] = ("linear", (1, D))
    return s


def _layer_spec(mcfg: ModelConfig, kind: str, ffn: str):
    s: dict[str, Any] = {"ln1": _norm_spec(mcfg)}
    s["mixer"] = _attn_spec(mcfg) if kind == "attn" else _mamba_spec(mcfg)
    if ffn != "none":
        s["ln2"] = _norm_spec(mcfg)
        s["ffn"] = _moe_spec(mcfg) if ffn == "moe" else _mlp_spec(mcfg,
                                                                  mcfg.d_ff)
    return s


def unit_spec(mcfg: ModelConfig):
    """The repeating scan unit: {"l0": layer, ..., "l{p-1}": layer}."""
    kinds, ffns = mcfg.layer_kinds(), mcfg.ffn_kinds()
    p = mcfg.period
    return {f"l{i}": _layer_spec(mcfg, kinds[i], ffns[i]) for i in range(p)}


def model_spec(mcfg: ModelConfig):
    D, V = mcfg.d_model, mcfg.vocab_size
    return {"embed": ("embed", (V, D)),
            "stack": unit_spec(mcfg),
            "final_norm": _norm_spec(mcfg),
            "head": ("linear", (V, D))}


def _is_leaf_spec(x):
    return (isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], str))


def _map_spec(fn, spec):
    return ctree.map(fn, spec, is_leaf=_is_leaf_spec)


def param_shapes(mcfg: ModelConfig):
    """ShapeDtypeStruct tree — dry-run params, never allocated."""
    n_scan = mcfg.num_layers // mcfg.period

    def to_sds(leaf):
        kind, shape = leaf
        return jax.ShapeDtypeStruct(shape, mcfg.dtype)

    spec = model_spec(mcfg)
    out = {}
    for k, v in spec.items():
        if k == "stack":
            out[k] = _map_spec(
                lambda leaf: jax.ShapeDtypeStruct(
                    (n_scan,) + leaf[1], mcfg.dtype), v)
        else:
            out[k] = _map_spec(to_sds, v)
    return out


def _init_leaf(key, kind, shape, dtype):
    if kind in ("zeros",):
        return jnp.zeros(shape, dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "dt_bias":
        # softplus(dt_bias) ≈ dt ∈ [1e-3, 1e-1] (mamba init)
        u = jax.random.uniform(key, shape, _F32,
                               math.log(1e-3), math.log(1e-1))
        dt = jnp.exp(u)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if kind == "a_log":
        di, n = shape
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=_F32)), (di, n)).astype(dtype)
    if kind == "conv":
        k, di = shape
        bound = 1.0 / math.sqrt(k)
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    if kind == "embed":
        return (0.02 * jax.random.normal(key, shape, _F32)).astype(dtype)
    # linear / linear3: fan-in scaled normal; d_in is the last dim.
    fan_in = shape[-1]
    w = jax.random.normal(key, shape, _F32) / math.sqrt(fan_in)
    return w.astype(dtype)


def init_params(key, mcfg: ModelConfig):
    n_scan = mcfg.num_layers // mcfg.period
    spec = model_spec(mcfg)
    _, treedef = ctree.flatten(spec, is_leaf=_is_leaf_spec)
    # Stable per-leaf keys via fold_in of the leaf index.
    paths = ctree.flatten_with_path(spec, is_leaf=_is_leaf_spec)[0]
    leaves = []
    for i, ((path, leaf)) in enumerate(paths):
        kind, shape = leaf
        in_stack = path and ctree.path_key(path[0]) == "stack"
        k = jax.random.fold_in(key, i)
        if in_stack:
            ks = jax.random.split(k, n_scan)
            leaves.append(jax.vmap(
                lambda kk: _init_leaf(kk, kind, shape, mcfg.dtype))(ks))
        else:
            leaves.append(_init_leaf(k, kind, shape, mcfg.dtype))
    return ctree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# DoRA adapter trees.
# ---------------------------------------------------------------------------

def _adapted_paths(mcfg: ModelConfig, targets):
    """Paths (tuples of keys) into the stack unit that get adapters, with
    their (d_out, d_in)."""
    out = []

    def walk(spec, path):
        for k, v in spec.items():
            if _is_leaf_spec(v):
                kind, shape = v
                if k in targets and kind == "linear" and len(shape) == 2:
                    out.append((path + (k,), shape))
            else:
                walk(v, path + (k,))

    walk(unit_spec(mcfg), ())
    return out


def adapter_shapes(mcfg: ModelConfig, dcfg: DoRAConfig,
                   targets=DEFAULT_DORA_TARGETS):
    """ShapeDtypeStruct tree of adapters (stacked over the scan dim)."""
    n_scan = mcfg.num_layers // mcfg.period
    r = dcfg.rank
    tree: dict[str, Any] = {}
    for path, (d_out, d_in) in _adapted_paths(mcfg, targets):
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        leaf = {
            "A": jax.ShapeDtypeStruct((n_scan, r, d_in), mcfg.dtype),
            "B": jax.ShapeDtypeStruct((n_scan, d_out, r), mcfg.dtype),
            "m": jax.ShapeDtypeStruct((n_scan, d_out), _F32),
        }
        if dcfg.cache_base_norm:
            leaf["base_sq"] = jax.ShapeDtypeStruct((n_scan, d_out), _F32)
        node[path[-1]] = leaf
    return {"stack": tree}


def init_adapters(key, mcfg: ModelConfig, params, dcfg: DoRAConfig,
                  targets=DEFAULT_DORA_TARGETS):
    """A ~ U(±1/√d_in), B = 0, m = ||W||_row (DoRA init) per layer slice."""
    tree: dict[str, Any] = {}
    for i, (path, _) in enumerate(_adapted_paths(mcfg, targets)):
        W = params["stack"]
        for k in path:
            W = W[k]                                  # [n_scan, d_out, d_in]
        k_i = jax.random.fold_in(key, i)
        leaf = init_dora_params(k_i, W, dcfg)         # vmapped over n_scan
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = leaf
    return {"stack": tree}


def adapter_param_count(mcfg: ModelConfig, dcfg: DoRAConfig,
                        targets=DEFAULT_DORA_TARGETS) -> int:
    n_scan = mcfg.num_layers // mcfg.period
    total = 0
    for _, (d_out, d_in) in _adapted_paths(mcfg, targets):
        total += n_scan * (dcfg.rank * d_in + d_out * dcfg.rank + d_out)
    return total


# ---------------------------------------------------------------------------
# Caches.
# ---------------------------------------------------------------------------

def cache_shapes(mcfg: ModelConfig, batch: int, max_len: int,
                 dtype=None, *, row_lens: bool = False,
                 block_size: int | None = None,
                 n_blocks: int | None = None):
    """ShapeDtypeStruct tree for the decode cache.

    ``row_lens=True``: continuous-batching cache — ``"len"`` is a ``[B]``
    int32 vector of per-row cache lengths instead of one scalar, so every
    slot of the batch stands at its own position (requests join/leave
    mid-decode; see ``repro.launch.engine``). The scalar form stays the
    default for training/static serving.

    ``block_size``: block-PAGED cache — per-layer K/V become a shared
    block pool ``[n_scan, n_blocks, block_size, Hkv, hd]`` (no batch
    dim), addressed through a per-row block table ``"pages"``
    ``[batch, max_len // block_size]`` int32 (``-1`` = unallocated, reads
    as zeros). ``n_blocks`` defaults to ``batch * max_len // block_size``
    (paged == rectangular bytes at full allocation; the engine sizes it
    smaller to realize the HBM win). Requires ``row_lens=True`` and an
    attention-only arch — paging is a serving-cache layout, and SSM
    states are O(1) per row, not positional."""
    dtype = dtype or mcfg.dtype
    n_scan = mcfg.num_layers // mcfg.period
    kinds = mcfg.layer_kinds()
    paged = block_size is not None
    if paged:
        if not row_lens:
            raise ValueError("paged cache requires row_lens=True "
                             "(per-row frontiers address the block table)")
        if max_len % block_size != 0:
            raise ValueError(f"max_len={max_len} must be a multiple of "
                             f"block_size={block_size}")
        if any(k != "attn" for k in kinds):
            raise ValueError(f"paged cache requires an attention-only "
                             f"arch; {mcfg.name!r} has {kinds}")
        max_blocks = max_len // block_size
        if n_blocks is None:
            n_blocks = batch * max_blocks
    unit: dict[str, Any] = {}
    for i in range(mcfg.period):
        if kinds[i] == "attn":
            kv_shape = ((n_scan, n_blocks, block_size, mcfg.num_kv_heads,
                         mcfg.head_dim) if paged else
                        (n_scan, batch, max_len, mcfg.num_kv_heads,
                         mcfg.head_dim))
            unit[f"l{i}"] = {
                "k": jax.ShapeDtypeStruct(kv_shape, dtype),
                "v": jax.ShapeDtypeStruct(kv_shape, dtype),
            }
        else:
            unit[f"l{i}"] = {
                "h": jax.ShapeDtypeStruct(
                    (n_scan, batch, mcfg.d_inner, mcfg.ssm_state), _F32),
                "conv": jax.ShapeDtypeStruct(
                    (n_scan, batch, mcfg.ssm_conv - 1, mcfg.d_inner), dtype),
            }
    out = {"stack": unit,
           "len": jax.ShapeDtypeStruct((batch,) if row_lens else (),
                                       jnp.int32)}
    if paged:
        out["pages"] = jax.ShapeDtypeStruct((batch, max_blocks), jnp.int32)
    return out


def init_cache(mcfg: ModelConfig, batch: int, max_len: int, dtype=None, *,
               row_lens: bool = False, block_size: int | None = None,
               n_blocks: int | None = None):
    shapes = cache_shapes(mcfg, batch, max_len, dtype, row_lens=row_lens,
                          block_size=block_size, n_blocks=n_blocks)
    cache = ctree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    if "pages" in cache:
        # -1 = unallocated: a zeroed table would alias every row to
        # block 0.
        cache["pages"] = jnp.full(shapes["pages"].shape, -1, jnp.int32)
    return cache


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------

def _apply_norm(x, p, mcfg: ModelConfig):
    if mcfg.norm_kind == "layer":
        x32 = x.astype(_F32)
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * jax.lax.rsqrt(var + mcfg.norm_eps)
        y = y * p["scale"].astype(_F32) + p["bias"].astype(_F32)
        return y.astype(x.dtype)
    return L.rms_norm(x, p["scale"], mcfg.norm_eps)


def _layer_apply(x, p, a, c, mcfg, dcfg, *, kind, ffn, positions, length,
                 training, constrain=None, tenant_groups=None, pages=None):
    """One layer: pre-norm mixer + pre-norm FFN, residual adds.

    c: None (no cache) or this layer's cache dict. Returns (x, new_cache,
    aux_loss). ``constrain`` pins the sublayer outputs to the
    sequence-parallel sharding so the row-parallel TP partial sums lower
    to reduce-scatter instead of all-reduce.
    ``tenant_groups``: multi-tenant serving — adapted linears apply the
    per-group folded adapter state (attention/dense-MLP archs only)."""
    aux = jnp.asarray(0.0, _F32)
    cst = constrain or (lambda t: t)
    if tenant_groups is not None and (kind != "attn" or ffn == "moe"):
        raise NotImplementedError(
            f"multi-tenant grouped serving supports attention + dense-MLP "
            f"layers only; arch {mcfg.name!r} has a "
            f"{'moe ffn' if ffn == 'moe' else kind} layer")
    h = _apply_norm(x, p["ln1"], mcfg)
    if kind == "attn":
        attn_cache = None
        if c is not None:
            attn_cache = {"k": c["k"], "v": c["v"], "len": length}
            if pages is not None:
                attn_cache["pages"] = pages
        y, new_c = L.attention(h, p["mixer"], (a or {}).get("mixer"), mcfg,
                               dcfg, positions=positions, cache=attn_cache,
                               training=training, constrain=constrain,
                               tenant_groups=tenant_groups)
        if new_c is not None:
            new_c = {"k": new_c["k"], "v": new_c["v"]}
    else:
        mcache = {"h": c["h"], "conv": c["conv"]} if c is not None else None
        y, new_c = M.mamba_block(h, p["mixer"], (a or {}).get("mixer"),
                                 mcfg, dcfg, cache=mcache,
                                 training=training, constrain=constrain)
    x = x + cst(y)
    if ffn != "none":
        h = _apply_norm(x, p["ln2"], mcfg)
        if ffn == "moe":
            y, aux = MOE.moe_ffn(h, p["ffn"], (a or {}).get("ffn"), mcfg,
                                 dcfg, training=training)
        elif mcfg.mlp_kind == "swiglu":
            y = L.mlp_swiglu(h, p["ffn"], (a or {}).get("ffn"), dcfg,
                             training=training, constrain=constrain,
                             tenant_groups=tenant_groups)
        else:
            d = (a or {}).get("ffn") or {}
            y = L.maybe_dora(h, p["ffn"]["w_up"], d.get("w_up"), dcfg,
                             bias=p["ffn"]["w_up_bias"], training=training,
                             tenant_groups=tenant_groups)
            y = jax.nn.gelu(y)
            y = L.maybe_dora(y, p["ffn"]["w_down"], d.get("w_down"), dcfg,
                             bias=p["ffn"]["w_down_bias"], training=training,
                             tenant_groups=tenant_groups)
        x = x + cst(y)
    return x, new_c, aux


def forward(mcfg: ModelConfig, params, adapters, dcfg: DoRAConfig | None,
            *, tokens=None, embeds=None, cache=None, positions=None,
            training: bool = True, boundary_constraint=None,
            loss_slice: int | None = None, gather_position=None,
            tenant_groups=None):
    """Returns (logits [B,S,V], new_cache, aux_loss).

    tokens [B,S] int32 OR embeds [B,S,D] (modality-frontend stubs feed
    precomputed patch/frame embeddings). cache: decode cache tree or None.

    ``boundary_constraint``: optional fn applied to the [B,S,D] activations
    at every scan-unit boundary — the hook the distribution layer uses to
    pin sequence-parallel sharding (saved remat residuals inherit it).
    ``loss_slice``: keep only the last N positions before the LM head
    (paper §5.1 partial-sequence loss — avoids the full-vocab logit spike).
    ``gather_position``: int32 scalar (traced OK) — keep ONLY this position
    before the final norm + LM head (logits come back [B, 1, V]); the
    shape-bucketed prefill uses it so the full-vocab head runs on exactly
    one row regardless of how much right-padding the bucket added.
    Overrides ``loss_slice``.
    ``tenant_groups``: multi-tenant serving — STATIC (start, size) row
    blocks grouping the batch by adapter, OR a TRACED int32 [B] array of
    per-row positions into the stacked tenant dim (dynamic fleet serving:
    tenant churn changes values, never the compile signature); either
    way ``adapters`` must be a stacked folded serving tree (leaves
    [n_scan, K, ...], see ``repro.core.stack_adapter_states``).
    Serving-only: requires ``training=False``.
    """
    if tenant_groups is not None and training:
        raise ValueError("tenant_groups is a serving-only path "
                         "(training=False required)")
    kinds, ffns = mcfg.layer_kinds(), mcfg.ffn_kinds()
    p = mcfg.period
    adapters = adapters or {}

    if embeds is None:
        emb = jax.lax.stop_gradient(params["embed"])
        x = jnp.take(emb, tokens, axis=0)
    else:
        x = embeds.astype(mcfg.dtype)
    B, S = x.shape[:2]

    length = cache["len"] if cache is not None else None
    if positions is None:
        pos_base = jnp.arange(S, dtype=jnp.int32)[None, :]
        if length is not None and getattr(length, "ndim", 0) == 1:
            # Continuous batching: per-row cache lengths [B] — every slot
            # positions its new tokens at its own depth.
            positions = pos_base + length[:, None].astype(jnp.int32)
        else:
            positions = jnp.broadcast_to(
                pos_base if length is None else pos_base + length, (B, S))
    if mcfg.pos_mode == "sinusoidal":
        x = x + L.sinusoidal_embedding(positions, mcfg.d_model).astype(
            x.dtype)

    stack_p = params["stack"]
    stack_a = adapters.get("stack", {})
    stack_c = cache["stack"] if cache is not None else None
    # Paged serving cache: the per-row block table rides OUTSIDE the scan
    # (like "len") — one table addresses every layer's pool, and it is
    # read-only inside the forward (the engine owns allocation).
    pages = cache.get("pages") if cache is not None else None

    if boundary_constraint is not None:
        x = boundary_constraint(x)

    # remat="layer" keeps only layer boundaries (and the named DoRA norms)
    # for the backward: a one-layer scan unit is checkpointed whole, the
    # layers of a multi-layer unit (a hybrid's period) one by one, so the
    # backward holds one layer's activations at a time, not the period's.
    remat = functools.partial(
        jax.checkpoint,
        policy=jax.checkpoint_policies.save_only_these_names("dora_wnorm"))

    def layer_apply(x, p_i, a_i, c_i, kind, ffn):
        return _layer_apply(
            x, p_i, a_i, c_i, mcfg, dcfg, kind=kind, ffn=ffn,
            positions=positions, length=length, training=training,
            constrain=boundary_constraint, tenant_groups=tenant_groups,
            pages=pages)

    if mcfg.remat == "layer" and p > 1:
        layer_apply = remat(layer_apply, static_argnums=(4, 5))

    def unit_body(x, unit_p, unit_a, unit_c):
        aux_total = jnp.asarray(0.0, _F32)
        new_cs = {}
        for i in range(p):
            li = f"l{i}"
            c_i = unit_c[li] if unit_c is not None else None
            x, new_c, aux = layer_apply(x, unit_p[li], unit_a.get(li), c_i,
                                        kinds[i], ffns[i])
            if new_c is not None:
                new_cs[li] = new_c
            aux_total = aux_total + aux
        if boundary_constraint is not None:
            x = boundary_constraint(x)
        return x, new_cs, aux_total

    if mcfg.remat == "layer" and p == 1:
        unit_body = remat(unit_body)

    if stack_c is None:
        def body(carry, xs):
            x, aux = carry
            unit_p, unit_a = xs
            x, _, aux_u = unit_body(x, unit_p, unit_a, None)
            return (x, aux + aux_u), None

        (x, aux), _ = jax.lax.scan(body, (x, jnp.asarray(0.0, _F32)),
                                   (stack_p, stack_a))
        new_cache = None
    else:
        def body(carry, xs):
            x, aux = carry
            unit_p, unit_a, unit_c = xs
            x, new_cs, aux_u = unit_body(x, unit_p, unit_a, unit_c)
            return (x, aux + aux_u), new_cs

        (x, aux), new_stack_c = jax.lax.scan(
            body, (x, jnp.asarray(0.0, _F32)), (stack_p, stack_a, stack_c))
        new_cache = {"stack": new_stack_c, "len": length + S}
        if pages is not None:
            new_cache["pages"] = pages

    if gather_position is not None:
        x = jax.lax.dynamic_slice_in_dim(x, gather_position, 1, axis=1)
    elif loss_slice is not None and loss_slice < x.shape[1]:
        x = x[:, -loss_slice:]
    x = _apply_norm(x, params["final_norm"], mcfg)
    head = jax.lax.stop_gradient(params["head"])
    logits = x @ head.T
    return logits, new_cache, aux
