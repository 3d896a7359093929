"""DoRA adapter: parameter init + the adapted linear application.

Forward contract (paper App. A):

    ΔY = g ⊙ (s · X·Aᵀ·Bᵀ) + (g − 1) ⊙ Y_base,   Y = Y_base + ΔY
    g  = m / max(w_norm, ε)            (fp32, outside the no-grad context)
    w_norm = ||W + s·B·A||_row         (fp32, detached, recomputed per step)

Bias is subtracted before the compose and re-added after (i.e. the compose
operates on the bias-free Y_base); under training the norm is recomputed
every forward. Weights follow the paper's [d_out, d_in] convention with
per-output-row norms.

``dora_linear`` is the single integration point the models use; it routes
through the three-tier dispatch. Two hot-path overhauls live here:

  - **Matmul-fused compose** (plan flag ``matmul_fused``): when the rank
    passes the crossover guard, the LoRA up-projection ``h @ Bᵀ`` runs
    inside the compose kernel and the ``[M, d_out]`` y_lora tensor is never
    materialized in HBM — including under SPMD: sharded call sites pin the
    rank-space ``h`` (rows like the output, rank replicated) instead of a
    materialized y_lora, and an expressible :class:`~repro.core.sharding.
    ComposeSharding` plan runs the kernel shard-local under shard_map.
  - **Frozen-adapter serving state** (:func:`precompute_adapter_state`):
    during generation A/B/m are frozen, so ``w_norm`` — and hence ``g`` —
    is computed ONCE per adapter set and carried in the adapter tree as a
    ``"g"`` leaf; the decode loop then does zero factored-norm work per
    token. **Invalidation contract:** the cached state is only valid while
    A/B/m are untouched — ``dora_linear(training=True)`` refuses a tree
    carrying ``"g"`` so a stale cache can never silently leak into
    training; rebuild the state after every adapter update.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import ad_checkpoint as _adc

from repro.core import compose as _compose
from repro.core import dispatch as _dispatch
from repro.core import factored_norm as _norm
from repro.core.config import DoRAConfig
from repro.core.sharding import ComposeSharding, as_compose_sharding

_F32 = jnp.float32


def init_dora_params(key, W, cfg: DoRAConfig, *, m_dtype=jnp.float32):
    """Init A ~ U(-1/√d_in, 1/√d_in) (PEFT's LoRA-A default), B = 0,
    m = ||W||_row (DoRA init). Supports stacked weights [..., d_out, d_in]
    (layer stacks / experts) by vmapping over leading dims."""
    if W.ndim > 2:
        keys = jax.random.split(key, W.shape[0])
        return jax.vmap(
            lambda k, w: init_dora_params(k, w, cfg, m_dtype=m_dtype)
        )(keys, W)
    d_out, d_in = W.shape
    bound = 1.0 / (d_in ** 0.5)
    A = jax.random.uniform(key, (cfg.rank, d_in), W.dtype, -bound, bound)
    B = jnp.zeros((d_out, cfg.rank), W.dtype)
    # At init B = 0 so ||W + sBA|| = ||W||: reuse the factored base term.
    base_sq, _, _ = _norm.factored_norm_terms(W, A, B, compute_cross=False)
    m = jnp.sqrt(jnp.maximum(base_sq, 0.0)).astype(m_dtype)
    out = {"A": A, "B": B, "m": m}
    if cfg.cache_base_norm:
        # Paper §2.3 future work, implemented (H3.2): W is frozen, so
        # ||W||²_row is precomputed once into a [d_out] fp32 buffer and
        # carried in the adapter tree — the per-step norm never re-reads
        # W for the base term.
        out["base_sq"] = base_sq
    return out


def compute_weight_norm(W, A, B, cfg: DoRAConfig, *, axis_name=None,
                        base_sq_cache=None, interpret: bool | None = None):
    """Detached fp32 [d_out] row norm of the composed weight, routed through
    the configured implementation. Every route tags its result with the
    ``"dora_wnorm"`` checkpoint name: the layer-remat policy saves it
    (instead of recomputing O(d_out·d_in) in the backward) and tests
    assert from the jaxpr that cached-state serving steps contain no norm
    work at all."""
    return _adc.checkpoint_name(
        _compute_weight_norm(W, A, B, cfg, axis_name=axis_name,
                             base_sq_cache=base_sq_cache,
                             interpret=interpret), "dora_wnorm")


def _compute_weight_norm(W, A, B, cfg: DoRAConfig, *, axis_name,
                         base_sq_cache, interpret):
    impl = cfg.norm_impl
    if axis_name is not None:
        # Sharded accumulation (beyond-paper, DESIGN.md §5): only the
        # factored algebra distributes; the baselines would all-gather.
        return _norm.factored_norm_sharded(
            W, A, B, cfg.scaling, axis_name=axis_name,
            chunk_mb=cfg.resolve_chunk_mb(),
            base_sq_cache=base_sq_cache)
    if impl == "peft_eye":
        return _norm.norm_peft_eye(W, A, B, cfg.scaling)
    if impl == "dense_ba":
        return _norm.norm_dense_ba(W, A, B, cfg.scaling)
    plan = _dispatch.plan_norm(cfg, d_out=W.shape[0])
    if plan.fused:
        from repro.kernels import ops as _kops
        return _kops.fused_norm(
            W, A, B, cfg.scaling,
            block_rows=cfg.norm_block_rows, block_k=cfg.norm_block_k,
            interpret=(plan.interpret if interpret is None else interpret),
            base_sq_cache=base_sq_cache)
    return _norm.factored_norm(W, A, B, cfg.scaling,
                               chunk_mb=cfg.resolve_chunk_mb(),
                               base_sq_cache=base_sq_cache)


def _row_count(shape) -> int:
    rows = 1
    for d in shape[:-1]:
        rows *= d
    return rows


def compose_delta(y_base, y_lora, g, cfg: DoRAConfig, *, training: bool):
    """Route the compose through the three-tier dispatch (materialized
    y_lora form — rank unknown here, so never matmul-fused)."""
    _compose.check_broadcast(g, y_base)
    plan = _dispatch.plan_compose(cfg, training=training,
                                  rows=_row_count(y_base.shape),
                                  d_out=y_base.shape[-1])
    if plan.tier is _dispatch.Tier.EAGER:
        return _compose.compose_stable(y_base, y_lora, g, cfg.scaling)
    from repro.kernels import ops as _kops
    if plan.tier is _dispatch.Tier.FUSED_FWD:
        g = jax.lax.stop_gradient(g)
        return _kops.fused_compose(
            y_base, y_lora, g, cfg.scaling, save_inner=False,
            mag_grad=False, block_m=cfg.block_rows, block_n=cfg.block_cols,
            interpret=plan.interpret)
    return _kops.fused_compose(
        y_base, y_lora, g, cfg.scaling,
        save_inner=cfg.save_inner and cfg.magnitude_trainable,
        mag_grad=cfg.magnitude_trainable,
        block_m=cfg.block_rows, block_n=cfg.block_cols,
        interpret=plan.interpret)


def compose_delta_factored(y_base, h, B, g, cfg: DoRAConfig, *,
                           training: bool,
                           sharding: ComposeSharding | None = None,
                           constrain=None):
    """Compose from the factored LoRA activation ``h = x@Aᵀ``.

    When the plan resolves matmul-fused, the up-projection h@Bᵀ runs inside
    the compose kernel and y_lora never touches HBM; otherwise y_lora is
    materialized once and the classic element-wise path runs (identical
    math — tier-equivalence is tested).

    ``sharding``: the call site's :class:`ComposeSharding` plan. An
    expressible plan rides the KernelPlan into the shard_map'd kernel
    (shard-local tiles, no y_lora anywhere); an inexpressible one falls
    back to the materialized-lora route, where ``constrain`` (the output
    constraint — usually ``sharding`` itself or a legacy callable) pins
    y_lora so the TP partial sums still lower to reduce-scatter (H1.4).
    """
    _compose.check_broadcast(g, y_base)
    rows = _row_count(y_base.shape)
    plan = _dispatch.plan_compose(cfg, training=training, rows=rows,
                                  d_out=y_base.shape[-1],
                                  rank=B.shape[-1], sharding=sharding)
    if plan.matmul_fused:
        from repro.kernels import ops as _kops
        mag_grad = cfg.magnitude_trainable
        if plan.tier is _dispatch.Tier.FUSED_FWD:
            g = jax.lax.stop_gradient(g)
            mag_grad = False
        rows_local = rows // (plan.sharding.row_shards
                              if plan.sharding is not None else 1)
        return _kops.fused_compose_mm(
            y_base, h, B, g, cfg.scaling, mag_grad=mag_grad,
            block_m=cfg.resolve_mm_block_rows(rows_local),
            block_n=cfg.block_cols,
            interpret=plan.interpret, sharding=plan.sharding)
    y_lora = h @ B.T
    if constrain is not None:
        y_lora = constrain(y_lora)
    return compose_delta(y_base, y_lora, g, cfg, training=training)


def dora_linear(x, W, adapter: dict[str, Any], cfg: DoRAConfig, *,
                bias=None, training: bool = True, axis_name=None,
                base_sq_cache=None, constrain=None, tenant_groups=None):
    """Adapted linear: x [..., d_in] → y [..., d_out].

    W: frozen [d_out, d_in]; adapter: {"A": [r, d_in], "B": [d_out, r],
    "m": [d_out]} plus optional cached leaves — "base_sq" (precomputed
    ||W||²_row, H3.2) and the frozen-adapter serving state written by
    :func:`precompute_adapter_state` ("g", optionally "gsB"). A cached "g"
    skips the factored norm entirely (zero norm FLOPs per decode token) and
    is refused under ``training=True`` (invalidation contract).

    ``axis_name``: if W/A are d_in-sharded inside shard_map, the norm
    partials psum over this axis. ``constrain``: the call site's sharding —
    either a :class:`ComposeSharding` plan (or a callable carrying one as
    ``.plan``, like ``launch.sharding.make_boundary_constraint``'s), or a
    bare row-constraint callable. Sharded call sites pin y_base AND the
    rank-space intermediate ``h`` (rows sharded like the output, rank
    replicated) — never a materialized y_lora — so the matmul-fused route
    stays available under SPMD and the TP partial sums still lower to
    reduce-scatter (H1.4). With a full plan the fused kernels run
    shard-local under shard_map; a bare callable must be a row-only
    constraint (its feature entry replicated), which every
    sequence-parallel boundary constraint is.

    ``tenant_groups``: multi-tenant serving. EITHER a static tuple of
    ``(start, size)`` row blocks partitioning x's leading (batch) dim —
    one per tenant, compile-time signature — OR a TRACED int32 ``[B]``
    array of per-row positions into the stacked tenant dim (dynamic fleet
    serving: one executable for every tenant mix). Adapter leaves carry a
    leading tenant dim K — see :func:`dora_linear_grouped`.
    """
    if tenant_groups is not None:
        return dora_linear_grouped(x, W, adapter, cfg, tenant_groups,
                                   bias=bias, training=training,
                                   constrain=constrain)
    A, B, m = adapter["A"], adapter["B"], adapter["m"]
    plan_sh = as_compose_sharding(constrain)
    cfn = plan_sh if plan_sh is not None else constrain
    if "g" in adapter:
        if training:
            raise ValueError(
                "adapter tree carries precomputed serving state ('g'), "
                "which is stale the moment A/B/m change: it is invalid "
                "under training=True. Train on the raw adapter tree and "
                "rebuild the state with precompute_adapter_state() after "
                "the update.")
        g = jax.lax.stop_gradient(adapter["g"]).astype(_F32)
    else:
        if base_sq_cache is None and "base_sq" in adapter:
            base_sq_cache = adapter["base_sq"]
        if base_sq_cache is not None:
            base_sq_cache = jax.lax.stop_gradient(base_sq_cache)
        w_norm = compute_weight_norm(W, A, B, cfg, axis_name=axis_name,
                                     base_sq_cache=base_sq_cache)
        eps = _norm.dtype_eps(x.dtype)
        g = _compose.magnitude_scale(m, w_norm, eps)
    if not cfg.magnitude_trainable:
        g = jax.lax.stop_gradient(g)

    W = jax.lax.stop_gradient(W)
    y_base = x @ W.T
    h = x @ A.T
    if cfn is not None:
        y_base = cfn(y_base)
        # Constrain the RANK-SPACE intermediate, not y_lora: rows shard
        # exactly like the output, the rank dim replicates — [M, r] is the
        # cheap tensor to pin, and the fused compose stays factored.
        h = plan_sh.constrain_h(h) if plan_sh is not None else cfn(h)
    if "gsB" in adapter and not training:
        # Serving fast path (opt-in, see precompute_adapter_state): g·s is
        # pre-folded into B, so the per-token work collapses to two
        # matmuls + one fused multiply-add — the g·s broadcast over the
        # [M, d_out] lora term is gone (only the (g-1)·base one remains).
        # Sharded call sites take it too: h is already pinned rank-space
        # above, and the folded up-projection output inherits the output
        # constraint like any row-parallel matmul.
        gsB = jax.lax.stop_gradient(adapter["gsB"])
        if plan_sh is not None and plan_sh.b_dout_axes and gsB.ndim == 2:
            # B's d_out carries FSDP axes beyond the output's (the ROADMAP
            # b_spec gap): declare the true layout so GSPMD reshards the
            # small [d_out, r] folded weight explicitly, not the
            # activations.
            gsB = plan_sh.constrain_b(gsB)
        t = jax.lax.dot_general(
            h.astype(_F32), gsB.astype(_F32),
            (((x.ndim - 1,), (1,)), ((), ())), preferred_element_type=_F32)
        delta = ((g - 1.0) * y_base.astype(_F32) + t).astype(y_base.dtype)
        y = y_base + delta
    else:
        delta = compose_delta_factored(y_base, h, B, g, cfg,
                                       training=training, sharding=plan_sh,
                                       constrain=cfn)
        y = y_base + delta
    if bias is not None:
        y = y + bias  # bias re-added after the compose (paper App. A)
    return y


def dora_linear_stacked(x, W, adapter, cfg: DoRAConfig, *, bias=None,
                        training=True, base_sq_cache=None, constrain=None):
    """vmap over a leading stack dim (e.g. experts): x [E, ..., d_in],
    W [E, d_out, d_in], adapter leaves stacked on dim 0; ``bias`` /
    ``base_sq_cache`` (both [E, d_out] when given), ``training`` and
    ``constrain`` are forwarded so expert/layer stacks hit the same cached
    base-norm fast path — and the same SPMD-aware matmul-fused compose —
    as the unstacked call. ``constrain`` is a per-slice plan/callable (the
    stack dim is the vmap axis; specs describe the unstacked shapes)."""
    def one(xe, we, ad, be, bq):
        return dora_linear(xe, we, ad, cfg, bias=be, training=training,
                           base_sq_cache=bq, constrain=constrain)

    return jax.vmap(
        one,
        in_axes=(0, 0, 0,
                 None if bias is None else 0,
                 None if base_sq_cache is None else 0),
    )(x, W, adapter, bias, base_sq_cache)


def check_tenant_groups(tenant_groups, batch: int) -> tuple:
    """Validate a multi-tenant grouping: a tuple of ``(start, size)`` row
    blocks that tile ``[0, batch)`` contiguously in order (the server sorts
    request rows by adapter before building the step). Static — runs at
    trace time, so a bad grouping fails at step-build, not mid-decode."""
    groups = tuple((int(s), int(n)) for s, n in tenant_groups)
    if not groups:
        raise ValueError("tenant_groups must name at least one group")
    expect = 0
    for k, (start, size) in enumerate(groups):
        if start != expect or size < 1:
            raise ValueError(
                f"tenant_groups must tile the batch contiguously: group "
                f"{k} is (start={start}, size={size}) but rows 0..{expect} "
                f"are covered so far (groups={groups})")
        expect = start + size
    if expect != batch:
        raise ValueError(
            f"tenant_groups {groups} cover {expect} rows, batch has {batch}")
    return groups


def dora_linear_grouped(x, W, adapter: dict[str, Any], cfg: DoRAConfig,
                        tenant_groups, *, bias=None, training: bool = False,
                        constrain=None):
    """Multi-tenant adapted linear: one call serves a batch whose rows use
    per-row adapters out of a K-stacked serving tree (x [B, ..., d_in]).

    ``adapter`` leaves carry a leading tenant dim K (``stack_adapter_
    states``) and MUST be a folded serving tree — ``"g"`` and ``"gsB"``
    from ``precompute_adapter_state(fold_gsb=True)`` — so the per-group
    work is exactly the homogeneous broadcast-free decode compose: zero
    factored-norm work per token, and each row reads its own adapter state
    once (the cache-hit path prices identically to single-tenant cached
    decode — gated in ``scripts/check_bench_drift.py``).

    ``tenant_groups`` selects one of TWO grouping contracts:

    - **Static** (a tuple of ``(start, size)`` row blocks): grouping is a
      compile-time signature; each group's rows are a contiguous static
      slice run through the *same ops as the homogeneous path*, so a
      mixed-adapter batch is bitwise-equal (fp32) to serving each tenant
      sequentially with its own precomputed state — for groups of ≥ 2
      rows (XLA's single-row matmuls take a gemv path whose reduction
      order differs; 1-row groups are allclose, see docs/numerics.md).
      One executable per tenant-mix signature.
    - **Dynamic** (a TRACED int32 ``[B]`` array of per-row stack
      positions): the fleet-serving path. Every tenant's contribution is
      computed by ONE K-batched contraction (reduction order independent
      of the index values) and each row's result is then a pure gather
      (:func:`repro.core.compose.select_tenant`) — so admission and
      retirement change VALUES, never the compile signature: one decode
      executable serves every tenant mix. Per-row results are BITWISE
      per-tenant-sequential serving (the select touches no arithmetic);
      the price is K× adapter-path FLOPs per call, the XLA-expressible
      form of the S-LoRA gathered-BGMV kernel (a Pallas gather-BGMV is
      the TPU-tier residual, ROADMAP).
    """
    if training:
        raise ValueError(
            "dora_linear_grouped is a serving-only path: the grouped "
            "compose consumes precomputed per-tenant state ('g'/'gsB') "
            "that is stale the moment A/B/m change. Train per-tenant on "
            "the raw adapter trees.")
    missing = [k for k in ("g", "gsB") if k not in adapter]
    if missing:
        raise ValueError(
            f"multi-tenant grouped serving needs the FOLDED per-tenant "
            f"state (missing {missing!r} leaves): precompute each "
            f"tenant with precompute_adapter_state(..., fold_gsb=True) "
            f"(AdapterStateCache.for_serving does) and stack with "
            f"stack_adapter_states before building the grouped step.")
    A, g, gsB = adapter["A"], adapter["g"], adapter["gsB"]
    if W.ndim > 2:
        raise NotImplementedError(
            "grouped multi-tenant serving of stacked/expert weights "
            f"(W rank {W.ndim}) is not supported")
    if not isinstance(tenant_groups, (tuple, list)):
        return _dora_linear_dyn(x, W, A, g, gsB, tenant_groups, bias=bias,
                                constrain=constrain)
    groups = check_tenant_groups(tenant_groups, x.shape[0])
    K = A.shape[0]
    if len(groups) != K:
        raise ValueError(
            f"{len(groups)} tenant groups but the stacked adapter tree "
            f"carries K={K} tenants")
    plan_sh = as_compose_sharding(constrain)
    cfn = plan_sh if plan_sh is not None else constrain

    W = jax.lax.stop_gradient(W)
    y_base = x @ W.T
    if cfn is not None:
        y_base = cfn(y_base)
    y32 = y_base.astype(_F32)
    contract = (((x.ndim - 1,), (1,)), ((), ()))
    deltas = []
    for k, (start, size) in enumerate(groups):
        # Static row block, static tenant index: the ops below are the
        # SAME dots/elementwise the homogeneous gsB fast path runs on a
        # batch of `size` rows — bitwise parity by construction.
        xk = jax.lax.slice_in_dim(x, start, start + size, axis=0)
        hk = xk @ jax.lax.stop_gradient(A[k]).T
        gk = jax.lax.stop_gradient(g[k]).astype(_F32)
        gsBk = jax.lax.stop_gradient(gsB[k])
        if plan_sh is not None and plan_sh.b_dout_axes and gsBk.ndim == 2:
            gsBk = plan_sh.constrain_b(gsBk)
        tk = jax.lax.dot_general(hk.astype(_F32), gsBk.astype(_F32),
                                 contract, preferred_element_type=_F32)
        yk = jax.lax.slice_in_dim(y32, start, start + size, axis=0)
        deltas.append(((gk - 1.0) * yk + tk).astype(y_base.dtype))
    y = y_base + jnp.concatenate(deltas, axis=0)
    if bias is not None:
        y = y + bias
    return y


def _dora_linear_dyn(x, W, A, g, gsB, idx, *, bias=None, constrain=None):
    """Traced dynamic grouped compose (fleet serving): per-row adapters
    selected by a traced int32 stack position ``idx`` [B].

    Bitwise contract (locked in tests/test_engine.py + tests/test_
    property.py): the K-batched einsums below reduce over the SAME axes
    in the SAME order as the homogeneous gsB fast path's ``x @ Aᵀ`` /
    fp32 ``h·gsBᵀ`` for every stacked k, and the per-row select is a pure
    gather — so row b's output is bitwise ``dora_linear`` under adapter
    ``idx[b]``. The g term is row-local elementwise, applied per row from
    the gathered ``g[idx]``."""
    from repro.core.compose import select_tenant
    if x.ndim != 3:
        raise NotImplementedError(
            f"dynamic grouped serving expects [B, S, d_in] activations "
            f"(got ndim={x.ndim}); the serving steps always run the "
            f"model's batched token layout")
    idx = jnp.asarray(idx, jnp.int32)
    plan_sh = as_compose_sharding(constrain)
    cfn = plan_sh if plan_sh is not None else constrain
    W = jax.lax.stop_gradient(W)
    y_base = x @ W.T
    if cfn is not None:
        y_base = cfn(y_base)
    y32 = y_base.astype(_F32)
    A = jax.lax.stop_gradient(A)
    gsB = jax.lax.stop_gradient(gsB)
    g = jax.lax.stop_gradient(g)
    # All-K down-projection, THEN the gather: [B, S, K, r]. One gemm over
    # the shared d_in reduction — the selected slice is bitwise x @ A[k]ᵀ.
    h_all = jnp.einsum("bsd,krd->bskr", x, A)
    h = select_tenant(h_all, idx)                       # [B, S, r]
    # All-K folded up-projection in fp32 (preferred_element_type pins the
    # accumulator exactly like the homogeneous path's dot_general).
    t_all = jnp.einsum("bsr,kor->bsko", h.astype(_F32), gsB.astype(_F32),
                       preferred_element_type=_F32)     # [B, S, K, d_out]
    t = select_tenant(t_all, idx)                       # [B, S, d_out]
    g_row = jnp.take(g.astype(_F32), idx, axis=0)       # [B, d_out]
    delta = ((g_row[:, None, :] - 1.0) * y32 + t).astype(y_base.dtype)
    y = y_base + delta
    if bias is not None:
        y = y + bias
    return y


def stack_adapter_states(states, *, axis: int = 0):
    """Stack K congruent per-tenant serving trees leaf-wise along a new
    tenant dim at ``axis`` (0 for bare adapter leaves; the model-level
    trees from ``make_precompute_step`` use axis=1 so the scan dim stays
    leading: leaves go [n_scan, ...] → [n_scan, K, ...])."""
    states = list(states)
    if not states:
        raise ValueError("need at least one per-tenant state to stack")
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=axis), *states)


# ---------------------------------------------------------------------------
# Frozen-adapter serving state (decode does zero norm work per token).
# ---------------------------------------------------------------------------

def _is_adapter_leaf(node) -> bool:
    return isinstance(node, dict) and {"A", "B", "m"} <= set(node.keys())


def precompute_adapter_state(params, adapters, cfg: DoRAConfig, *,
                             act_dtype=None, fold_gsb: bool = False):
    """Compute the per-adapter serving state once for a frozen adapter set.

    Walks the adapter tree alongside the congruent ``params`` tree and
    returns a NEW adapter tree whose leaves additionally carry

      - ``"g"``  — fp32 [d_out] magnitude scale m / max(||W+sBA||_row, ε),
        computed with the exact runtime eps (``act_dtype`` must match the
        activation dtype the model runs in, else g is not bitwise-equal to
        the recomputed one);
      - ``"gsB"`` (``fold_gsb=True`` only) — [d_out, r] with g·s folded
        into B, enabling the broadcast-free decode compose. The product is
        formed in fp32 and stored in ``act_dtype`` (fp32 when None): the
        up-projection dot reads it at the activation precision, so a bf16
        model keeps one bf16 copy instead of re-rounding an fp32 one on
        every call. Off by default because the folded evaluation order
        differs from the canonical ``s·lora``-first form by rounding: a
        last ulp at fp32, the bf16 rounding of ``gsB`` at bf16 (see
        docs/numerics.md).

    Stacked leaves ([n_scan, ...] / experts) are handled by vmapping over
    the leading dims. The returned tree is for **serving only**: prefill
    and decode skip the factored norm entirely, and ``dora_linear``
    raises if the tree reaches a ``training=True`` call (the invalidation
    contract — any update to A/B/m invalidates the cache, so rebuild the
    state after each training step before serving again).
    """
    act_dtype = act_dtype if act_dtype is not None else _F32
    eps = _norm.dtype_eps(act_dtype)

    def leaf_state(W, ad):
        if W.ndim > 2:
            return jax.vmap(leaf_state)(W, ad)
        w_norm = compute_weight_norm(W, ad["A"], ad["B"], cfg,
                                     base_sq_cache=ad.get("base_sq"))
        g = _compose.magnitude_scale(ad["m"], w_norm, eps)
        # Strip any prior serving state first: re-precomputing a folded
        # tree with fold_gsb=False must not leave a stale "gsB" behind
        # (dora_linear would silently prefer it over the bitwise path).
        out = {k: v for k, v in ad.items() if k not in ("g", "gsB")}
        out["g"] = jax.lax.stop_gradient(g)
        if fold_gsb:
            gsB = (g * cfg.scaling)[:, None] * ad["B"].astype(_F32)
            out["gsB"] = jax.lax.stop_gradient(gsB.astype(act_dtype))
        return out

    def walk(p_node, a_node):
        if _is_adapter_leaf(a_node):
            return leaf_state(p_node, a_node)
        return {k: walk(p_node[k], v) for k, v in a_node.items()}

    return walk(params, adapters)


def invalidate_adapter_state(adapters):
    """Strip the serving-state leaves ("g"/"gsB") from an adapter tree,
    returning the raw trainable tree — the inverse of
    :func:`precompute_adapter_state`."""
    if _is_adapter_leaf(adapters):
        return {k: v for k, v in adapters.items() if k not in ("g", "gsB")}
    return {k: invalidate_adapter_state(v) for k, v in adapters.items()}


@dataclasses.dataclass(frozen=True)
class DoRAParamSpec:
    """Bookkeeping for one adapted weight: used by optimizer masking and
    sharding-rule generation."""
    path: str
    d_out: int
    d_in: int
    rank: int
