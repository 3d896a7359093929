"""Step functions (train / prefill / decode) + dry-run input specs.

``make_train_step`` / ``make_prefill_step`` / ``make_decode_step`` build
jit-able pure functions over (params, adapters, ...) with the sharding
rules from :mod:`repro.launch.sharding` attached via in/out_shardings.
``input_specs`` produces ShapeDtypeStruct stand-ins for every model input
(weak-type-correct, shardable, no device allocation) — the dry-run lowers
against these.

Under pjit, the gradient all-reduce over (pod, data), the factored-norm
partial-sum psums over the weight shard axis, and the sequence-parallel
collectives are all derived by the SPMD partitioner from the sharding
rules — the dry-run's compiled HLO is where we verify they are the ones
we designed for (see EXPERIMENTS.md §Dry-run).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, SMOKE_SHAPES, get_config
from repro.compat import tree as ctree
from repro.core import DoRAConfig
from repro.models import (adapter_shapes, cache_shapes, forward,
                          param_shapes)
from repro.models.config import ModelConfig
from repro.launch import sharding as S
from repro.optim import OptimizerConfig, adamw_update

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class StepConfig:
    """Everything the step builders need beyond the model config."""
    dora: DoRAConfig = DoRAConfig(rank=384, alpha=192.0, mode="auto")
    optim: OptimizerConfig = OptimizerConfig()
    # paper §5.1: partial-sequence loss (1024 tokens) matches production
    # RLHF memory profiles and avoids the full-seq logit spike.
    loss_tokens: int | None = None
    grad_accum: int = 1


# ---------------------------------------------------------------------------
# Loss.
# ---------------------------------------------------------------------------

def cross_entropy(logits, labels):
    """Mean token NLL; fp32 logsumexp (V may be sharded — SPMD reduces)."""
    logits32 = logits.astype(_F32)
    lse = jax.scipy.special.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(logits32, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


# ---------------------------------------------------------------------------
# Steps.
# ---------------------------------------------------------------------------

def _under_mesh(mesh, step):
    """``step`` traced under ``mesh``'s abstract mesh, so kernel dispatch
    sees how many devices the program is partitioned over and keeps the
    Pallas kernels XLA cannot partition out of it
    (:func:`repro.core.dispatch.unpartitionable`)."""
    if mesh is None:
        return step

    @functools.wraps(step)
    def traced(*args):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return step(*args)

    return traced


def make_train_step(mcfg: ModelConfig, scfg: StepConfig, mesh=None, *,
                    batch: int, seq: int):
    """(params, adapters, opt_state, batch) -> (adapters', opt_state',
    metrics). Frozen base params receive no gradient and no optimizer
    state."""
    constraint = (S.make_boundary_constraint(
        mesh, batch=batch, seq=seq,
        b_dout_axes=S.row_parallel_b_axes(mcfg, mesh))
        if mesh is not None else None)
    lt = scfg.loss_tokens

    def loss_fn(adapters, params, tokens_or_embeds, labels, is_embeds):
        kw = ({"embeds": tokens_or_embeds} if is_embeds
              else {"tokens": tokens_or_embeds})
        logits, _, aux = forward(
            mcfg, params, adapters, scfg.dora, training=True,
            boundary_constraint=constraint, loss_slice=lt, **kw)
        lbl = labels if lt is None or lt >= labels.shape[1] \
            else labels[:, -lt:]
        return cross_entropy(logits, lbl) + aux

    def train_step(params, adapters, opt_state, batch):
        is_embeds = "embeds" in batch
        x = batch["embeds"] if is_embeds else batch["tokens"]
        labels = batch["labels"]
        ga = scfg.grad_accum
        if ga <= 1:
            loss, grads = jax.value_and_grad(loss_fn)(
                adapters, params, x, labels, is_embeds)
        else:
            # Gradient accumulation: scan over microbatches along batch
            # (paper model benches use ga=8). Keeps activation memory at
            # 1/ga with identical math.
            b = x.shape[0]
            assert b % ga == 0, (b, ga)
            xm = x.reshape((ga, b // ga) + x.shape[1:])
            lm_ = labels.reshape((ga, b // ga) + labels.shape[1:])

            def micro(carry, inp):
                xi, li = inp
                l, g = jax.value_and_grad(loss_fn)(
                    adapters, params, xi, li, is_embeds)
                loss_acc, g_acc = carry
                return (loss_acc + l,
                        ctree.map(jnp.add, g_acc, g)), None

            zeros = ctree.map(lambda a: jnp.zeros(a.shape, _F32),
                                 adapters)
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.zeros((), _F32), zeros), (xm, lm_))
            loss = loss / ga
            grads = ctree.map(lambda g: g / ga, grads)

        new_adapters, new_opt, stats = adamw_update(
            grads, opt_state, adapters, scfg.optim)
        metrics = {"loss": loss, **stats}
        return new_adapters, new_opt, metrics

    return _under_mesh(mesh, train_step)


def make_prefill_step(mcfg: ModelConfig, scfg: StepConfig, mesh=None, *,
                      batch: int, seq: int, padded: bool = False,
                      tenant_groups=None):
    """(params, adapters, batch) -> (last_logits [B, V], cache).

    Processes the full prompt and materializes the KV/SSM cache sized to
    ``seq`` (the serving runtime hands it to the decode step).

    ``padded=True``: shape-bucketed serving — the prompt arrives
    right-padded to ``seq`` and ``batch["prompt_len"]`` carries the TRUE
    prompt length P as an int32 scalar. P is traced, so ONE compiled
    prefill covers every P ≤ seq. The returned logits are gathered at
    position P-1 (the full-vocab head runs on exactly that one row, not
    the padded tail) and the cache length is REWOUND to P so the first
    decode token overwrites the first padded row — without the rewind,
    decode appends after the pad garbage. Only valid for attention caches
    (a rewound "len" masks the stale K/V rows via causality; an SSM state
    has already integrated the pad tokens and cannot rewind).

    ``tenant_groups``: multi-tenant serving — static (start, size) row
    blocks grouping the batch by adapter; the adapter tree must be the
    stacked folded serving state (see ``repro.launch.serve``)."""
    constraint = (S.make_boundary_constraint(
        mesh, batch=batch, seq=seq,
        b_dout_axes=S.row_parallel_b_axes(mcfg, mesh))
        if mesh is not None else None)
    if padded and any(k != "attn" for k in mcfg.layer_kinds()):
        raise ValueError(
            "padded prefill requires attention-only caches: SSM layer "
            "states integrate the padded tokens and cannot be rewound "
            f"(arch {mcfg.name!r} has {mcfg.layer_kinds()})")

    def prefill_step(params, adapters, batch_in):
        is_embeds = "embeds" in batch_in
        kw = ({"embeds": batch_in["embeds"]} if is_embeds
              else {"tokens": batch_in["tokens"]})
        from repro.models import init_cache
        cache = init_cache(mcfg, batch, seq)
        if padded:
            p_len = jnp.asarray(batch_in["prompt_len"], jnp.int32)
            kw["gather_position"] = p_len - 1
        else:
            kw["loss_slice"] = 1
        logits, new_cache, _ = forward(
            mcfg, params, adapters, scfg.dora, cache=cache, training=False,
            boundary_constraint=constraint, tenant_groups=tenant_groups,
            **kw)
        if padded and new_cache is not None:
            new_cache = dict(new_cache)
            new_cache["len"] = p_len.astype(new_cache["len"].dtype)
        return logits[:, -1], new_cache

    return _under_mesh(mesh, prefill_step)


def make_prefill_into_slot_step(mcfg: ModelConfig, scfg: StepConfig,
                                mesh=None, *, seq: int):
    """(params, adapters, cache, batch_in) -> (logits [1, V], cache').

    Continuous-batching admission (see :mod:`repro.launch.engine`):
    prefill ONE new request into row ``batch_in["slot"]`` of a RUNNING
    batch's cache while every other row's state is untouched. ``cache``
    must be a per-row-length cache (``init_cache(..., row_lens=True)``,
    ``"len"`` a [B] vector). ``batch_in``: the prompt right-padded to
    ``seq`` as ``"tokens"`` [1, seq], the true length as ``"prompt_len"``
    (int32 scalar) and the target row as ``"slot"`` (int32 scalar). Slot
    AND prompt_len are traced, so ONE compiled step serves every slot
    index and every prompt length — a request joining mid-decode never
    recompiles.

    The row itself runs the SAME padded batch=1 prefill the static path
    uses (``make_prefill_step(batch=1, padded=True)``), so the inserted
    K/V rows and the first-token logits are bitwise the ones a static
    serve of that request would produce; the row's cache length lands at
    the true P (``cache["len"][slot] = P``), so the first decoded token
    writes at position P.

    This step is ALSO the engine's preempt/resume primitive (PR 7): a
    preempted request re-queues with prompt' = prompt + generated-so-far,
    and re-admission simply prefills prompt' into whatever row frees up —
    no snapshotting of K/V, no extra executable. The resumed stream is
    bitwise the uninterrupted one because this prefill's final-position
    logits equal the plain decode logits at that frontier (same dense
    per-row-frontier attention), and prompt' + remaining budget always
    fits ``seq`` (the displaced budget shrinks exactly as the prompt
    grows).

    Attention-only archs: an SSM state integrates every processed token
    and cannot be rewound to a slot's true prompt length, so
    prefill-into-slot is ill-defined for Mamba/hybrid stacks (raises at
    build time — the engine surfaces this as its admission contract)."""
    kinds = mcfg.layer_kinds()
    if any(k != "attn" for k in kinds):
        raise NotImplementedError(
            f"continuous batching requires attention-only caches: SSM "
            f"states integrate every processed token and cannot rewind "
            f"to a slot's true prompt length, so prefill-into-slot is "
            f"ill-defined (arch {mcfg.name!r} has layer kinds {kinds})")
    row_prefill = make_prefill_step(mcfg, scfg, mesh, batch=1, seq=seq,
                                    padded=True)

    def prefill_into_slot(params, adapters, cache, batch_in):
        logits, row_cache = row_prefill(
            params, adapters, {"tokens": batch_in["tokens"],
                               "prompt_len": batch_in["prompt_len"]})
        slot = jnp.asarray(batch_in["slot"], jnp.int32)
        zero = jnp.zeros((), jnp.int32)

        def insert(big, row):
            # big [n_scan, B, T, H, hd]; row [n_scan, 1, T, H, hd] — the
            # single-row prefill result dropped into the slot's row.
            start = (zero, slot) + (zero,) * (big.ndim - 2)
            return jax.lax.dynamic_update_slice(
                big, row.astype(big.dtype), start)

        new_stack = ctree.map(insert, cache["stack"], row_cache["stack"])
        new_len = cache["len"].at[slot].set(
            jnp.asarray(batch_in["prompt_len"], cache["len"].dtype))
        return logits, {"stack": new_stack, "len": new_len}

    return prefill_into_slot


def make_prefill_chunk_step(mcfg: ModelConfig, scfg: StepConfig,
                            mesh=None, *, chunk: int):
    """(params, adapters, cache, batch_in) -> (logits [1, V], cache').

    CHUNKED admission for the PAGED continuous-batching engine (see
    :mod:`repro.launch.engine`): process ``chunk`` prompt tokens of one
    request into its slot's pages of a RUNNING batch's paged cache, so a
    long prompt is admitted incrementally — interleaved with decode ticks
    — instead of stalling the batch behind one monolithic prefill.

    ``cache`` is the engine's PAGED cache (block pools + ``"pages"``
    table + per-row ``"len"``). ``batch_in``: ``"tokens"`` [1, chunk]
    (the chunk's tokens, right-padded), ``"slot"`` / ``"start"`` /
    ``"chunk_len"`` int32 scalars — ALL traced, so ONE compiled step
    serves every slot, every chunk boundary and every ragged tail: the
    compile surface stays one (chunk-prefill, decode) pair per
    (slots, chunk, signature).

    ``start`` is the HOST's admission frontier for the slot, not the
    device ``len[slot]`` — decode ticks advance the whole [B] length
    vector (admitting rows included), so the device value drifts by one
    per interleaved tick; the chunk must write at the true prompt offset.
    The step runs the forward over a batch-1 VIEW (shared pools, the
    slot's page row, ``len=[start]``), then writes ``len[slot] =
    start + chunk_len`` back into the full vector. The final chunk's
    logits (gathered at ``chunk_len - 1``) are the first-token logits —
    bitwise the padded whole-prompt prefill's, because every q row of a
    causal forward depends only on positions ≤ its own, the gathered
    paged view has the SAME [max_len] reduction extent as the
    rectangular buffer, and masked/unallocated positions contribute
    exactly-0.0 softmax weight in both.

    Attention-only archs, like every continuous-batching step (SSM
    states cannot rewind / re-view)."""
    kinds = mcfg.layer_kinds()
    if any(k != "attn" for k in kinds):
        raise NotImplementedError(
            f"chunked prefill requires attention-only caches: SSM states "
            f"integrate every processed token and cannot be re-viewed at "
            f"a chunk boundary (arch {mcfg.name!r} has layer kinds "
            f"{kinds})")
    constraint = (S.make_boundary_constraint(
        mesh, batch=1, seq=chunk,
        b_dout_axes=S.row_parallel_b_axes(mcfg, mesh))
        if mesh is not None else None)

    def prefill_chunk(params, adapters, cache, batch_in):
        slot = jnp.asarray(batch_in["slot"], jnp.int32)
        start = jnp.asarray(batch_in["start"], jnp.int32)
        c_len = jnp.asarray(batch_in["chunk_len"], jnp.int32)
        view = {
            "stack": cache["stack"],              # shared block pools
            "len": jnp.reshape(start, (1,)),      # host frontier, not
                                                  # the drifted device len
            "pages": jax.lax.dynamic_slice_in_dim(cache["pages"], slot, 1,
                                                  axis=0),
        }
        logits, new_view, _ = forward(
            mcfg, params, adapters, scfg.dora, cache=view, training=False,
            boundary_constraint=constraint, tokens=batch_in["tokens"],
            gather_position=c_len - 1)
        new_len = cache["len"].at[slot].set(
            (start + c_len).astype(cache["len"].dtype))
        return logits[:, -1], {"stack": new_view["stack"],
                               "len": new_len,
                               "pages": cache["pages"]}

    return _under_mesh(mesh, prefill_chunk)


def make_precompute_step(mcfg: ModelConfig, scfg: StepConfig, mesh=None, *,
                         fold_gsb: bool = False):
    """(params, adapters) -> serving adapter tree (jit-able).

    Runs :func:`repro.core.precompute_adapter_state` once per frozen
    adapter set: every adapter leaf gains a cached ``"g"`` (and ``"gsB"``
    when folded) so the prefill/decode steps built below do ZERO
    factored-norm work per call — the whole O(d_out·d_in) norm moves out
    of the token loop. The act_dtype is pinned to the model dtype so the
    cached g is bitwise-identical to the one the uncached forward would
    compute. Invalidation: any training step on the adapters makes the
    returned tree stale; rebuild it (cheap — one norm per adapted layer)
    before serving the updated weights.

    ``mesh``: when set, the cached leaves are pinned to the serving
    shardings (``sharding.adapter_sharding(serving=True)``): ``g``
    congruent with ``m``, and the folded ``gsB`` row-sharded exactly like
    the raw ``B`` — so the broadcast-free decode compose consumes a
    correctly-sharded cached B instead of all-gathering it per token."""
    from repro.core import precompute_adapter_state

    serving_sh = (S.adapter_sharding(mcfg, scfg.dora, mesh, serving=True)
                  if mesh is not None else None)

    def constrain_tree(vals, sh):
        if isinstance(vals, dict):
            return {k: (constrain_tree(v, sh[k]) if k in sh else v)
                    for k, v in vals.items()}
        return jax.lax.with_sharding_constraint(vals, sh)

    def precompute_step(params, adapters):
        tree = precompute_adapter_state(params, adapters, scfg.dora,
                                        act_dtype=mcfg.dtype,
                                        fold_gsb=fold_gsb)
        if serving_sh is not None:
            tree = constrain_tree(tree, serving_sh)
        return tree

    return _under_mesh(mesh, precompute_step)


def make_decode_step(mcfg: ModelConfig, scfg: StepConfig, mesh=None, *,
                     batch: int, tenant_groups=None,
                     dynamic_groups: bool = False):
    """(params, adapters, cache, tokens [B,1]) -> (logits [B,V], cache').

    One new token against a pre-filled cache (the ``decode_*`` /
    ``long_*`` shapes lower THIS, not train_step). The cache's ``"len"``
    is either the scalar of the static serve loop or the [B] per-row
    length vector of the continuous-batching engine — the SAME builder
    compiles both (shape-keyed traces); with per-row lengths every slot
    attends/writes at its own position.

    ``tenant_groups``: multi-tenant serving — the decode batch's rows are
    grouped by adapter (static compile-time signature); the adapter tree
    must be the stacked folded serving state. The grouped step's jaxpr
    contains zero ``dora_wnorm``-tagged ops: a cache hit does no norm
    work (asserted in ``tests/test_serve_multitenant.py``).

    ``dynamic_groups``: fleet serving — each row's adapter is selected by
    the TRACED int32 per-row stack position ``batch_in["adapter_idx"]``
    ([B]) out of the K-stacked adapter tree, so tenant churn changes
    VALUES, never this step's compile signature: ONE decode executable
    serves every tenant mix (see ``repro.core.dora_linear_grouped``).
    Mutually exclusive with a static ``tenant_groups``."""
    if dynamic_groups and tenant_groups is not None:
        raise ValueError(
            "dynamic_groups=True takes the per-row adapter index from "
            "batch_in['adapter_idx']; a static tenant_groups signature "
            "cannot be given at the same time")

    def decode_step(params, adapters, cache, batch_in):
        is_embeds = "embeds" in batch_in
        kw = ({"embeds": batch_in["embeds"]} if is_embeds
              else {"tokens": batch_in["tokens"]})
        tg = (jnp.asarray(batch_in["adapter_idx"], jnp.int32)
              if dynamic_groups else tenant_groups)
        logits, new_cache, _ = forward(
            mcfg, params, adapters, scfg.dora, cache=cache,
            training=False, tenant_groups=tg, **kw)
        return logits[:, -1], new_cache

    return _under_mesh(mesh, decode_step)


def make_draft_step(mcfg: ModelConfig, scfg: StepConfig, mesh=None, *,
                    batch: int):
    """(params, cache, tokens [B,1]) -> (logits [B,V], cache').

    The speculative-draft step: one decode token through the BASE model
    only — the adapter tree is the empty dict, so every projection takes
    the ``maybe_dora`` base-matmul short-circuit. Zero ``dora_wnorm``
    work, zero gsB/grouped-adapter ops, and no adapter argument at all:
    one compiled executable serves every tenant mix (the draft is
    adapter-blind by design — the full grouped DoRA path only runs in the
    verify step). The cache contract is the decode step's: per-row
    ``"len"`` vector, each slot writes/attends at its own position.

    Draft K/V writes are base-path values at the drafted positions; the
    verify step re-writes those exact positions with full-path K/V, so
    nothing base-flavored survives into the committed cache (see
    ``launch/engine.py``)."""

    def draft_step(params, cache, batch_in):
        logits, new_cache, _ = forward(
            mcfg, params, {}, scfg.dora, cache=cache, training=False,
            tokens=batch_in["tokens"])
        return logits[:, -1], new_cache

    return _under_mesh(mesh, draft_step)


def make_verify_step(mcfg: ModelConfig, scfg: StepConfig, mesh=None, *,
                     batch: int, window: int, tenant_groups=None,
                     dynamic_groups: bool = False):
    """(params, adapters, cache, tokens [B,window]) ->
    (logits [B,window,V], cache').

    The speculative-verify step: score ``window`` = k+1 positions per row
    in ONE batched forward through the FULL grouped DoRA path — the same
    adapter compose (precomputed ``g``, folded ``gsB``, static tenant
    groups) the plain decode step runs, so greedy acceptance against
    these logits is bitwise the plain-decode token stream. Logits are
    returned for EVERY window position (no gather/loss_slice): position j
    scores the draft token at j+1 and supplies the correction token when
    the draft diverges.

    The cache write covers the whole window at each row's own frontier
    (per-row ``"len"`` + the per-row causal mask in
    ``models/layers.py``), overwriting the draft step's base-path K/V
    with full-path values. The ENGINE owns the rewind: it re-syncs
    ``"len"`` to each row's accepted frontier after this step (the step
    itself advances ``len`` by ``window`` like any forward).

    ``dynamic_groups``: as for :func:`make_decode_step` — per-row
    adapters from the traced ``batch_in["adapter_idx"]``, one verify
    executable per window across every tenant mix."""
    if dynamic_groups and tenant_groups is not None:
        raise ValueError(
            "dynamic_groups=True takes the per-row adapter index from "
            "batch_in['adapter_idx']; a static tenant_groups signature "
            "cannot be given at the same time")

    def verify_step(params, adapters, cache, batch_in):
        tg = (jnp.asarray(batch_in["adapter_idx"], jnp.int32)
              if dynamic_groups else tenant_groups)
        logits, new_cache, _ = forward(
            mcfg, params, adapters, scfg.dora, cache=cache,
            training=False, tenant_groups=tg,
            tokens=batch_in["tokens"])
        return logits, new_cache

    return _under_mesh(mesh, verify_step)


# ---------------------------------------------------------------------------
# Dry-run input specs (ShapeDtypeStructs; nothing allocated).
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(mcfg: ModelConfig, *, batch: int, seq: int, kind: str):
    """Model-input ShapeDtypeStructs for one (arch, shape) cell.

    ``[vlm]``/``[audio]`` archs take precomputed patch/frame embeddings
    from the (stubbed) modality frontend; LM archs take token ids."""
    if kind == "decode":
        seq_in = 1
    else:
        seq_in = seq
    if mcfg.frontend:
        b = {"embeds": _sds((batch, seq_in, mcfg.d_model), mcfg.dtype)}
    else:
        b = {"tokens": _sds((batch, seq_in), jnp.int32)}
    if kind == "train":
        b["labels"] = _sds((batch, seq), jnp.int32)
    return b


def cell_specs(arch: str, shape_name: str, mesh, *, smoke: bool = False,
               scfg: StepConfig | None = None):
    """Everything the dry-run needs for one (arch × shape) cell:
    (step_fn, example_args, in_shardings, out_shardings placeholders).

    Returns a dict with keys: step, args, in_shardings, kind, mcfg.
    """
    mcfg = get_config(arch, smoke=smoke)
    shape = (SMOKE_SHAPES if smoke else SHAPES)[shape_name]
    scfg = scfg or StepConfig()
    B, T = shape.global_batch, shape.seq_len
    kind = shape.kind

    # NOTE (H2.4, refuted): chunk-local MoE dispatch (moe_seq_chunks=tp)
    # was measured to INCREASE collective time under GSPMD — the merged
    # (data x model) token dim is not localized by the partitioner and
    # the capacity buffers reshard anyway (EXPERIMENTS.md §Perf cell 2).
    # The mechanism stays available on ModelConfig for the shard_map
    # expert-parallel path; default off.

    p_sh = S.param_sharding(mcfg, mesh)
    a_sh = S.adapter_sharding(mcfg, scfg.dora, mesh)
    p_sds = param_shapes(mcfg)
    a_sds = adapter_shapes(mcfg, scfg.dora)
    b_sds = batch_specs(mcfg, batch=B, seq=T, kind=kind)
    b_sh = {k: (S.batch_sharding(mesh, batch=B) if v.ndim == 2
                else NamedSharding(mesh, S.activation_spec(
                    mesh, batch=B, seq=v.shape[1])))
            for k, v in b_sds.items()}

    if kind == "train":
        opt_sds = {
            "mu": ctree.map(
                lambda s: _sds(s.shape, _F32), a_sds),
            "nu": ctree.map(
                lambda s: _sds(s.shape, _F32), a_sds),
            "count": _sds((), jnp.int32),
        }
        opt_sh = S.opt_state_sharding(a_sh, mesh, a_sds)
        step = make_train_step(mcfg, scfg, mesh, batch=B, seq=T)
        args = (p_sds, a_sds, opt_sds, b_sds)
        in_sh = (p_sh, a_sh, opt_sh, b_sh)
        out_sh = (a_sh, opt_sh, None)
        donate = (1, 2)   # adapters, opt_state update in place
    elif kind == "prefill":
        step = make_prefill_step(mcfg, scfg, mesh, batch=B, seq=T)
        args = (p_sds, a_sds, b_sds)
        in_sh = (p_sh, a_sh, b_sh)
        c_sh = S.cache_sharding(mcfg, mesh, batch=B)
        out_sh = (None, c_sh)
        donate = ()
    else:  # decode
        c_sds = cache_shapes(mcfg, B, T)
        # the pre-filled cache: len == T - 1, one slot free for the token
        c_sh = S.cache_sharding(mcfg, mesh, batch=B)
        step = make_decode_step(mcfg, scfg, mesh, batch=B)
        args = (p_sds, a_sds, c_sds, b_sds)
        in_sh = (p_sh, a_sh, c_sh, b_sh)
        out_sh = (None, c_sh)
        donate = (2,)     # cache updated in place (as the serve loop does)
    return {"step": step, "args": args, "in_shardings": in_sh,
            "out_shardings": out_sh, "kind": kind, "mcfg": mcfg,
            "shape": shape, "donate": donate}
