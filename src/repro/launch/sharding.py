"""Sharding rules for the production mesh, with divisibility fallback.

Strategy:

  - **Base weights**: TP over ``model`` on the hidden/head dim + FSDP over
    (``pod``, ``data``) on the other dim. Frozen → no optimizer state, no
    gradient collectives for them.
  - **Adapters (A, B, m)**: sharded congruent with their base weight's TP
    axis only (B row-sharded when W is out-sharded; A col-sharded when W is
    in-sharded); never FSDP-sharded (they are small); DP-replicated so the
    adapter grad all-reduce is the only cross-pod gradient traffic.
  - **Batch**: sharded over (``pod``, ``data``).
  - **Activations**: sequence-sharded over ``model`` at scan-unit
    boundaries (sequence parallelism) so saved remat residuals scale with
    1/(dp·tp).
  - **Decode caches**: batch → (pod, data), KV seq → ``model``.

Every rule goes through :func:`pick_axes`, which drops to progressively
smaller axis sets (and finally replication) when a dim is not divisible —
e.g. qwen2-moe's 60 experts fall from (pod,data)=32 to pod=2; GQA KV
projections with kv_heads < 16 replicate over ``model`` (Megatron GQA
convention) instead of head-splitting. These fallbacks are exactly what the
multi-pod dry-run exercises.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compat import tree as ctree
from repro.core import DoRAConfig
from repro.core import sharding as _csh
from repro.models import lm as _lm
from repro.models.config import ModelConfig

# Role → ordered axis-set preferences (first divisible wins; tuples of mesh
# axes; missing axes are dropped for the single-pod mesh).
ROLE_PREFS: dict[str, tuple[tuple[str, ...], ...]] = {
    "tp": (("model",),),
    # Weight FSDP default is POD-ONLY (H1.3 — measured ~neutral on
    # collectives; the big ARs turned out to be TP row-parallel, not
    # FSDP): per-chip weights = total/(16 model x 2 pod), data axis
    # carries batch parallelism.
    "fsdp": (("pod",),),
    # Large-model FSDP (H3.5): models whose TP-sharded weights exceed the
    # per-chip budget (> ~6 GB at model=16) shard d_in over data too —
    # the 72B class cannot replicate weights within a pod.
    "fsdp_data": (("pod", "data"), ("data",), ("pod",)),
    # Weights with NO TP dim (e.g. llama4's 40 Q-heads on a 16-way model
    # axis) would otherwise replicate entirely; shard their d_out over
    # (pod, data) instead — GSPMD all-gathers the (small) weight before
    # the matmul, which costs ~weight-bytes/layer of link traffic instead
    # of activation-sized partial-sum all-reduces (H2.2).
    "fsdp_gather": (("pod", "data"), ("data",), ("pod",)),
    "expert": (("pod", "data"), ("data",), ("pod",)),
    "repl": (),
}

# Per-chip weight budget above which d_in FSDP extends to the data axis.
_FSDP_DATA_THRESHOLD_BYTES = 6 * 2**30

DP_AXES = ("pod", "data")


def dp_axes(mesh) -> tuple[str, ...]:
    return tuple(a for a in DP_AXES if a in mesh.axis_names)


def pick_axes(size: int, role: str, mesh, used: set[str]) -> Any:
    """First preference whose axes all exist, don't collide with ``used``,
    and whose product divides ``size``. None = replicate this dim."""
    for axes in ROLE_PREFS.get(role, ()):
        axes = tuple(a for a in axes if a in mesh.axis_names)
        if not axes or any(a in used for a in axes):
            continue
        prod = 1
        for a in axes:
            prod *= mesh.shape[a]
        if size % prod == 0:
            used.update(axes)
            return axes if len(axes) > 1 else axes[0]
    return None


def spec_for(shape: tuple[int, ...], roles: tuple[str, ...], mesh) -> P:
    assert len(shape) == len(roles), (shape, roles)
    used: set[str] = set()
    return P(*(pick_axes(d, r, mesh, used) for d, r in zip(shape, roles)))


# ---------------------------------------------------------------------------
# Role tables. Keyed by leaf name; roles are per-dim, for the UNSTACKED
# shape (the stacked scan dim is prepended as "repl" automatically).
# ---------------------------------------------------------------------------

def _attn_tp_ok(mcfg: ModelConfig, mesh) -> tuple[bool, bool]:
    """(q sharded?, kv sharded?) — heads must split the model axis so the
    attention core stays head-aligned (Megatron GQA convention)."""
    tp = mesh.shape.get("model", 1)
    q_ok = mcfg.num_heads > 0 and mcfg.num_heads % tp == 0
    kv_ok = mcfg.num_kv_heads > 0 and mcfg.num_kv_heads % tp == 0
    return q_ok, kv_ok


def _fsdp_role(mcfg: ModelConfig, mesh) -> str:
    """'fsdp_data' for models whose TP-sharded weights exceed the
    per-chip budget; 'fsdp' (pod-only) otherwise."""
    tp = dict(mesh.shape).get("model", 1)
    per_chip = mcfg.count_params() * 2 / max(tp, 1)  # bf16
    return "fsdp_data" if per_chip > _FSDP_DATA_THRESHOLD_BYTES else "fsdp"


def leaf_roles(mcfg: ModelConfig, name: str, ndim: int, mesh) \
        -> tuple[str, ...]:
    """Per-dim sharding roles for a (non-stacked) param leaf."""
    q_ok, kv_ok = _attn_tp_ok(mcfg, mesh)
    fsdp = _fsdp_role(mcfg, mesh)
    table: dict[str, tuple[str, ...]] = {
        # embeddings / head: vocab TP (V-sharded logits → parallel CE loss),
        # FSDP on d_model.
        "embed": ("tp", fsdp),
        "head": ("tp", fsdp),
        # attention; non-TP-able projections get gather-FSDP on d_out
        "wq": ("tp" if q_ok else "fsdp_gather",
               fsdp if q_ok else "repl"),
        "wk": ("tp" if kv_ok else "fsdp_gather",
               fsdp if kv_ok else "repl"),
        "wv": ("tp" if kv_ok else "fsdp_gather",
               fsdp if kv_ok else "repl"),
        "wo": ((fsdp, "tp") if q_ok else ("fsdp_gather", "repl")),
        "wq_bias": ("tp" if q_ok else "repl",),
        "wk_bias": ("tp" if kv_ok else "repl",),
        "wv_bias": ("tp" if kv_ok else "repl",),
        # dense MLP
        "w_gate": ("tp", fsdp),
        "w_up": ("tp", fsdp),
        "w_down": (fsdp, "tp"),
        "w_up_bias": ("tp",),
        "w_down_bias": ("repl",),
        # MoE (stacked experts): expert dim FSDP-ish, hidden dim TP
        "router": ("repl", "repl"),
        "gate": ("expert", "tp", fsdp),
        "up": ("expert", "tp", fsdp),
        "down": ("expert", fsdp, "tp"),
        "shared_gate": ("repl", "repl"),
        # mamba: d_inner is the TP axis
        "in_proj": ("tp", fsdp),
        "out_proj": (fsdp, "tp"),
        "x_proj": ("repl", "tp"),
        "dt_proj": ("tp", "repl"),
        "dt_bias": ("tp",),
        "A_log": ("tp", "repl"),
        "skip_d": ("tp",),
        "conv_w": ("repl", "tp"),
        "conv_b": ("tp",),
        # Jamba's inner norms: scales of the dt_rank / state dims, which
        # no TP axis splits
        "dt_norm": ("repl",),
        "b_norm": ("repl",),
        "c_norm": ("repl",),
    }
    if name in table:
        roles = table[name]
        assert len(roles) == ndim, (name, roles, ndim)
        return roles
    # norm scales, q_norm/k_norm, anything small: replicate.
    return ("repl",) * ndim


def param_sharding(mcfg: ModelConfig, mesh):
    """NamedSharding tree matching ``param_shapes(mcfg)``."""
    shapes = _lm.param_shapes(mcfg)

    def walk(tree, in_stack):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = walk(v, in_stack or k == "stack")
            else:
                nd = len(v.shape) - (1 if in_stack else 0)
                roles = leaf_roles(mcfg, k, nd, mesh)
                if in_stack:
                    roles = ("repl",) + roles
                out[k] = NamedSharding(mesh, spec_for(v.shape, roles, mesh))
        return out

    return walk(shapes, False)


def adapter_sharding(mcfg: ModelConfig, dcfg: DoRAConfig, mesh,
                     targets=_lm.DEFAULT_DORA_TARGETS, *,
                     serving: bool = False):
    """NamedSharding tree matching ``adapter_shapes``.

    Adapters shard CONGRUENT with their base weight on the matching dim
    (A col-sharded like W's d_in, B/m row-sharded like W's d_out); the
    rank dim replicates. At r = 384 on a 30-70B model the adapters are
    multi-GB, so — unlike low-rank LoRA — they cannot be DP-replicated on
    16 GB chips; the factored norm's distributed accumulation (the
    paper's FSDP2 future-work item) is what makes the d_in sharding of
    A/W work without an all-gather.

    ``serving=True`` additionally emits the frozen-adapter serving-state
    leaves written by ``precompute_adapter_state``: ``"g"`` [n_scan,
    d_out] shards like ``m`` (congruent with W's d_out), and ``"gsB"``
    [n_scan, d_out, r] shards like ``B`` — the folded cached B must land
    row-sharded exactly where the raw B lives, or the broadcast-free
    decode compose would all-gather it every token.
    """
    shapes = _lm.adapter_shapes(mcfg, dcfg, targets)

    def walk(tree):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict) and "A" not in v:
                out[k] = walk(v)
                continue
            # v = {"A": sds, "B": sds, "m": sds}; base weight name = k
            roles = leaf_roles(mcfg, k, 2, mesh)
            out[k] = {
                # A [n_scan, r, d_in]: congruent with W's d_in
                "A": NamedSharding(mesh, spec_for(
                    v["A"].shape, ("repl", "repl", roles[-1]), mesh)),
                # B [n_scan, d_out, r]: congruent with W's d_out
                "B": NamedSharding(mesh, spec_for(
                    v["B"].shape, ("repl", roles[0], "repl"), mesh)),
                "m": NamedSharding(mesh, spec_for(
                    v["m"].shape, ("repl", roles[0]), mesh)),
            }
            if "base_sq" in v:  # H3.2 cached ||W||²_row: like m
                out[k]["base_sq"] = NamedSharding(mesh, spec_for(
                    v["base_sq"].shape, ("repl", roles[0]), mesh))
            if serving:
                out[k]["g"] = NamedSharding(mesh, spec_for(
                    v["m"].shape, ("repl", roles[0]), mesh))
                out[k]["gsB"] = NamedSharding(mesh, spec_for(
                    v["B"].shape, ("repl", roles[0], "repl"), mesh))
        return out

    return {"stack": walk(shapes["stack"])}


def fleet_stack_sharding(adapter_shardings, mesh):
    """Sharding tree for a device-resident FLEET STACK of serving states
    (dynamic grouped decode, see ``DecodeEngine(dynamic_grouping=True)``).

    The fleet stack holds K tenants' folded serving leaves stacked on a
    new axis 1 — ``[n_scan, K, ...]``, the ``stack_adapter_states(...,
    axis=1)`` layout — and is indexed per row by a TRACED int32 position,
    so the K axis must be REPLICATED: sharding it would turn the
    per-row ``take_along_axis`` gather into cross-device traffic on the
    decode hot path. Every other dim keeps the per-tenant serving
    sharding (A congruent with W's d_in, g/gsB row-sharded on d_out)
    unchanged — insert the tenant axis, touch nothing else."""
    def stackify(sh):
        spec = list(sh.spec)
        spec.insert(1, None)
        return NamedSharding(mesh, P(*spec))
    return ctree.map(stackify, adapter_shardings)


def opt_state_sharding(adapter_shardings, mesh, adapter_shapes=None):
    """AdamW moments: adapter sharding + ZeRO-1-style data-sharding.

    Moments are only touched elementwise in the update, never by a
    matmul, so they can shard over ``data`` even where the parameter
    cannot (H2.3): GSPMD reduce-scatters the incoming gradient and
    all-gathers the updated parameter — the ZeRO-1 schedule — trading
    ~param-bytes of link traffic per step for an 8x cut in fp32 moment
    memory. The largest still-replicated dim that divides the data axis
    takes the sharding.
    """
    data = dict(mesh.shape).get("data", 1)

    def shard_moment(sh, sds):
        if adapter_shapes is None or data <= 1:
            return sh
        spec = list(sh.spec) + [None] * (len(sds.shape) - len(sh.spec))
        used = set()
        for e in spec:
            for a in (e if isinstance(e, tuple) else (e,)):
                if a:
                    used.add(a)
        if "data" in used:
            return sh
        cands = [(d, i) for i, (d, e) in enumerate(zip(sds.shape, spec))
                 if e is None and d % data == 0]
        if not cands:
            return sh
        _, i = max(cands)
        spec[i] = "data"
        return NamedSharding(mesh, P(*spec))

    if adapter_shapes is not None:
        moments = ctree.map(shard_moment, adapter_shardings,
                               adapter_shapes)
    else:
        moments = adapter_shardings
    return {
        "mu": moments,
        "nu": moments,
        "count": NamedSharding(mesh, P()),
    }


def _dp_entry(mesh, batch: int):
    """The batch-dim PartitionSpec entry: (pod, data) when divisible,
    replicated otherwise (e.g. long_500k's global_batch=1)."""
    dp = dp_axes(mesh)
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    if size == 0 or batch % size != 0:
        dp = ()
    return dp if len(dp) > 1 else (dp[0] if dp else None)


def batch_spec(mesh, *, batch: int) -> P:
    return P(_dp_entry(mesh, batch), None)


def batch_sharding(mesh, *, batch: int):
    """tokens/labels [B, S]: batch over (pod, data) when divisible."""
    return NamedSharding(mesh, batch_spec(mesh, batch=batch))


def activation_spec(mesh, *, batch: int, seq: int) -> P:
    """[B, S, D] activations: batch over dp, sequence over model (SP)."""
    bdim = _dp_entry(mesh, batch)
    tp = dict(mesh.shape).get("model", 1)
    sdim = "model" if seq % tp == 0 and seq > 1 else None
    return P(bdim, sdim, None)


def row_parallel_b_axes(mcfg: ModelConfig, mesh) -> tuple[str, ...]:
    """Mesh axes FSDP-sharding the d_out of the ROW-PARALLEL adapted
    weights (wo / w_down — the only call sites that receive the boundary
    constraint). Their adapters' B/m shard d_out over these axes
    (``adapter_sharding`` uses the weight's dim-0 role), but the module
    output's feature dim does not — the ROADMAP ``b_spec`` gap. The axes
    are threaded into the compose plan (``ComposeSharding.b_dout_axes``)
    so the folded-gsB serving path declares B's true layout and the
    shard-local kernel path falls back cleanly instead of silently
    gathering at the shard_map boundary.

    Derived from each weight's ACTUAL dim-0 role (wo degrades to
    'fsdp_gather' when the heads don't divide the model axis — a
    different axis set than w_down's 'fsdp'). The one boundary-constraint
    plan is shared by both call sites, so when the two weights disagree
    the declaration is dropped entirely (legacy behavior, never a WRONG
    pin). Size-1 axes are dropped too (replication in disguise — they
    must not flip kernel expressibility)."""
    per_weight = []
    for name in ("wo", "w_down"):
        role = leaf_roles(mcfg, name, 2, mesh)[0]
        axes = pick_axes(mcfg.d_model, role, mesh, set())
        if axes is None:
            axes = ()
        elif not isinstance(axes, tuple):
            axes = (axes,)
        per_weight.append(tuple(a for a in axes if mesh.shape[a] > 1))
    if per_weight[0] != per_weight[1]:
        return ()
    return per_weight[0]


def make_boundary_constraint(mesh, *, batch: int, seq: int,
                             b_dout_axes: tuple[str, ...] = ()):
    """SP constraint for [B, S, D] activations; carries ``.heads`` — the
    head-parallel constraint for [B, S, H, hd] attention tensors (H3.4:
    forces the SP→head transition to all-to-all the small q/k/v instead
    of the fp32 score tiles) — and ``.plan``, the
    :class:`~repro.core.sharding.ComposeSharding` the adapted linears use
    to pin the rank-space LoRA intermediate and run the matmul-fused
    compose shard-local (no y_lora materialization under SPMD).
    ``b_dout_axes`` (usually :func:`row_parallel_b_axes`): extra FSDP axes
    on the constrained layers' B d_out, threaded into the plan."""
    spec = activation_spec(mesh, batch=batch, seq=seq)
    sharding = NamedSharding(mesh, spec)
    plan = _csh.plan_for_output(mesh, spec, b_dout_axes=tuple(b_dout_axes))

    def constrain(x):
        return jax.lax.with_sharding_constraint(x, sharding)

    bdim = _dp_entry(mesh, batch)
    tp = dict(mesh.shape).get("model", 1)

    def heads(q):
        h_ax = "model" if q.shape[2] % tp == 0 and tp > 1 else None
        spec = P(bdim, None, h_ax, None)
        return jax.lax.with_sharding_constraint(
            q, NamedSharding(mesh, spec))

    constrain.heads = heads
    constrain.plan = plan
    return constrain


def cache_sharding(mcfg: ModelConfig, mesh, *, batch: int,
                   block_size: int | None = None):
    """Decode cache tree: KV [n_scan, B, T, Hkv, hd] — batch over dp, seq
    over model; mamba h [n_scan, B, di, n] — d_inner over model.

    ``block_size``: the PAGED cache layout — block pools
    [n_scan, n_blocks, bs, Hkv, hd] carry no batch dim (any pool block
    serves any row), so they never shard over dp; the in-block seq dim
    shards over model like the rectangular T dim when it divides. The
    ``"pages"`` table (and ``"len"``) are tiny host-mirrored int32 state:
    replicated."""
    b_ax = _dp_entry(mesh, batch)
    tp = dict(mesh.shape).get("model", 1)
    kinds = mcfg.layer_kinds()
    unit: dict[str, Any] = {}
    for i in range(mcfg.period):
        if kinds[i] == "attn":
            if block_size is not None:
                bs_ax = "model" if tp > 1 and block_size % tp == 0 \
                    else None
                kv = NamedSharding(mesh, P(None, None, bs_ax, None, None))
            else:
                kv = NamedSharding(mesh, P(None, b_ax, "model", None,
                                           None))
            unit[f"l{i}"] = {"k": kv, "v": kv}
        else:
            di_ok = mcfg.d_inner % tp == 0
            unit[f"l{i}"] = {
                "h": NamedSharding(
                    mesh, P(None, b_ax, "model" if di_ok else None, None)),
                "conv": NamedSharding(
                    mesh, P(None, b_ax, None, "model" if di_ok else None)),
            }
    out = {"stack": unit, "len": NamedSharding(mesh, P())}
    if block_size is not None:
        out["pages"] = NamedSharding(mesh, P())
    return out


def replicated(mesh):
    return NamedSharding(mesh, P())


def tree_replicated(tree, mesh):
    rep = replicated(mesh)
    return ctree.map(lambda _: rep, tree)
