"""Three-tier runtime dispatch (paper §4, Fig. 2, Table 2).

Tier 1 — fused backward: training + accelerator + above crossover. The
         custom-vjp fused op saves ``inner`` for the magnitude gradient.
Tier 2 — fused forward: inference + accelerator. Forward-only kernel, no
         residuals.
Tier 3 — eager fallback: CPU / forced-off / sub-crossover shapes / unmet
         shape constraints (d_out % 128 != 0, bad magnitude broadcast).

Every tier routes through ONE capability-probed dispatch table
(:data:`DISPATCH_TABLE`): a kernel *backend* ("tpu" — compiled Pallas,
"interpret" — the Pallas interpreter for CPU validation, "eager" — pure
jnp) is resolved from the probes in :mod:`repro.compat.probes`, the config
mode, and the forced-tier override (``REPRO_FORCE_TIER`` env var or
``DoRAConfig.force_tier``), and the paper's Tier-1/2/3 split is then layered
on top of that backend. Shapes are static under jit, so selection happens at
trace time, exactly like the paper's Python-level ``_compose_with_dispatch``.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Callable

import jax

from repro.compat import probes
from repro.core.config import DoRAConfig
from repro.core.sharding import ComposeSharding


class Tier(enum.Enum):
    FUSED_BWD = 1
    FUSED_FWD = 2
    EAGER = 3


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """One row of the dispatch table: how a tier's kernels execute."""
    name: str                      # "tpu" | "interpret" | "eager"
    fused: bool                    # routes to the Pallas kernels
    interpret: bool                # Pallas interpreter (CPU validation)
    available: Callable[[], bool]  # capability probe


DISPATCH_TABLE: dict[str, KernelBackend] = {
    "tpu": KernelBackend("tpu", fused=True, interpret=False,
                         available=probes.is_tpu),
    "interpret": KernelBackend("interpret", fused=True, interpret=True,
                               available=lambda: True),
    "eager": KernelBackend("eager", fused=False, interpret=False,
                           available=lambda: True),
}

# Config/env mode → table row. "fused" means "the compiled kernels": off a
# TPU it raises instead of running the interpreter in their place.
_MODE_TO_BACKEND = {"fused": "tpu", "interpret": "interpret",
                    "eager": "eager"}


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Resolved execution plan for one kernel call site."""
    tier: Tier
    backend: str       # DISPATCH_TABLE key actually used
    interpret: bool    # pass to pallas_call
    # Matmul-fused compose: the LoRA up-projection h@Bᵀ runs inside the
    # compose kernel (y_lora never materialized). Only ever True on a fused
    # tier with a crossover-eligible rank (see ``mm_fused_eligible``).
    matmul_fused: bool = False
    # SPMD plan for the call site (None = unsharded / legacy constraint).
    # When set together with ``matmul_fused``, the kernel wrapper runs the
    # compose shard-local under shard_map with block specs derived from the
    # mesh axis sizes; the plan is only ever attached when
    # ``sharding.kernel_expressible(d_out)`` holds.
    sharding: ComposeSharding | None = None

    @property
    def fused(self) -> bool:
        return self.tier is not Tier.EAGER


def available_backends() -> tuple[str, ...]:
    """Table rows whose capability probe passes on this host, best first."""
    return tuple(name for name, b in DISPATCH_TABLE.items()
                 if b.available())


def resolve_backend(cfg: DoRAConfig) -> KernelBackend:
    """Mode/override → the dispatch-table row to execute on.

    A *forced* tier (``REPRO_FORCE_TIER`` / ``cfg.force_tier``, surfaced
    through ``cfg.resolve_mode()``) is honored or fails loudly: a forced
    ``tpu``/``fused`` tier off a TPU raises, naming the reason. CPU
    validation of the kernels asks for ``interpret`` explicitly.
    """
    mode = cfg.resolve_mode()
    if mode == "auto":
        name = "tpu" if DISPATCH_TABLE["tpu"].available() else "eager"
        return DISPATCH_TABLE[name]
    name = _MODE_TO_BACKEND[mode]
    backend = DISPATCH_TABLE[name]
    if backend.available():
        return backend
    raise RuntimeError(
        f"kernel tier {name!r} was forced but is unavailable on this host: "
        f"{probes.why_unavailable(name)}")


def unpartitionable(backend: KernelBackend) -> bool:
    """True when ``backend`` compiles Mosaic kernels and the call site is
    being traced under an (abstract) mesh of more than one device. XLA
    cannot partition a Mosaic kernel, so there only a kernel that runs
    shard-local under shard_map (the matmul-fused compose with its
    sharding plan) may be used; every other call site takes the eager
    tier, which GSPMD partitions. The step builders in
    ``repro.launch.steps`` trace under their mesh. The interpreter lowers
    to plain jnp and partitions like it."""
    return (backend.fused and not backend.interpret
            and jax.sharding.get_abstract_mesh().size > 1)


def above_crossover(rows: int, d_out: int, cfg: DoRAConfig) -> bool:
    """Paper §4: d_out >= 2048 and rows*d_out >= 2048*6144; below this,
    launch latency dominates (KV projections with d_out as low as 512 fall
    through to Tier 3)."""
    return (d_out >= cfg.min_fused_dout
            and rows * d_out >= cfg.min_fused_elems)


def shape_supported(d_out: int) -> bool:
    """Paper App. C: d_out must divide the 128-lane block."""
    return d_out % 128 == 0


def mm_fused_eligible(rank: int | None, cfg: DoRAConfig,
                      rows: int | None = None) -> bool:
    """Crossover guard for the matmul-fused compose: the kernel re-reads the
    B tile once per row-tile, so its extra traffic is ~(rows/block_m)·
    d_out·r bytes vs the 2·rows·d_out the fusion saves — profitable while
    the (lane-padded) rank stays below ``mm_fused_max_rank`` (≈2·block_m
    by the bytes model, derived at the block the call site actually
    executes — see ``DoRAConfig.resolve_mm_fused_max_rank``). ``rows``
    prices decode-shaped calls at their shrunken grid, where the B
    re-read stops amortizing and the materialized path wins (the
    committed decode row of BENCH_compose.json records the 0.67x ratio).
    ``rank=None`` (call sites composing an already materialized y_lora)
    is never eligible."""
    if rank is None or not cfg.compose_matmul_fused:
        return False
    rank_padded = (rank + 127) // 128 * 128
    return rank_padded <= cfg.resolve_mm_fused_max_rank(rows)


def plan_compose(cfg: DoRAConfig, *, training: bool, rows: int,
                 d_out: int, rank: int | None = None,
                 sharding: ComposeSharding | None = None) -> KernelPlan:
    """Resolve the compose call site to (Tier, backend, interpret, mm-fused,
    sharding).

    The shape constraint outranks even a forced tier: d_out % 128 != 0 is
    inexpressible in the 128-lane kernels, and the paper (App. B/C)
    specifies the eager fallback for it — same precedence the seed
    dispatch had. ``rank``: the adapter rank when the caller still holds
    the factored ``h = x@Aᵀ`` (enables the matmul-fused kernel); None when
    only the materialized y_lora is available. ``sharding``: the call
    site's :class:`ComposeSharding` plan; when the plan is expressible for
    the kernels (even d_out shards, 128-lane local blocks) the matmul-fused
    route runs shard-local under it, and the shape constraint is evaluated
    on the LOCAL d_out shard — the unsharded path is just the one-device
    instance. An inexpressible plan drops the matmul fusion (the
    materialized-lora route honours the constraint instead); it never
    errors.
    """
    rows_local = rows
    if sharding is not None:
        row_shards = max(sharding.row_shards, 1)
        if not sharding.kernel_expressible(d_out) \
                or rows % row_shards != 0:
            # The d_out shard breaks the 128-lane block constraint, or
            # the rows do not divide the row axes: inexpressible for the
            # shard-local kernels, eager fallback (the caller still
            # applies the constraints; GSPMD partitions jnp).
            return KernelPlan(Tier.EAGER, "eager", False)
        rows_local = rows // row_shards
    local_dout = sharding.local_dout(d_out) if sharding is not None \
        else d_out
    if not shape_supported(local_dout):
        return KernelPlan(Tier.EAGER, "eager", False)
    mode = cfg.resolve_mode()
    backend = resolve_backend(cfg)
    if not backend.fused:
        return KernelPlan(Tier.EAGER, backend.name, False)
    if mode == "auto" and not above_crossover(rows, d_out, cfg):
        return KernelPlan(Tier.EAGER, "eager", False)
    tier = Tier.FUSED_BWD if training else Tier.FUSED_FWD
    mm = mm_fused_eligible(rank, cfg, rows_local)
    if unpartitionable(backend) and not (mm and sharding is not None):
        return KernelPlan(Tier.EAGER, "eager", False)
    return KernelPlan(tier, backend.name, backend.interpret,
                      matmul_fused=mm, sharding=sharding if mm else None)


def plan_norm(cfg: DoRAConfig, *, d_out: int) -> KernelPlan:
    """Resolve the factored-norm call site. The norm kernel is forward-only
    (the norm is detached), so the fused choice is Tier 2 by construction;
    no crossover guard — the norm reads the whole [d_out, d_in] weight, so
    the fused pass wins at every adapted-layer size (paper §2.3)."""
    if not shape_supported(d_out):
        return KernelPlan(Tier.EAGER, "eager", False)
    backend = resolve_backend(cfg)
    if not backend.fused or unpartitionable(backend):
        return KernelPlan(Tier.EAGER, "eager", False)
    return KernelPlan(Tier.FUSED_FWD, backend.name, backend.interpret)


def plan_gather(cfg: DoRAConfig | None, *, head_elems: int) -> KernelPlan:
    """Resolve the paged K/V gather call site (block pool → logical view;
    ``repro.kernels.paged_gather``). Forward-only by construction (the
    cache carries no gradients), so the fused choice is Tier 2, like the
    norm. ``head_elems`` = Hkv*hd, the flattened trailing dim of one
    cache block — the 128-lane constraint applies to it; unsupported
    shapes (and ``cfg=None``: serving a base model with no adapter
    config) take the eager gather, which is bitwise-identical (both
    tiers are pure copies + zero fill), so the fallback costs layout,
    never parity."""
    if cfg is None or not shape_supported(head_elems):
        return KernelPlan(Tier.EAGER, "eager", False)
    backend = resolve_backend(cfg)
    if not backend.fused or unpartitionable(backend):
        return KernelPlan(Tier.EAGER, "eager", False)
    return KernelPlan(Tier.FUSED_FWD, backend.name, backend.interpret)


def plan_scan(cfg: DoRAConfig | None, *, d_inner: int) -> KernelPlan:
    """Resolve the Mamba selective-scan call site
    (``repro.kernels.selective_scan``): the Pallas forward/backward pair
    on a fused tier, the XLA chunked scan otherwise. ``d_inner`` is the
    kernels' 128-lane axis. ``cfg=None`` (a base model with no adapter
    config) takes the XLA scan, which computes the same recurrence."""
    if cfg is None or not shape_supported(d_inner):
        return KernelPlan(Tier.EAGER, "eager", False)
    backend = resolve_backend(cfg)
    if not backend.fused or unpartitionable(backend):
        return KernelPlan(Tier.EAGER, "eager", False)
    return KernelPlan(Tier.FUSED_BWD, backend.name, backend.interpret)


def select_tier(cfg: DoRAConfig, *, training: bool, rows: int,
                d_out: int) -> Tier:
    return plan_compose(cfg, training=training, rows=rows,
                        d_out=d_out).tier


def use_interpret(cfg: DoRAConfig) -> bool:
    backend = resolve_backend(cfg)
    if not backend.fused:
        # Eager never reaches a pallas_call; answer for "if it did".
        return not probes.is_tpu()
    return backend.interpret
