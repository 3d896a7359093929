"""Continuous-batching decode engine: slot-scheduled serving with per-row
cache state.

The static serve loop (``repro.launch.serve``) retires a batch only when
EVERY row is done: a request that finishes early keeps burning its row,
and a waiting request cannot start until the whole batch drains. This
module adds the scheduler subsystem that keeps the decode batch full:

  - **slot table** — the decode batch is ``slots`` fixed rows over ONE
    persistent cache whose ``"len"`` is a per-row vector
    (``init_cache(..., row_lens=True)``): every row stands at its own
    position, attends under its own causal frontier, and writes its new
    K/V at its own depth;
  - **admission** — a waiting request is prefilled INTO a free row of the
    running batch (``make_prefill_into_slot_step``: slot and prompt
    length both traced, so joining never recompiles) while the other
    rows' state is untouched;
  - **retirement** — a row retires the moment its request finishes (EOS,
    its token budget, or the cache's ``max_len``); the freed slot admits
    the next queued request at the next engine step — no idle decode
    rows while work is waiting;
  - **fixed-shape steps** — the compiled surface is exactly one
    (prefill-into-slot, decode) pair per (slots, max_len,
    group-signature): join/leave traffic changes VALUES (slot index,
    per-row lengths, tokens), never shapes. The decode step's jaxpr
    contains zero ``dora_wnorm`` ops (the frozen-adapter serving state —
    which also carries the rsLoRA scale — does all norm work at
    precompute time, exactly as in the static path);
  - **per-slot adapters** — requests carry
    :class:`~repro.core.AdapterHandle`\\ s resolved through the PR-4
    :class:`~repro.core.AdapterStateCache` LRU. Slots whose handles
    coincide take the single-tenant bitwise path (``groups=None``);
    mixed-handle slot tables group contiguous same-handle runs through
    ``dora_linear_grouped`` (the PR-4 grouped gsB-folded compose, ≥2-row
    groups bitwise) with free slots absorbed into a neighbouring run;
  - **dynamic grouping** — with ``dynamic_grouping=True`` the static
    (start, size) signature gives way to a device-resident FLEET STACK
    of serving states indexed by a TRACED per-row int32 position
    (``batch_in["adapter_idx"]``): tenant churn — admissions,
    retirements, version bumps — changes VALUES, never the compile
    signature, so a fleet of thousands of adapters decodes through
    exactly ONE executable (``compile_counts()["decode"]`` has the
    single key ``"dynamic"``). Greedy dynamic streams are bitwise the
    static grouped streams AND per-tenant batched sequential serving
    (``select_tenant`` gathers after tenant-independent contractions;
    docs/serving.md).

With ``paged=True`` the rectangular per-row K/V gives way to a
block-paged cache: a per-layer block POOL plus a per-slot block TABLE
(``cache["pages"]``, a traced operand — paging never recompiles), blocks
allocated as a row's frontier crosses into them and freed at
retirement/preemption/speculative rewind, and prompts admitted
INCREMENTALLY in fixed-size chunks interleaved with decode ticks
(``make_prefill_chunk_step``). Greedy paged streams are bitwise the
rectangular streams; see ``docs/engine.md`` for the full contract and
the allocation/reclaim policy.

Scheduling is HOST logic over host mirrors (per-slot position/budget
counters): the engine never reads ``cache["len"]`` back from the device,
so the only per-step sync is the logits fetch that sampling needs anyway.
Scheduling is also deterministic and model-independent when no ``eos_id``
is set — ``benchmarks/serve_bench.py`` re-prices it analytically and
``scripts/check_bench_drift.py`` gates the result.

SSM/Mamba archs are rejected at construction (their states integrate
every processed token and cannot rewind to a slot's true prompt length);
MoE FFNs are rejected too (expert-capacity dispatch couples rows, so a
retired slot's garbage tokens could evict a live row's tokens from an
expert).
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict, deque
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adapter import stack_adapter_states
from repro.core.adapter_cache import (AdapterHandle, AdapterStateCache,
                                      mesh_fingerprint)
from repro.launch.faults import FaultPlan
from repro.launch.steps import (StepConfig, make_decode_step,
                                make_draft_step,
                                make_prefill_chunk_step,
                                make_prefill_into_slot_step,
                                make_verify_step)
from repro.models import init_cache
from repro.models.config import ModelConfig
from repro.obs.trace import TraceRecorder, span

#: Every finish_reason a RequestResult can carry.
#:   eos           the request's eos_id was sampled
#:   length        the request's max_new_tokens budget ran out
#:   max_len       the CACHE bound ran out before the request's budget
#:   error         admission-time resolution failed (error_type/_message)
#:   timeout       deadline_ticks expired (queued or mid-decode); tokens
#:                 generated so far are delivered
#:   error_numeric the row's logits went non-finite and it was quarantined
FINISH_REASONS = ("eos", "length", "max_len", "error", "timeout",
                  "error_numeric")


class EngineBusy(RuntimeError):
    """Submit-time backpressure: the adapter-state cache is thrashing
    (every recent lookup an evicting miss) and admitting this cold
    request would stall the serve path on yet another full precompute.
    ``retry_after`` is the suggested backoff in engine ticks (the cache's
    thrash window — the window must see a non-evicting lookup before the
    signal clears)."""

    def __init__(self, message: str, retry_after: int = 1):
        super().__init__(message)
        self.retry_after = retry_after


@dataclasses.dataclass(frozen=True)
class EngineRequest:
    """One queued/running request (engine-internal; build via
    :meth:`DecodeEngine.submit`)."""
    request_id: int
    prompt: np.ndarray                 # int32 [P]
    adapter: AdapterHandle | None      # None = the engine's fixed adapters
    max_new_tokens: int
    eos_id: int | None = None
    key_id: int = 0                    # sample-key fold-in (see submit)
    state: Any = dataclasses.field(default=None, repr=False)
    #                                    serving tree pinned at submit: a
    #                                    tenant update() while this request
    #                                    waits in the queue must not change
    #                                    (or lose) the weights it serves with
    priority: int = 0                  # higher admits first / preempts lower
    deadline_step: int | None = None   # ABSOLUTE engine step (submit step +
    #                                    deadline_ticks); expired -> "timeout"
    # -- continuation bookkeeping (set by preemption, not by submit) --------
    prefix: np.ndarray | None = None   # tokens generated before preemption
    orig_prompt: np.ndarray | None = None   # prompt as originally submitted
    resume_cap: str | None = None      # finish_cap carried across preemption
    first_admitted: int | None = None  # step of the FIRST admission
    preempted: int = 0                 # times this request was preempted


@dataclasses.dataclass
class RequestResult:
    """Everything the engine produced for one request.

    Results are PICKLABLE: errors are carried as ``error_type`` (the
    exception class name) + ``error_message`` strings so a result can
    cross a process boundary or land in a structured log. The live
    exception — when the result was produced in THIS process — stays
    reachable behind the :attr:`error` debug accessor, which pickling
    drops."""
    request_id: int
    prompt: np.ndarray                 # int32 [P] (as submitted)
    tokens: np.ndarray                 # int32 [n] generated tokens
    finish_reason: str                 # one of FINISH_REASONS
    admitted_step: int                 # engine step the prefill ran in
    finished_step: int                 # engine step the last token landed
    error_type: str | None = None      # exception class name, iff "error"
    error_message: str | None = None   # str(exception), iff "error"
    preempted: int = 0                 # times the request was preempted

    @property
    def error(self) -> Exception | None:
        """The live exception behind an ``"error"`` result — debug only:
        present in the producing process, ``None`` after a pickle
        round-trip (``error_type``/``error_message`` survive)."""
        return getattr(self, "_live_error", None)

    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_live_error", None)
        return state


@dataclasses.dataclass(frozen=True)
class EngineStats:
    """Deterministic scheduling counters (point-in-time snapshot)."""
    slots: int
    steps: int                  # engine steps driven (incl. idle ones)
    decode_steps: int           # steps that ran the batched PLAIN decode
    prefills: int               # prefill-into-slot calls (= admissions)
    admitted: int
    retired: int
    generated_tokens: int       # sampled tokens (prefill + decode + verify)
    slot_steps: int             # sum over plain decode steps of active slots
    draft_steps: int = 0        # base-only draft forwards (speculative)
    verify_steps: int = 0       # full-DoRA k+1-window verifies (= spec ticks)
    accepted_drafts: int = 0    # draft tokens the verify accepted
    stack_inserts: int = 0      # fleet-stack state writes (dynamic grouping):
    #                             one per DISTINCT handle admission, zero per
    #                             token — the churn-cost counter the fleet
    #                             bench prices
    # -- robustness counters (all zero on a sunny-day run) ------------------
    preemptions: int = 0        # slots displaced by higher-priority requests
    timeouts: int = 0           # requests retired by deadline expiry
    quarantined: int = 0        # rows retired with non-finite logits
    busy_rejections: int = 0    # submits refused with EngineBusy (thrash)
    spec_disables: int = 0      # speculative ladder trips (accept collapse)
    spec_reenables: int = 0     # speculative re-enables after cooldown
    injected_nans: int = 0      # FaultPlan: logits rows poisoned
    forced_evictions: int = 0   # FaultPlan: cache invalidations fired
    stale_injected: int = 0     # FaultPlan: admissions handed stale handles
    slow_ticks: int = 0         # FaultPlan: straggler sleeps injected

    @property
    def mean_occupancy(self) -> float:
        """Active rows per decode step / slots — the fraction of decode
        row-work that produced a live request's token."""
        if self.decode_steps == 0:
            return 0.0
        return self.slot_steps / (self.decode_steps * self.slots)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["mean_occupancy"] = self.mean_occupancy
        return d


@dataclasses.dataclass
class _Slot:
    idx: int = -1                      # this slot's row index (fixed)
    req: EngineRequest | None = None
    handle: AdapterHandle | None = None
    state: Any = None                  # pinned serving tree for this row
    last_token: int = 0
    budget: int = 0                    # tokens still to sample
    finish_cap: str = "length"         # reason when the budget runs out
    generated: list = dataclasses.field(default_factory=list)
    admitted_step: int = 0
    pos: int = 0                       # host mirror of cache["len"][slot]:
    #                                    where this row's NEXT K/V write
    #                                    lands (speculative rewind target)
    n_prior: int = 0                   # tokens emitted in earlier legs of a
    #                                    preempted request: keeps the sample-
    #                                    key fold count (and so the
    #                                    temperature>0 stream) continuous
    #                                    across preempt/resume
    prefilling: bool = False           # paged chunked admission in flight:
    #                                    the slot holds a request but does
    #                                    not decode yet
    chunk_next: int = 0                # next chunk's start offset into the
    #                                    prompt while prefilling

    @property
    def occupied(self) -> bool:
        """The slot holds a request (decoding OR mid-admission)."""
        return self.req is not None

    @property
    def active(self) -> bool:
        """The slot decodes this tick (admission, if any, is complete)."""
        return self.req is not None and not self.prefilling


class DecodeEngine:
    """Slot-scheduled continuous-batching serving over one fixed-shape
    decode step.

    ``adapters`` is EITHER a single precomputed serving tree every
    request shares (single-tenant engine), OR ``None`` with an
    ``adapter_cache`` (:class:`~repro.core.AdapterStateCache`) — then
    every request carries an adapter id / handle resolved through the
    LRU at SUBMIT time. The resolved state is pinned on the request
    (and then on its slot) for the request's lifetime: a tenant
    ``update()`` mid-flight never swaps weights under a submitted
    request — whether it is already decoding or still waiting in the
    FIFO — and the NEXT submission picks up the new version.

    ``step()`` is one scheduler tick: retire-finished → admit-into-free
    (prefill + first token) → one batched decode for every active slot.
    ``run()`` drives until the queue and the slot table drain. Sampling
    is host-side (greedy at ``temperature=0.0``, else per-request keys —
    ``fold_in(fold_in(PRNGKey(seed), request_id), n_sampled)`` — so a
    request's sample stream is independent of what shares its batch).

    ``speculative_k > 0`` turns a tick into draft-then-verify: ``k``
    base-only draft forwards (adapter path short-circuited — zero
    ``dora_wnorm``, zero gsB work) propose tokens per row, ONE k+1-window
    forward through the full grouped DoRA path verifies them, each row
    accepts its longest matching prefix and rewinds ``cache["len"]`` to
    its accepted frontier (host mirrors — the engine still never reads
    ``len`` back from the device). Greedy speculative token streams are
    bitwise the plain greedy streams: the verify logits at every accepted
    position are the plain decode logits (same dense per-row-frontier
    attention math), so acceptance-by-argmax-match IS plain decode.
    Ticks fall back to plain decode when ``temperature > 0`` (rejection
    sampling not yet implemented) or when any active row's window would
    overflow ``max_len``.

    Fleet semantics (PR 9): ``dynamic_grouping=True`` (cache-routed
    engines only) replaces the static per-layout decode signatures with
    ONE traced executable — slots index a device-resident fleet stack of
    serving states by per-row int32 position, so admissions/retirements/
    version bumps never recompile (``compile_counts()["decode"]`` stays
    ``{"dynamic": 1}`` under arbitrary churn) at the cost of K× adapter-
    path FLOPs per decode (K = slots; the base matmul still dominates).
    Greedy dynamic streams are bitwise the static grouped streams and
    per-tenant batched sequential serving. ``max_active_per_adapter``
    caps how many slots one adapter id may hold simultaneously: excess
    requests wait in the queue (keeping their positions) so a hot
    tenant's burst cannot starve the fleet.

    Failure semantics (PR 7): requests may carry a ``priority`` (higher
    preempts lower when no slot is free — the victim re-queues as a
    continuation and resumes bitwise) and ``deadline_ticks`` (expiry
    retires the request with ``finish_reason="timeout"`` and its tokens
    so far); every tick's fetched logits pass a host-side non-finite
    guard that quarantines ONLY the poisoned row
    (``finish_reason="error_numeric"``) while its neighbours stay
    bitwise; speculative decode self-disables with hysteresis when the
    accept rate collapses; a thrashing adapter cache pushes back at
    submit time with :class:`EngineBusy`. All of it is driven
    deterministically by a :class:`~repro.launch.faults.FaultPlan`, and
    none of it adds executables: preempt/resume, quarantine and timeout
    reuse the same traced prefill/decode/verify steps
    (``compile_counts()`` is fault-invariant).

    Observability (PR 10): ``trace=`` takes a
    :class:`repro.obs.TraceRecorder`; the engine then emits one
    structured lifecycle event per transition (``submitted → queued →
    admitted → chunk_prefill* → first_token → token* → {preempted,
    resumed}* → terminal``) plus fault/ladder/cache events, each stamped
    with the engine tick and a monotonic wall time. The recorder reads
    ONLY host mirrors the scheduler already maintains — tracing on vs.
    off leaves streams bitwise identical, ``compile_counts()``
    unchanged, and adds zero device fetches (tests/test_obs.py;
    docs/observability.md).
    """

    def __init__(self, mcfg: ModelConfig, scfg: StepConfig, params, *,
                 slots: int, max_len: int, adapters=None,
                 adapter_cache: AdapterStateCache | None = None,
                 mesh=None, allow_miss: bool = True,
                 dynamic_grouping: bool = False,
                 max_active_per_adapter: int | None = None,
                 temperature: float = 0.0, seed: int = 0,
                 speculative_k: int = 0,
                 max_cached_steps: int = 16,
                 fault_plan: FaultPlan | None = None,
                 spec_accept_floor: float = 0.0,
                 spec_window: int = 4,
                 spec_reenable_after: int = 8,
                 paged: bool = False,
                 block_size: int | None = None,
                 n_blocks: int | None = None,
                 prefill_chunk: int | None = None,
                 trace: TraceRecorder | None = None):
        kinds = mcfg.layer_kinds()
        if any(k != "attn" for k in kinds):
            raise NotImplementedError(
                f"continuous batching requires attention-only caches: SSM "
                f"states integrate every processed token and cannot rewind "
                f"to a slot's true prompt length, so admission "
                f"(prefill-into-slot) and per-row retirement are "
                f"ill-defined (arch {mcfg.name!r} has layer kinds "
                f"{kinds})")
        if any(f == "moe" for f in mcfg.ffn_kinds()):
            raise NotImplementedError(
                f"continuous batching does not support MoE FFNs: expert-"
                f"capacity dispatch couples batch rows, so a retired "
                f"slot's garbage tokens could evict a live row's tokens "
                f"from an expert (arch {mcfg.name!r})")
        if slots < 1:
            raise ValueError(f"need at least one slot, got {slots}")
        if (adapters is None) == (adapter_cache is None):
            # Exactly one source of adapter state: mixing a fixed tree
            # with cache-routed requests would make a handle-less ACTIVE
            # slot indistinguishable from a free one in _slot_grouping —
            # its rows would silently decode under a neighbouring
            # tenant's adapters.
            raise ValueError(
                "DecodeEngine needs EITHER a fixed precomputed `adapters` "
                "tree (single-tenant) OR an `adapter_cache` to resolve "
                "per-request adapter handles against — not both, not "
                "neither")
        if adapter_cache is not None \
                and adapter_cache.sharding != mesh_fingerprint(mesh):
            raise ValueError(
                f"adapter cache is keyed for sharding "
                f"{adapter_cache.sharding} but the engine runs on mesh "
                f"{mesh_fingerprint(mesh)} — build the cache with "
                f"AdapterStateCache.for_serving(mcfg, scfg, mesh) for "
                f"THIS mesh")
        if dynamic_grouping and adapter_cache is None:
            raise ValueError(
                "dynamic_grouping=True requires an adapter_cache: the fleet "
                "stack is indexed by per-request adapter handles, which "
                "only cache-routed engines carry")
        if max_active_per_adapter is not None and max_active_per_adapter < 1:
            raise ValueError(
                f"max_active_per_adapter={max_active_per_adapter} < 1 would "
                f"make every adapter-carrying request permanently "
                f"inadmissible")
        self.mcfg = mcfg
        self.scfg = scfg
        self.params = params
        self.slots = int(slots)
        self.max_len = int(max_len)
        self.mesh = mesh
        self.adapters = adapters
        self.adapter_cache = adapter_cache
        self.allow_miss = allow_miss
        self.temperature = float(temperature)
        self.seed = int(seed)
        if speculative_k < 0:
            raise ValueError(f"speculative_k={speculative_k} < 0")
        self.speculative_k = int(speculative_k)
        self.max_cached_steps = int(max_cached_steps)
        # -- robustness knobs ----------------------------------------------
        self.fault_plan = fault_plan
        if not 0.0 <= spec_accept_floor <= 1.0:
            raise ValueError(
                f"spec_accept_floor={spec_accept_floor} not in [0, 1]")
        self.spec_accept_floor = float(spec_accept_floor)
        self.spec_window = int(spec_window)
        self.spec_reenable_after = int(spec_reenable_after)
        # -- paged K/V knobs -----------------------------------------------
        self._paged = bool(paged)
        if not self._paged and (block_size is not None or n_blocks is not None
                                or prefill_chunk is not None):
            raise ValueError(
                "block_size / n_blocks / prefill_chunk require paged=True")
        if self._paged:
            if block_size is None:
                # Largest divisor of max_len up to 16: always valid, and
                # small enough that short tenants waste little slack.
                block_size = max(d for d in range(1, min(16, self.max_len) + 1)
                                 if self.max_len % d == 0)
            self._block_size = int(block_size)
            if self.max_len % self._block_size != 0:
                raise ValueError(
                    f"max_len={self.max_len} must be a multiple of "
                    f"block_size={self._block_size}")
            self._max_blocks = self.max_len // self._block_size
            if n_blocks is None:
                # Parity-safe default: enough blocks for every slot to
                # reach max_len (same HBM as the rectangular cache).
                # Pass a smaller pool to realise the paged memory win.
                n_blocks = self.slots * self._max_blocks
            self._n_blocks = int(n_blocks)
            if self._n_blocks < self._max_blocks:
                raise ValueError(
                    f"n_blocks={self._n_blocks} < max_blocks="
                    f"{self._max_blocks}: one slot alone must be able to "
                    f"grow to max_len, or the engine could deadlock with "
                    f"an admitted request it can never finish")
            self._chunk = int(prefill_chunk if prefill_chunk is not None
                              else self._block_size)
            if not 1 <= self._chunk <= self.max_len:
                raise ValueError(
                    f"prefill_chunk={self._chunk} not in [1, "
                    f"max_len={self.max_len}] (the chunk step's row writes "
                    f"must fit the logical window)")

        # Pin the persistent cache to the serving shardings (and the step
        # OUTPUT caches to the same layout): the cache round-trips through
        # every prefill/decode, and an unpinned layout would let GSPMD
        # re-lay it out after the first call — one spurious recompile per
        # step fn, breaking the one-executable-per-signature contract.
        self.cache = init_cache(
            mcfg, self.slots, self.max_len, row_lens=True,
            block_size=self._block_size if self._paged else None,
            n_blocks=self._n_blocks if self._paged else None)
        cache_out_sh = None
        if mesh is not None:
            from repro.launch import sharding as S
            c_sh = S.cache_sharding(
                mcfg, mesh, batch=self.slots,
                block_size=self._block_size if self._paged else None)
            self.cache = jax.device_put(self.cache, c_sh)
            cache_out_sh = c_sh
        self._prefill = jax.jit(
            make_prefill_into_slot_step(mcfg, scfg, mesh, seq=max_len),
            donate_argnums=(2,),
            out_shardings=(None, cache_out_sh))
        self._chunk_prefill = None
        if self._paged:
            self._chunk_prefill = jax.jit(
                make_prefill_chunk_step(mcfg, scfg, mesh, chunk=self._chunk),
                donate_argnums=(2,),
                out_shardings=(None, cache_out_sh))
        self._cache_out_sh = cache_out_sh
        # -- host mirror of the block pool (paged only) --------------------
        # The device never sees allocation logic: the engine owns the
        # free list and the per-slot block lists, mirrors them into the
        # int32 block table (cache["pages"]), and flushes the table as a
        # TRACED operand before any device step that reads the cache —
        # paging never recompiles anything.
        if self._paged:
            # pop() hands out ascending ids; freed blocks return LIFO.
            self._free: list[int] = list(range(self._n_blocks - 1, -1, -1))
            self._blocks: list[list[int]] = [[] for _ in range(self.slots)]
            self._pages_np = np.full((self.slots, self._max_blocks), -1,
                                     np.int32)
            self._pages_dirty = False
            self._peak_used = 0
        # Compiled decode steps per group signature (None = single
        # tenant). Same LRU discipline as MultiTenantServer._steps: each
        # entry pins a jitted executable.
        self._decodes: "OrderedDict[Any, Callable]" = OrderedDict()
        # Speculative executables: ONE adapter-free draft step (no group
        # signature — the draft never touches adapters) and one verify
        # step per (group signature, window) — window = k+1 is a SHAPE,
        # so each k the engine is driven at gets its own executable.
        self._draft: Callable | None = None
        self._verifies: "OrderedDict[Any, Callable]" = OrderedDict()
        # (slot-handle layout, groups, stacked tree) of the last decode —
        # re-stacked only when the layout changes, never per token.
        self._grouping_cache: tuple | None = None
        # -- dynamic fleet stack (dynamic_grouping=True) --------------------
        # K = slots stacked positions over the full serving-tree structure;
        # positions are handed out per DISTINCT handle (refcounted across
        # the slots sharing it) and recycled at last retirement. Occupied
        # slots ≤ slots, so distinct handles ≤ slots and _dyn_free can
        # never underflow at assignment time (the seating slot is still
        # free when its position is claimed).
        self._dynamic = bool(dynamic_grouping)
        self.max_active_per_adapter = (
            None if max_active_per_adapter is None
            else int(max_active_per_adapter))
        self._dyn_stack = None               # leaves [n_scan, K, ...]
        self._dyn_pos: dict[AdapterHandle, list] = {}   # handle→[pos, refs]
        self._dyn_free: list[int] = list(range(self.slots - 1, -1, -1))
        self._dyn_insert: Callable | None = None
        self._dyn_idx_np = np.zeros((self.slots,), np.int32)
        self._dyn_idx_cached = None          # device mirror of _dyn_idx_np
        self._stack_inserts = 0
        self._slots: list[_Slot] = [_Slot(idx=i) for i in range(self.slots)]
        self._queue: deque[EngineRequest] = deque()
        self._results: dict[int, RequestResult] = {}
        self._next_id = 0
        self._steps = 0
        self._decode_steps = 0
        self._prefills = 0
        self._admitted = 0
        self._retired = 0
        self._generated = 0
        self._slot_steps = 0
        self._draft_steps = 0
        self._verify_steps = 0
        self._accepted_drafts = 0
        # -- robustness state ----------------------------------------------
        self._preemptions = 0
        self._timeouts = 0
        self._quarantined = 0
        self._busy_rejections = 0
        self._spec_disables = 0
        self._spec_reenables = 0
        self._injected_nans = 0
        self._forced_evictions = 0
        self._stale_injected = 0
        self._slow_ticks = 0
        self._nan_tick: tuple = ()     # this tick's poisoned slots (faults)
        self._stale_pending = False    # next admission gets a stale handle
        self._spec_rates: list[float] = []   # recent per-tick accept rates
        self._spec_cooldown = 0        # plain ticks left before re-enable
        # -- observability (PR 10) -----------------------------------------
        # The recorder only ever receives host scalars the scheduler
        # already holds; a None trace makes every emit a single attribute
        # check. The adapter cache's spill/reload hook is claimed only
        # when tracing — an untraced engine leaves the cache untouched.
        self.trace = trace
        if trace is not None and adapter_cache is not None:
            adapter_cache.on_event = self._cache_event

    # -- observability -------------------------------------------------------

    def _emit(self, name: str, *, rid: int | None = None,
              slot: int | None = None, **data) -> None:
        """Record one lifecycle event (no-op untraced). Every argument
        must already be host state — this path adds zero device work."""
        if self.trace is not None:
            self.trace.emit(name, tick=self._steps, request_id=rid,
                            slot=slot, **data)

    def _cache_event(self, kind: str, key) -> None:
        """AdapterStateCache tier-traffic hook: ``spill`` / ``reload``
        events land on the engine's trace at the current tick."""
        self._emit(kind, adapter=key.adapter_id, version=key.version)

    # -- submission ---------------------------------------------------------

    def check_request(self, prompt, *,
                      adapter: AdapterHandle | str | None = None,
                      max_new_tokens: int):
        """Validate a request WITHOUT queuing it: raises exactly what
        :meth:`submit` would, and returns the (normalized prompt,
        resolved handle) pair it would queue. Batch front ends run this
        over EVERY request before the first submit — a bad request in
        the middle of a batch must fail the call, not strand the
        already-queued ones in the persistent engine."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        P = prompt.shape[0]
        if P < 1:
            raise ValueError("empty prompt")
        if P + 1 > self.max_len:
            raise ValueError(
                f"prompt length {P} leaves no room to generate within "
                f"max_len={self.max_len} (need P + 1 <= max_len)")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={max_new_tokens} < 1")
        if adapter is None:
            if self.adapters is None:
                raise ValueError(
                    "this engine routes requests through an adapter cache; "
                    "every request must carry an adapter id or handle")
            handle = None
        else:
            if self.adapter_cache is None:
                raise ValueError(
                    "this engine serves one fixed adapter tree; requests "
                    "cannot carry adapter handles (construct the engine "
                    "with adapter_cache= to route per-request adapters)")
            handle = (adapter if isinstance(adapter, AdapterHandle)
                      else self.adapter_cache.current_handle(adapter))
            # Backpressure BEFORE the state resolution: when the LRU is
            # thrashing (every recent lookup an evicting miss), admitting
            # another COLD current-version request would stall the serve
            # path on yet one more full precompute — refuse it with a
            # retry hint instead. Stale/unregistered handles fall through
            # to get_state below so they keep raising their own errors.
            # SPILLED handles are exempt: a host-tier state costs one
            # host→device reload (queue latency), never a precompute, so
            # refusing it would turn the cheap case into a retry storm.
            if (self.adapter_cache.thrashing()
                    and not self.adapter_cache.is_resident(handle)
                    and not self.adapter_cache.is_spilled(handle)):
                try:
                    cur = self.adapter_cache.current_handle(
                        handle.adapter_id)
                except KeyError:
                    cur = None
                if cur == handle:
                    self._busy_rejections += 1
                    self._emit("busy_rejected", adapter=handle.adapter_id,
                               version=handle.version,
                               retry_after=self.adapter_cache.thrash_window)
                    raise EngineBusy(
                        f"adapter-state cache is thrashing (last "
                        f"{self.adapter_cache.thrash_window} lookups were "
                        f"all evicting misses) and "
                        f"{handle.adapter_id!r}@v{handle.version} is not "
                        f"resident — admitting it would evict yet another "
                        f"tenant; retry in ~"
                        f"{self.adapter_cache.thrash_window} ticks",
                        retry_after=self.adapter_cache.thrash_window)
            # Resolve the serving tree NOW: submit is the pin point, so
            # a stale handle — or a cold state under warm-only routing —
            # must fail here, before a batch front end queues anything,
            # not later at admission.
            self.adapter_cache.get_state(self.params, handle,
                                         allow_miss=self.allow_miss)
        return prompt, handle

    def submit(self, prompt, *, adapter: AdapterHandle | str | None = None,
               max_new_tokens: int, eos_id: int | None = None,
               key_id: int | None = None, priority: int = 0,
               deadline_ticks: int | None = None) -> int:
        """Queue one request; returns its request id. ``adapter``: an
        :class:`AdapterHandle`, a registered adapter id (resolved to the
        CURRENT version at submit time), or None when the engine serves a
        fixed adapter tree. The resolved serving tree is pinned on the
        request HERE: an :meth:`AdapterStateCache.update` issued while
        the request waits in the queue neither re-routes it to the new
        version nor errors it — it serves with the tree it was submitted
        against (so a stale handle or a cold warm-only state raises
        here, not at admission). ``key_id``: the fold-in for this request's
        temperature-sampling key stream (default: the request id, which
        monotonically increases on a persistent engine — batch-level
        callers wanting call-reproducible sampling pass the request's
        index within the batch, as ``EngineServer``/mixed-length
        ``serve()`` do).

        ``priority``: higher admits first and may PREEMPT a lower-priority
        active slot when no slot is free (the victim re-queues as a
        continuation — see :meth:`step`). ``deadline_ticks``: the request
        expires ``deadline_ticks`` engine steps from now — queued or
        mid-decode — retiring with ``finish_reason="timeout"`` and
        whatever tokens it generated."""
        if deadline_ticks is not None and deadline_ticks < 1:
            raise ValueError(f"deadline_ticks={deadline_ticks} < 1")
        prompt, handle = self.check_request(prompt, adapter=adapter,
                                            max_new_tokens=max_new_tokens)
        state = (self.adapters if handle is None
                 else self.adapter_cache.get_state(
                     self.params, handle, allow_miss=self.allow_miss))
        rid = self._next_id
        self._next_id += 1
        self._queue.append(EngineRequest(
            rid, prompt, handle, int(max_new_tokens), eos_id,
            key_id=rid if key_id is None else int(key_id), state=state,
            priority=int(priority),
            deadline_step=(None if deadline_ticks is None
                           else self._steps + int(deadline_ticks))))
        self._emit("submitted", rid=rid, prompt_len=int(prompt.shape[0]),
                   max_new_tokens=int(max_new_tokens),
                   adapter=(None if handle is None else handle.adapter_id),
                   priority=int(priority),
                   deadline_ticks=deadline_ticks)
        self._emit("queued", rid=rid, depth=len(self._queue))
        return rid

    # -- scheduling ---------------------------------------------------------

    def has_work(self) -> bool:
        return bool(self._queue) or any(s.occupied for s in self._slots)

    def stats(self) -> EngineStats:
        return EngineStats(slots=self.slots, steps=self._steps,
                           decode_steps=self._decode_steps,
                           prefills=self._prefills,
                           admitted=self._admitted, retired=self._retired,
                           generated_tokens=self._generated,
                           slot_steps=self._slot_steps,
                           draft_steps=self._draft_steps,
                           verify_steps=self._verify_steps,
                           accepted_drafts=self._accepted_drafts,
                           stack_inserts=self._stack_inserts,
                           preemptions=self._preemptions,
                           timeouts=self._timeouts,
                           quarantined=self._quarantined,
                           busy_rejections=self._busy_rejections,
                           spec_disables=self._spec_disables,
                           spec_reenables=self._spec_reenables,
                           injected_nans=self._injected_nans,
                           forced_evictions=self._forced_evictions,
                           stale_injected=self._stale_injected,
                           slow_ticks=self._slow_ticks)

    def compile_counts(self) -> dict:
        """How many executables each step fn holds — the compile-count
        acceptance: after any join/leave trace this must be exactly 1 for
        the prefill, 1 per decode group-signature, 1 for the (adapter-
        free) draft, and 1 per (group-signature, window) verify. A
        dynamic-grouping engine has exactly ONE decode signature (the
        ``"dynamic"`` key) no matter the tenant mix, plus one traced
        ``adapter_insert`` executable for fleet-stack writes."""
        return {"prefill_into_slot": self._prefill._cache_size(),
                "adapter_insert": (0 if self._dyn_insert is None
                                   else self._dyn_insert._cache_size()),
                "prefill_chunk": (0 if self._chunk_prefill is None
                                  else self._chunk_prefill._cache_size()),
                "decode": {sig: fn._cache_size()
                           for sig, fn in self._decodes.items()},
                "draft": (0 if self._draft is None
                          else self._draft._cache_size()),
                "verify": {key: fn._cache_size()
                           for key, fn in self._verifies.items()}}

    # -- block pool (paged K/V) ---------------------------------------------

    def pool_stats(self) -> dict:
        """Host-mirror block-pool accounting (paged engines only): pool
        geometry, current and peak occupancy, and per-slot block counts.
        ``used_blocks == 0`` after the engine drains is the no-leak
        invariant the property suite exercises."""
        if not self._paged:
            raise ValueError("pool_stats() requires a paged engine "
                             "(construct with paged=True)")
        used = self._n_blocks - len(self._free)
        return {"block_size": self._block_size,
                "n_blocks": self._n_blocks,
                "max_blocks": self._max_blocks,
                "prefill_chunk": self._chunk,
                "free_blocks": len(self._free),
                "used_blocks": used,
                "peak_used_blocks": self._peak_used,
                "per_slot_blocks": [len(b) for b in self._blocks]}

    def _ensure_blocks(self, idx: int, upto_len: int) -> bool:
        """Grow slot ``idx``'s block list until it covers K/V positions
        [0, upto_len); False (with the partial growth kept — the blocks
        are reserved either way) when the pool runs dry."""
        need = -(-upto_len // self._block_size)
        blocks = self._blocks[idx]
        while len(blocks) < need:
            if not self._free:
                return False
            b = self._free.pop()
            self._pages_np[idx, len(blocks)] = b
            blocks.append(b)
            self._pages_dirty = True
        used = self._n_blocks - len(self._free)
        if used > self._peak_used:
            self._peak_used = used
        return True

    def _free_tail(self, idx: int, new_len: int) -> None:
        """Return every block of slot ``idx`` past position ``new_len``
        to the pool (a straddling block stays — it still holds live
        K/V). A freed block's stale content is harmless wherever it is
        reallocated: a slot only receives a new block when its frontier
        crosses INTO it, so every stale position sits at-or-beyond the
        new owner's causal frontier until overwritten."""
        keep = -(-new_len // self._block_size)
        blocks = self._blocks[idx]
        while len(blocks) > keep:
            b = blocks.pop()
            self._pages_np[idx, len(blocks)] = -1
            self._free.append(b)
            self._pages_dirty = True

    def _free_all(self, idx: int) -> None:
        self._free_tail(idx, 0)

    def _flush_pages(self) -> None:
        """Mirror the host block table into ``cache["pages"]``. A FRESH
        device array every time (the steps donate the cache); called
        before every device step that reads the cache, so allocation and
        freeing are visible exactly when they must be."""
        if not self._pages_dirty:
            return
        with span("engine.pages"):
            arr = jnp.asarray(np.array(self._pages_np))
            if self._cache_out_sh is not None:
                arr = jax.device_put(arr, self._cache_out_sh["pages"])
            cache = dict(self.cache)
            cache["pages"] = arr
            self.cache = cache
            self._pages_dirty = False

    def _block_victim(self) -> int | None:
        """Deterministic reclaim order under pool exhaustion: lowest
        priority first, most recently admitted among equals, highest
        slot index as the final tie-break."""
        occ = [i for i, s in enumerate(self._slots) if s.occupied]
        if not occ:
            return None
        return min(occ, key=lambda i: (self._slots[i].req.priority,
                                       -self._slots[i].admitted_step, -i))

    def _ensure_active_blocks(self, rows: list[int], extra: int
                              ) -> list[int]:
        """Allocate so every row in ``rows`` can write K/V positions
        pos..pos+extra-1 this tick. On pool exhaustion, reclaim by
        preempting :meth:`_block_victim` slots (their requests re-queue
        as continuations and resume bitwise) until the allocation fits.
        Returns the rows still active — a row preempted as its own
        victim drops out."""
        for i in rows:
            slot = self._slots[i]
            while slot.active and not self._ensure_blocks(i, slot.pos + extra):
                victim = self._block_victim()
                if victim is None:     # unreachable: row i itself is occupied
                    raise RuntimeError(
                        "paged block pool exhausted with nothing to preempt")
                self._preempt(victim)
        return [i for i in rows if self._slots[i].active]

    def _sample_rows(self, logits_rows, key_ids_and_counts) -> list[int]:
        """One token per row. Greedy is a host argmax over the
        already-fetched logits (zero device work); temperature>0 runs
        ONE vmapped categorical over the rows' per-request keys — a
        single device round trip per step, not one per active slot."""
        if self.temperature <= 0.0:
            return [int(np.argmax(row)) for row in logits_rows]
        keys = jnp.stack([
            jax.random.fold_in(
                jax.random.fold_in(jax.random.PRNGKey(self.seed), kid), n)
            for kid, n in key_ids_and_counts])
        draws = jax.vmap(
            lambda k, row: jax.random.categorical(
                k, row / self.temperature)
        )(keys, jnp.asarray(np.stack(logits_rows)))
        return [int(t) for t in np.asarray(draws)]

    def _resolve_state(self, req: EngineRequest):
        if req.adapter is None:
            return self.adapters
        return self.adapter_cache.get_state(self.params, req.adapter,
                                            allow_miss=self.allow_miss)

    def _finish(self, slot: _Slot, reason: str) -> None:
        req = slot.req
        # A preempted-and-resumed request reports its ORIGINAL prompt and
        # the full token stream (earlier legs' prefix + this leg), and its
        # FIRST admission step — the continuation re-prefill is an engine
        # implementation detail the caller never sees.
        prefix = [] if req.prefix is None else list(req.prefix)
        self._results[req.request_id] = RequestResult(
            request_id=req.request_id,
            prompt=(req.prompt if req.orig_prompt is None
                    else req.orig_prompt),
            tokens=np.asarray(prefix + slot.generated, np.int32),
            finish_reason=reason,
            admitted_step=(slot.admitted_step if req.first_admitted is None
                           else req.first_admitted),
            finished_step=self._steps, preempted=req.preempted)
        self._emit("terminal", rid=req.request_id, slot=slot.idx,
                   reason=reason,
                   n_tokens=len(prefix) + len(slot.generated))
        if reason == "timeout":
            self._timeouts += 1
        elif reason == "error_numeric":
            self._quarantined += 1
        self._retired += 1
        if self._paged:
            self._free_all(slot.idx)
        if self._dynamic and slot.handle is not None:
            self._dyn_release(slot.handle)
        slot.req = None
        slot.handle = None
        slot.state = None
        slot.generated = []
        slot.prefilling = False

    def _note_token(self, slot: _Slot, tok: int, on_token) -> str | None:
        """Record one sampled token; returns the finish reason if the
        request is now done."""
        slot.generated.append(tok)
        slot.budget -= 1
        slot.last_token = tok
        self._generated += 1
        self._emit(("first_token"
                    if slot.n_prior + len(slot.generated) == 1
                    else "token"),
                   rid=slot.req.request_id, slot=slot.idx, token=tok)
        if on_token is not None:
            on_token(slot.req.request_id, tok)
        if slot.req.eos_id is not None and tok == slot.req.eos_id:
            return "eos"
        if slot.budget <= 0:
            return slot.finish_cap
        return None

    def _first_token(self, slot: _Slot, logits, on_token) -> None:
        """Complete an admission: fetch the prefill's last-position
        ``logits`` ([1, V]), sample and deliver the first token — or, when
        the row is non-finite, quarantine it before it ever decodes."""
        req = slot.req
        with span("engine.fetch"):
            row = np.asarray(logits)[0]
        with span("engine.sample", rows=1):
            if self._nan_targets([slot.idx]):
                row = np.full_like(row, np.nan)
                self._injected_nans += 1
            if not np.isfinite(row).all():
                self._emit("quarantined", rid=req.request_id, slot=slot.idx,
                           at="admission")
                self._finish(slot, "error_numeric")
                return
            tok = self._sample_rows([row], [(req.key_id, slot.n_prior)])[0]
        with span("engine.deliver"):
            reason = self._note_token(slot, tok, on_token)
            if reason is not None:
                self._finish(slot, reason)   # slot free again

    def _error_result(self, req: EngineRequest, e: Exception) -> None:
        res = RequestResult(
            request_id=req.request_id,
            prompt=(req.prompt if req.orig_prompt is None
                    else req.orig_prompt),
            tokens=np.asarray(
                [] if req.prefix is None else list(req.prefix), np.int32),
            finish_reason="error",
            admitted_step=(self._steps if req.first_admitted is None
                           else req.first_admitted),
            finished_step=self._steps, error_type=type(e).__name__,
            error_message=str(e), preempted=req.preempted)
        res._live_error = e
        self._results[req.request_id] = res
        self._emit("terminal", rid=req.request_id, reason="error",
                   error_type=type(e).__name__)

    def _timeout_queued(self, req: EngineRequest) -> None:
        """Retire a QUEUED request whose deadline expired: it never held
        (or no longer holds) a slot, so there is nothing to free — it
        just reports whatever earlier legs generated."""
        self._results[req.request_id] = RequestResult(
            request_id=req.request_id,
            prompt=(req.prompt if req.orig_prompt is None
                    else req.orig_prompt),
            tokens=np.asarray(
                [] if req.prefix is None else list(req.prefix), np.int32),
            finish_reason="timeout",
            admitted_step=(self._steps if req.first_admitted is None
                           else req.first_admitted),
            finished_step=self._steps, preempted=req.preempted)
        self._emit("terminal", rid=req.request_id, reason="timeout",
                   queued=True)
        self._timeouts += 1

    def _expire_deadlines(self) -> None:
        """Retire every request — queued or mid-decode — whose absolute
        deadline step has arrived, with ``finish_reason="timeout"``."""
        if any(r.deadline_step is not None and self._steps >= r.deadline_step
               for r in self._queue):
            keep: deque[EngineRequest] = deque()
            for req in self._queue:
                if (req.deadline_step is not None
                        and self._steps >= req.deadline_step):
                    self._timeout_queued(req)
                else:
                    keep.append(req)
            self._queue = keep
        for slot in self._slots:
            if (slot.occupied and slot.req.deadline_step is not None
                    and self._steps >= slot.req.deadline_step):
                self._finish(slot, "timeout")

    def _apply_tick_faults(self) -> None:
        """Consult the FaultPlan once at the top of the tick (no-op
        without a plan): straggler sleeps fire immediately, evictions hit
        the adapter cache, stale/NaN injections arm flags that the
        admission / sampling paths consume."""
        plan = self.fault_plan
        self._nan_tick = ()
        if plan is None:
            return
        d = plan.slow_at(self._steps)
        if d > 0:
            time.sleep(d)
            self._slow_ticks += 1
            self._emit("fault", kind="slow", seconds=d)
        if plan.evict_at(self._steps) and self.adapter_cache is not None:
            # Pinned slot/request states are untouched (containment); the
            # NEXT cold lookup pays a re-precompute — or errors, under
            # warm-only routing.
            self.adapter_cache.invalidate()
            self._forced_evictions += 1
            self._emit("fault", kind="evict")
        if plan.stale_at(self._steps):
            self._stale_pending = True
            self._emit("fault", kind="stale")
        self._nan_tick = plan.nan_slots(self._steps)
        if self._nan_tick:
            self._emit("fault", kind="nan",
                       slots=[(-1 if t is None else int(t))
                              for t in self._nan_tick])

    def _nan_targets(self, rows: list[int]) -> list[int]:
        """Which of ``rows`` this tick's plan poisons (None = all)."""
        if not self._nan_tick:
            return []
        if any(t is None for t in self._nan_tick):
            return list(rows)
        return [i for i in rows if i in self._nan_tick]

    def _poison(self, rows: list[int], logits_np: np.ndarray) -> np.ndarray:
        """Overwrite the planned rows with NaN on the host mirror.
        ``np.asarray`` of a jax array is read-only, so injection copies
        first; the no-fault path never copies."""
        targets = self._nan_targets(rows)
        if not targets:
            return logits_np
        logits_np = np.array(logits_np)
        for i in targets:
            logits_np[i] = np.nan
            self._injected_nans += 1
        return logits_np

    def _adapter_eligible(self, req: EngineRequest) -> bool:
        """Per-adapter admission rate limit (``max_active_per_adapter``):
        a request is held in the queue — WITHOUT losing its position —
        while its adapter already occupies that many slots, so one hot
        tenant's burst cannot monopolise the slot table and starve the
        fleet. No limit set (or a fixed-adapter engine): always True."""
        if self.max_active_per_adapter is None or req.adapter is None:
            return True
        n = sum(1 for s in self._slots
                if s.occupied and s.handle is not None
                and s.handle.adapter_id == req.adapter.adapter_id)
        return n < self.max_active_per_adapter

    def _pop_next(self) -> EngineRequest | None:
        """Pop the highest-priority ELIGIBLE queued request (earliest
        submitted among equals — all-default-priority queues stay exactly
        FIFO); None when every queued request is rate-limited by
        ``max_active_per_adapter`` (ineligible requests keep their queue
        positions)."""
        best = -1
        for j, r in enumerate(self._queue):
            if not self._adapter_eligible(r):
                continue
            if best < 0 or r.priority > self._queue[best].priority:
                best = j
        if best < 0:
            return None
        if best == 0:
            return self._queue.popleft()
        self._queue.rotate(-best)
        req = self._queue.popleft()
        self._queue.rotate(best)
        return req

    def _preempt(self, idx: int) -> None:
        """Displace slot ``idx``: re-queue its request as a CONTINUATION
        whose prompt is (prompt + generated-so-far) — re-admission
        re-prefills that through the traced prefill-into-slot, and the
        resumed stream is bitwise the uninterrupted one (the re-prefill's
        final-position logits ARE the plain decode logits at that
        frontier, and the sample-key fold count continues via n_prior).
        The continuation always fits: P' + budget' = P + budget <=
        max_len keeps room for every remaining token.

        A slot still MID-ADMISSION (paged chunked prefill) re-queues its
        request UNCHANGED — it has produced nothing yet, so there is no
        continuation to build — and returns its reserved blocks."""
        slot = self._slots[idx]
        req = slot.req
        self._emit("preempted", rid=req.request_id, slot=idx,
                   mid_admission=slot.prefilling,
                   n_generated=len(slot.generated))
        if slot.prefilling:
            self._queue.append(dataclasses.replace(
                req, preempted=req.preempted + 1))
            self._preemptions += 1
            self._free_all(idx)
            if self._dynamic and slot.handle is not None:
                self._dyn_release(slot.handle)
            slot.req = None
            slot.handle = None
            slot.state = None
            slot.generated = []
            slot.prefilling = False
            return
        gen = np.asarray(slot.generated, np.int32)
        self._queue.append(dataclasses.replace(
            req,
            prompt=np.concatenate([req.prompt, gen]),
            max_new_tokens=slot.budget,
            prefix=(gen if req.prefix is None
                    else np.concatenate([req.prefix, gen])),
            orig_prompt=(req.prompt if req.orig_prompt is None
                         else req.orig_prompt),
            resume_cap=slot.finish_cap,
            first_admitted=(slot.admitted_step if req.first_admitted is None
                            else req.first_admitted),
            preempted=req.preempted + 1))
        self._preemptions += 1
        if self._paged:
            self._free_all(idx)
        if self._dynamic and slot.handle is not None:
            self._dyn_release(slot.handle)
        slot.req = None
        slot.handle = None
        slot.state = None
        slot.generated = []

    def _admit_into(self, idx: int, slot: _Slot, req: EngineRequest,
                    on_token) -> bool:
        """One admission. Rectangular path: prefill INTO slot ``idx`` +
        first sampled token (a request whose budget is one token retires
        here without ever occupying a decode row). Paged path: SEAT the
        request (reserve blocks for the whole prompt + the first decode
        write) and mark the slot ``prefilling`` — the prompt streams in
        over :meth:`_chunk_tick` chunks, and the first token is sampled
        by the FINAL chunk. Returns False only when a paged admission is
        DEFERRED (the pool cannot hold the prompt right now; the request
        goes back to the queue head and this tick stops admitting)."""
        if self._stale_pending and req.adapter is not None:
            # Fault injection: hand the admission a handle whose version
            # the registry never issued, with the pinned state stripped —
            # the late-resolution path below then raises the cache's REAL
            # stale error (version mismatch), not a simulation of it.
            self._stale_pending = False
            self._stale_injected += 1
            req = dataclasses.replace(
                req, adapter=dataclasses.replace(
                    req.adapter, version=req.adapter.version + 1),
                state=None)
        try:
            # submit() pins the resolved tree on the request, so
            # normally this is a plain attribute read immune to
            # mid-queue cache churn; the late-resolution fallback
            # only fires for hand-built EngineRequests.
            state = (req.state if req.state is not None
                     else self._resolve_state(req))
        except Exception as e:
            # A failed LATE resolution must neither silently
            # lose the request nor wedge the FIFO behind it
            # forever: the request is finished with an errored
            # result and admission moves on to the next one.
            self._error_result(req, e)
            return True
        P = req.prompt.shape[0]
        if self._paged:
            # Admission-start gate: the WHOLE prompt (+ the first decode
            # write) is reserved up front, so chunked prefill can never
            # strand a half-admitted prompt on pool exhaustion. When the
            # pool cannot cover it, the request defers at the queue HEAD
            # (documented head-of-line policy: decode keeps running and
            # retirements will free blocks) rather than being skipped.
            need = -(-(P + 1) // self._block_size)
            if len(self._free) < need:
                self._queue.appendleft(req)
                return False
            slot.req = req
            slot.handle = req.adapter
            slot.state = state
            if self._dynamic:
                self._dyn_assign(idx, req.adapter, state, req.request_id)
            slot.admitted_step = self._steps
            slot.pos = 0
            slot.n_prior = (0 if req.prefix is None
                            else int(req.prefix.shape[0]))
            slot.generated = []
            slot.prefilling = True
            slot.chunk_next = 0
            self._ensure_blocks(idx, P + 1)
            self._emit("admitted", rid=req.request_id, slot=idx,
                       prompt_len=P, paged=True)
            if req.preempted:
                self._emit("resumed", rid=req.request_id, slot=idx,
                           attempt=req.preempted)
            return True
        if self._dynamic:
            # Claim the fleet-stack position BEFORE the prefill: a
            # budget-1 request that retires inside this admission still
            # releases a position it actually held.
            self._dyn_assign(idx, req.adapter, state, req.request_id)
        toks = np.zeros((1, self.max_len), np.int32)
        toks[0, :P] = req.prompt
        logits, self.cache = self._prefill(
            self.params, state, self.cache,
            {"tokens": jnp.asarray(toks),
             "prompt_len": jnp.asarray(P, jnp.int32),
             "slot": jnp.asarray(idx, jnp.int32)})
        self._prefills += 1
        self._admitted += 1
        slot.req = req
        slot.handle = req.adapter
        slot.state = state
        slot.admitted_step = self._steps
        self._emit("admitted", rid=req.request_id, slot=idx, prompt_len=P)
        if req.preempted:
            self._emit("resumed", rid=req.request_id, slot=idx,
                       attempt=req.preempted)
        slot.pos = P    # first decode K/V write lands at P
        slot.n_prior = 0 if req.prefix is None else int(req.prefix.shape[0])
        # Token budget: the request's own cap, or the cache bound
        # (P + budget - 1 decode writes must stay < max_len; the
        # last sampled token is never written back). A continuation
        # carries its ORIGINAL cap label (resume_cap): its shrunken
        # budget always fits the remaining room, so recomputing the
        # label here would misreport a capped request as "length".
        room = self.max_len - P
        slot.budget = min(req.max_new_tokens, room)
        slot.finish_cap = (req.resume_cap if req.resume_cap is not None
                           else ("length" if req.max_new_tokens <= room
                                 else "max_len"))
        self._first_token(slot, logits, on_token)
        return True

    def _admit(self, on_token=None) -> None:
        """Fill free slots from the queue (highest priority first, FIFO
        among equals), then preempt: while a queued request outranks the
        lowest-priority OCCUPIED slot and no slot is free, that victim is
        displaced (re-queued as a continuation — a mid-admission slot
        re-queues its request unchanged) and the fill loop seats the
        outranking request in its row. Each preemption strictly raises
        the displaced slot's priority, so the loop terminates. A paged
        admission deferred on block exhaustion stops the whole tick's
        admitting (head-of-line)."""
        while True:
            for idx, slot in enumerate(self._slots):
                while not slot.occupied and self._queue:
                    req = self._pop_next()
                    if req is None:
                        break   # every queued request is rate-limited
                    if not self._admit_into(idx, slot, req, on_token):
                        return
            if not self._queue:
                return
            # Preemption considers ELIGIBLE queued requests only: a
            # rate-limited request must not displace anyone (it could
            # not be seated in the freed slot anyway).
            elig = [r.priority for r in self._queue
                    if self._adapter_eligible(r)]
            if not elig:
                return
            best = max(elig)
            occupied = [i for i, s in enumerate(self._slots) if s.occupied]
            if not occupied:
                return
            victim = min(occupied,
                         key=lambda i: (self._slots[i].req.priority, i))
            if best <= self._slots[victim].req.priority:
                return
            self._preempt(victim)

    # -- dynamic fleet stack (traced grouping) ------------------------------

    def _dyn_insert_fn(self):
        """ONE jitted fleet-stack writer: position traced, stack donated —
        admissions at every position share a single executable
        (``compile_counts()["adapter_insert"]``)."""
        if self._dyn_insert is None:
            def insert(stack, state, pos):
                def upd(big, leaf):
                    starts = (jnp.zeros((), jnp.int32), pos) + tuple(
                        jnp.zeros((), jnp.int32)
                        for _ in range(leaf.ndim - 1))
                    return jax.lax.dynamic_update_slice(
                        big, jnp.expand_dims(leaf, 1).astype(big.dtype),
                        starts)
                return jax.tree_util.tree_map(upd, stack, state)
            self._dyn_insert = jax.jit(insert, donate_argnums=(0,))
        return self._dyn_insert

    def _dyn_assign(self, idx: int, handle, state, request_id: int) -> None:
        """Give slot ``idx`` (seating ``request_id``) a fleet-stack
        position for ``handle``: slots sharing a handle share its position
        (refcounted), a NEW handle claims a free position and writes its
        serving tree there (the one churn-time device copy — decode ticks
        never restack). The stack is built lazily from the first state's
        leaf shapes (zeros rows: finite garbage nothing indexes)."""
        ent = self._dyn_pos.get(handle)
        if ent is not None:
            ent[1] += 1
        else:
            pos = self._dyn_free.pop()
            self._dyn_pos[handle] = ent = [pos, 1]
            if self._dyn_stack is None:
                self._dyn_stack = jax.tree_util.tree_map(
                    lambda l: jnp.zeros(
                        (l.shape[0], self.slots) + l.shape[1:], l.dtype),
                    state)
            with span("engine.stack_insert", request_id=request_id,
                      slot=idx):
                self._dyn_stack = self._dyn_insert_fn()(
                    self._dyn_stack, state, jnp.asarray(pos, jnp.int32))
            self._stack_inserts += 1
        self._dyn_idx_np[idx] = ent[0]
        self._dyn_idx_cached = None

    def _dyn_release(self, handle) -> None:
        """Drop one slot's claim on ``handle``'s position; the LAST claim
        recycles it (the stale stack row needs no zeroing — no live row's
        index points at it)."""
        ent = self._dyn_pos.get(handle)
        if ent is None:
            return
        ent[1] -= 1
        if ent[1] == 0:
            del self._dyn_pos[handle]
            self._dyn_free.append(ent[0])

    def _dyn_idx(self):
        """Device mirror of the per-slot position vector — the traced
        ``batch_in["adapter_idx"]`` operand; rebuilt only when an
        admission moved a slot's index, never per token. Free slots keep
        a stale (in-range) position: their rows decode garbage nothing
        reads, exactly like the static path's absorbed free slots."""
        if self._dyn_idx_cached is None:
            self._dyn_idx_cached = jnp.asarray(np.array(self._dyn_idx_np))
        return self._dyn_idx_cached

    def _slot_grouping(self):
        """(tenant_groups | None, adapter tree) for the CURRENT slot
        table. Free slots are absorbed into a neighbouring run (their
        rows decode garbage that nothing reads), so the signature only
        changes when the handle layout of OCCUPIED slots changes — a
        paged slot mid-chunked-admission already counts, so a prompt
        streaming in does not flap the signature when it joins decode —
        and the (groups, stacked-tree) pair is cached on that layout:
        re-stacking every tenant's full serving tree is a device-side
        copy that must happen per admission/retirement, not per sampled
        token."""
        if self.adapter_cache is None:
            return None, self.adapters
        if self._dynamic:
            # The signature is the CONSTANT "dynamic": churn moved values
            # (stack rows, index vector), never the trace.
            return "dynamic", self._dyn_stack
        layout = tuple((s.handle if s.occupied else None)
                       for s in self._slots)
        if self._grouping_cache is not None \
                and self._grouping_cache[0] == layout:
            return self._grouping_cache[1], self._grouping_cache[2]
        keys: list[Any] = list(layout)
        states = {s.handle: s.state for s in self._slots if s.occupied}
        # forward fill from the left, then leading Nones from the right
        last = None
        for i, k in enumerate(keys):
            if k is None:
                keys[i] = last
            else:
                last = k
        nxt = None
        for i in reversed(range(len(keys))):
            if keys[i] is None:
                keys[i] = nxt
            else:
                nxt = keys[i]
        if len(set(keys)) == 1:
            groups, adapters = None, states[keys[0]]
        else:
            runs: list[tuple[Any, int]] = []
            for k in keys:
                if runs and runs[-1][0] == k:
                    runs[-1] = (k, runs[-1][1] + 1)
                else:
                    runs.append((k, 1))
            groups, start = [], 0
            for _, n in runs:
                groups.append((start, n))
                start += n
            groups = tuple(groups)
            adapters = stack_adapter_states([states[k] for k, _ in runs],
                                            axis=1)
        self._grouping_cache = (layout, groups, adapters)
        return groups, adapters

    def _get_decode(self, groups):
        if groups in self._decodes:
            self._decodes.move_to_end(groups)
            return self._decodes[groups]
        dyn = groups == "dynamic"
        fn = jax.jit(make_decode_step(self.mcfg, self.scfg, self.mesh,
                                      batch=self.slots,
                                      tenant_groups=None if dyn else groups,
                                      dynamic_groups=dyn),
                     donate_argnums=(2,),
                     out_shardings=(None, self._cache_out_sh))
        self._decodes[groups] = fn
        while len(self._decodes) > self.max_cached_steps:
            self._decodes.popitem(last=False)
        return fn

    def _get_draft(self):
        if self._draft is None:
            self._draft = jax.jit(
                make_draft_step(self.mcfg, self.scfg, self.mesh,
                                batch=self.slots),
                donate_argnums=(1,),
                out_shardings=(None, self._cache_out_sh))
        return self._draft

    def _get_verify(self, groups, window: int):
        key = (groups, window)
        if key in self._verifies:
            self._verifies.move_to_end(key)
            return self._verifies[key]
        dyn = groups == "dynamic"
        fn = jax.jit(make_verify_step(self.mcfg, self.scfg, self.mesh,
                                      batch=self.slots, window=window,
                                      tenant_groups=None if dyn else groups,
                                      dynamic_groups=dyn),
                     donate_argnums=(2,),
                     out_shardings=(None, self._cache_out_sh))
        self._verifies[key] = fn
        while len(self._verifies) > self.max_cached_steps:
            self._verifies.popitem(last=False)
        return fn

    def _sync_len(self, lens: np.ndarray) -> None:
        """Overwrite ``cache["len"]`` with a host-built per-row vector —
        the speculative rewind. A FRESH device array every time: the
        steps donate the cache, so yesterday's ``len`` buffer may
        already be dead. Free rows get 0 (their buffer content is
        garbage either way — admission prefills the whole row)."""
        arr = jnp.asarray(np.asarray(lens, np.int32))
        if self._cache_out_sh is not None:
            arr = jax.device_put(arr, self._cache_out_sh["len"])
        cache = dict(self.cache)
        cache["len"] = arr.astype(cache["len"].dtype)
        self.cache = cache

    def _speculative_ok(self, active: list[int]) -> bool:
        """Whether THIS tick can draft-and-verify: greedy sampling only
        (rejection sampling for temperature>0 is future work) and every
        active row's k+1-window must fit under ``max_len`` — a clamped
        ``dynamic_update_slice`` would silently shift a row's writes.
        Rows with ≥ k remaining budget always fit (the admission budget
        keeps ``pos + budget <= max_len - 1``); a row at its max_len cap
        degrades the whole batch to plain decode for its last tokens.

        Degradation ladder: when the measured accept rate over the last
        ``spec_window`` speculative ticks collapses below
        ``spec_accept_floor`` (drafts are just burning forwards), the
        engine falls back to plain decode for ``spec_reenable_after``
        ticks, then retries — hysteresis, so a borderline adapter does
        not flap every tick."""
        if self.speculative_k <= 0 or self.temperature > 0.0:
            return False
        if self._spec_cooldown > 0:
            self._spec_cooldown -= 1
            if self._spec_cooldown == 0:
                self._spec_reenables += 1
                self._emit("spec_reenabled")
            return False
        k = self.speculative_k
        if not all(self._slots[i].pos + k + 1 <= self.max_len
                   for i in active):
            return False
        if self._paged:
            # A mid-admission slot degrades the tick to plain decode: the
            # draft loop would advance ITS device length k+1 positions
            # past the host chunk cursor, beyond what the next chunk
            # rewrites. And the whole k+1 window must be block-backed up
            # front — on exhaustion, fall back to plain decode (which
            # needs one block at most) instead of preempting for
            # speculation.
            if any(s.prefilling for s in self._slots):
                return False
            if not all(self._ensure_blocks(i, self._slots[i].pos + k + 1)
                       for i in active):
                return False
        return True

    def _quarantine(self, rows: list[int], logits_np: np.ndarray
                    ) -> tuple[list[int], np.ndarray]:
        """Per-row non-finite guard over the already-fetched host logits
        (zero extra device syncs): poisoned rows — injected or genuine —
        retire with ``finish_reason="error_numeric"``; the survivors'
        streams are untouched (attention and compose are row-local, so a
        quarantined neighbour never perturbs a live row's logits).
        Returns (surviving rows, possibly-poisoned logits)."""
        logits_np = self._poison(rows, logits_np)
        flat = logits_np.reshape(logits_np.shape[0], -1)
        bad = [i for i in rows if not np.isfinite(flat[i]).all()]
        for i in bad:
            self._emit("quarantined", rid=self._slots[i].req.request_id,
                       slot=i, at="decode")
            self._finish(self._slots[i], "error_numeric")
        if bad:
            rows = [i for i in rows if self._slots[i].active]
        return rows, logits_np

    def _chunk_tick(self, on_token) -> None:
        """Paged chunked admission: ONE prompt chunk per mid-admission
        slot per tick, through the traced batch-1 chunk step (slot,
        start, chunk length all traced — one executable total). Chunk
        starts are ``0, C, 2C, ...`` with the FINAL chunk re-anchored at
        ``P - C`` (when P > C): its window overlaps the previous chunk
        and rewrites those positions with bitwise-identical K/V, which
        keeps every start in-range for the clamping dynamic-slice write.
        The final chunk's last-position logits are the whole-prompt
        prefill logits bitwise (causal rows are independent, earlier
        chunks committed identical K/V), so the first token it samples —
        and the NaN quarantine guarding it — match the rectangular
        admission exactly.

        Between a slot's chunks, the batched decode advances EVERY row's
        device length by one and writes one garbage K/V row at the
        mid-admission slot's drifted frontier; the chunk step takes its
        start from the HOST mirror, and the drifted position always
        falls inside the NEXT chunk's window, so the garbage is
        overwritten before the final chunk reads it."""
        for idx, slot in enumerate(self._slots):
            if not slot.prefilling:
                continue
            req = slot.req
            P = req.prompt.shape[0]
            C = self._chunk
            final = P - slot.chunk_next <= C
            if final:
                c_len = min(P, C)
                start = P - c_len
            else:
                start, c_len = slot.chunk_next, C
            toks = np.zeros((1, C), np.int32)
            toks[0, :c_len] = req.prompt[start:start + c_len]
            self._emit("chunk_prefill", rid=req.request_id, slot=idx,
                       start=start, chunk_len=c_len, final=final)
            self._flush_pages()
            with span("engine.chunk", request_id=req.request_id, slot=idx,
                      start=start, tokens=c_len):
                logits, self.cache = self._chunk_prefill(
                    self.params, slot.state, self.cache,
                    {"tokens": jnp.asarray(toks),
                     "slot": jnp.asarray(idx, jnp.int32),
                     "start": jnp.asarray(start, jnp.int32),
                     "chunk_len": jnp.asarray(c_len, jnp.int32)})
            if not final:
                slot.chunk_next = start + C
                continue
            # Final chunk: admission completes — the slot joins decode
            # THIS tick (a prompt that fits one chunk matches the
            # rectangular admission schedule exactly).
            slot.prefilling = False
            slot.pos = P
            self._prefills += 1
            self._admitted += 1
            room = self.max_len - P
            slot.budget = min(req.max_new_tokens, room)
            slot.finish_cap = (req.resume_cap if req.resume_cap is not None
                               else ("length" if req.max_new_tokens <= room
                                     else "max_len"))
            self._first_token(slot, logits, on_token)

    def _decode_tick(self, active: list[int], on_token) -> None:
        """One plain batched decode over the active slots."""
        if self._paged:
            active = self._ensure_active_blocks(active, 1)
            if not active:
                return
            self._flush_pages()
        groups, adapters = self._slot_grouping()
        decode = self._get_decode(groups)
        with span("engine.decode", rows=len(active)):
            toks = np.zeros((self.slots, 1), np.int32)
            for i in active:
                toks[i, 0] = self._slots[i].last_token
            batch_in = {"tokens": jnp.asarray(toks)}
            if groups == "dynamic":
                batch_in["adapter_idx"] = self._dyn_idx()
            logits, self.cache = decode(self.params, adapters, self.cache,
                                        batch_in)
        with span("engine.fetch"):
            logits_np = np.asarray(logits)      # the sampling sync
        self._decode_steps += 1
        self._slot_steps += len(active)
        with span("engine.sample", rows=len(active)):
            active, logits_np = self._quarantine(active, logits_np)
            toks_out = self._sample_rows(
                [logits_np[i] for i in active],
                [(self._slots[i].req.key_id,
                  self._slots[i].n_prior + len(self._slots[i].generated))
                 for i in active])
        with span("engine.deliver"):
            for i, tok in zip(active, toks_out):
                slot = self._slots[i]
                slot.pos += 1               # this decode wrote K/V at pos
                reason = self._note_token(slot, tok, on_token)
                if reason is not None:
                    self._finish(slot, reason)

    def _speculative_tick(self, active: list[int], on_token) -> None:
        """Draft k base-only tokens per row, verify the k+1 window in one
        full-DoRA forward, accept each row's longest matching prefix and
        rewind its cache length to the accepted frontier.

        Cache discipline: the drafts write BASE-path K/V at positions
        pos..pos+k-1; the verify then rewinds to pos and overwrites
        positions pos..pos+k with FULL-path K/V, so nothing base-flavored
        is ever attended to by a committed token. After acceptance each
        row rewinds to pos + emitted (the slot's next write position);
        rows beyond that frontier hold stale K/V that the per-row causal
        mask excludes until overwritten."""
        k = self.speculative_k
        base_len = np.zeros((self.slots,), np.int32)
        for i in active:
            base_len[i] = self._slots[i].pos
        cur = np.zeros((self.slots, 1), np.int32)
        for i in active:
            cur[i, 0] = self._slots[i].last_token

        # -- draft: k greedy base-only tokens per row -----------------------
        self._sync_len(base_len)
        if self._paged:
            self._flush_pages()   # _speculative_ok grew the k+1 window
        draft = self._get_draft()
        drafts = np.zeros((self.slots, k), np.int32)
        for j in range(k):
            with span("engine.decode", rows=len(active)):
                logits, self.cache = draft(self.params, self.cache,
                                           {"tokens": jnp.asarray(cur)})
            with span("engine.fetch"):
                lnp = np.asarray(logits)
            self._draft_steps += 1
            with span("engine.sample", rows=len(active)):
                for i in active:
                    t = int(np.argmax(lnp[i]))
                    drafts[i, j] = t
                    cur[i, 0] = t

        # -- verify: ONE grouped full-DoRA forward over [t0, q1..qk] --------
        self._sync_len(base_len)    # rewind over the drafts' len advance
        win = np.zeros((self.slots, k + 1), np.int32)
        for i in active:
            win[i, 0] = self._slots[i].last_token
            win[i, 1:] = drafts[i]
        groups, adapters = self._slot_grouping()
        verify = self._get_verify(groups, k + 1)
        with span("engine.decode", rows=len(active)):
            batch_in = {"tokens": jnp.asarray(win)}
            if groups == "dynamic":
                batch_in["adapter_idx"] = self._dyn_idx()
            logits, self.cache = verify(self.params, adapters, self.cache,
                                        batch_in)
        with span("engine.fetch"):
            logits_np = np.asarray(logits)       # [slots, k+1, V]
        self._verify_steps += 1
        with span("engine.sample", rows=len(active)):
            # Quarantine BEFORE acceptance: a poisoned row emits nothing
            # (its verify window is garbage end to end) and its rewind
            # target is 0 — the freed row's buffer is garbage either way.
            active, logits_np = self._quarantine(active, logits_np)
            # true[i][j] = the token plain decode would emit after window
            # position j (valid as long as window[:j+1] matches the true
            # stream — which holds exactly up to the first draft miss).
            true = {i: np.argmax(logits_np[i], axis=-1) for i in active}

        # -- accept: longest matching prefix per row, then rewind -----------
        accepted_this = 0
        new_len = np.zeros((self.slots,), np.int32)
        with span("engine.deliver"):
            for i in active:
                slot = self._slots[i]
                a = 0
                while a < k and drafts[i, a] == true[i][a]:
                    a += 1
                self._accepted_drafts += a
                accepted_this += a
                # emit true[0..a]: the a accepted drafts plus the verify's
                # own next token (a rejected draft's correction, or the
                # bonus token after a fully-accepted window).
                for tok in true[i][:a + 1]:
                    slot.pos += 1
                    reason = self._note_token(slot, int(tok), on_token)
                    if reason is not None:
                        self._finish(slot, reason)
                        break
                if slot.active:
                    new_len[i] = slot.pos
        if self._paged:
            # Speculative rewind frees the dead tail: blocks past each
            # surviving row's accepted frontier (allocated for the k+1
            # window) return to the pool; finished rows already freed
            # everything in _finish.
            for i in active:
                if self._slots[i].active:
                    self._free_tail(i, self._slots[i].pos)
        self._sync_len(new_len)

        # -- degradation ladder: track the accept rate ----------------------
        if active and self.spec_accept_floor > 0.0:
            self._spec_rates.append(accepted_this / (k * len(active)))
            if len(self._spec_rates) > self.spec_window:
                self._spec_rates.pop(0)
            if (len(self._spec_rates) == self.spec_window
                    and (sum(self._spec_rates) / self.spec_window)
                    < self.spec_accept_floor):
                self._spec_cooldown = self.spec_reenable_after
                self._spec_disables += 1
                self._emit("spec_disabled",
                           cooldown=self.spec_reenable_after)
                self._spec_rates.clear()

    def step(self, on_token=None) -> list[RequestResult]:
        """One scheduler tick: apply this tick's planned faults, expire
        deadlines, admit into free slots (preempting lower-priority rows
        when an outranking request is queued), then one batched decode —
        or draft/verify/rewind when ``speculative_k > 0`` — over every
        active slot. Returns the requests that FINISHED during this tick
        (also retrievable via :meth:`results`).
        ``on_token(request_id, token)`` streams every sampled token."""
        with span("engine.tick", tick=self._steps):
            before = set(self._results)
            self._apply_tick_faults()
            self._expire_deadlines()
            if self._queue:
                with span("engine.admit", queued=len(self._queue)):
                    self._admit(on_token)
            if self._paged:
                self._chunk_tick(on_token)
            active = [i for i, s in enumerate(self._slots) if s.active]
            if active:
                if self._speculative_ok(active):
                    self._speculative_tick(active, on_token)
                else:
                    self._decode_tick(active, on_token)
            self._nan_tick = ()
            self._steps += 1
            return [self._results[rid]
                    for rid in sorted(set(self._results) - before)]

    def run(self, on_token=None) -> list[RequestResult]:
        """Drive :meth:`step` until the queue and slot table drain, then
        deliver (and DROP — the engine persists across calls, so results
        are handed over exactly once rather than retained forever) every
        undelivered finished result, ordered by request id."""
        while self.has_work():
            self.step(on_token)
        return self.pop_results()

    def results(self) -> list[RequestResult]:
        """Finished-but-undelivered results, oldest request first (kept
        until :meth:`run`/:meth:`pop_results` hands them over — a manual
        :meth:`step` driver should pop periodically, or the retained
        history grows with every request served)."""
        return [self._results[rid] for rid in sorted(self._results)]

    def pop_results(self) -> list[RequestResult]:
        """:meth:`results`, handing ownership over: the returned results
        are removed from the engine's retained set."""
        out = self.results()
        self._results.clear()
        return out
