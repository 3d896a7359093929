"""Serving driver: batched prefill + decode with per-request adapter routing.

CPU-runnable with a smoke config::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \
        --batch 2 --prompt-len 32 --gen-len 16 [--tenants 3] [--continuous]

Implements the production serving shape (docs/serving.md):

  - **one jitted precompute per adapter set** — the frozen-adapter state
    (w_norm/g cached once; the decode loop does zero factored-norm work
    per token), held in an :class:`repro.core.AdapterStateCache` LRU keyed
    by (adapter id, version, dtype, sharding) with byte-bounded eviction;
  - **request-routed batches** — every request carries an adapter handle;
    :class:`MultiTenantServer` groups the batch's rows by adapter and
    serves heterogeneous-adapter batches in ONE prefill/decode step via
    the grouped gsB-folded compose (``repro.core.dora_linear_grouped``).
    Homogeneous batches take today's single-tenant path bitwise;
  - **shape-bucketed prefill** — one jitted prefill (prompt right-padded
    to ``max_len``, true P traced) serves every prompt length on
    attention-only archs, with the cache length rewound to P; one jitted
    decode step is re-used per token (cache donated = in place).

Sampling is greedy/temperature on the host — the device step is exactly
the ``serve_step`` the ``decode_*``/``long_*`` dry-run cells lower.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core import DoRAConfig
from repro.core.adapter import stack_adapter_states
from repro.core.adapter_cache import (AdapterHandle, AdapterStateCache,
                                      mesh_fingerprint)
from repro.launch import compile_cache
from repro.launch.steps import StepConfig, make_decode_step, \
    make_precompute_step, make_prefill_step
from repro.launch.train import build_state
# monotonic (time.perf_counter) for every wall-clock delta: time.time()
# can step backwards under NTP and is banned from latency math here
# (the one sanctioned epoch-time user is the checkpoint heartbeat).
from repro.obs import TraceRecorder, engine_metrics, monotonic


def _check_cache_mesh(cache: AdapterStateCache, mesh) -> None:
    """The cache keys states on the mesh they were pinned for — serving
    them under a DIFFERENT mesh would re-lay-out g/gsB every step, the
    exact per-token work the cache exists to remove. Refuse loudly."""
    want = mesh_fingerprint(mesh)
    if cache.sharding != want:
        raise ValueError(
            f"adapter cache is keyed for sharding {cache.sharding} but "
            f"serving runs on mesh {want} — build the cache with "
            f"AdapterStateCache.for_serving(mcfg, scfg, mesh) for THIS "
            f"mesh so cached states land pre-pinned to its shardings")


def _sample(last, temperature, key):
    if temperature > 0.0:
        key, sub = jax.random.split(key)
        nxt = jax.random.categorical(sub, last / temperature, axis=-1)
    else:
        nxt = jnp.argmax(last, axis=-1)
    return nxt.astype(jnp.int32)[:, None], key


def _contract_checks_enabled() -> bool:
    """The prefill/decode cache-length contract checks call ``int()`` on
    a device scalar — a host sync per prefill (and per first decode) that
    stalls the pipeline. They default OFF in the serving path; the
    contract itself stays hard-error (not assert) and is locked by tests,
    which force the checks on via ``check_contract=True``. Set
    ``REPRO_SERVE_DEBUG=1`` to re-enable them operationally."""
    return os.environ.get("REPRO_SERVE_DEBUG", "") not in ("", "0")


def _decode_loop(prefill, decode, params, adapters, toks, *, prompt_len,
                 gen_len, pad, temperature, seed, collect_logits=False,
                 check_contract: bool | None = None):
    """The shared prefill → sample → decode loop. Returns (tokens
    [B, P+gen_len], logits-per-sampled-token list or None).

    ``check_contract``: run the blocking cache-length contract checks
    (None = the ``REPRO_SERVE_DEBUG`` env switch; see
    :func:`_contract_checks_enabled`)."""
    check = (_contract_checks_enabled() if check_contract is None
             else check_contract)
    P = prompt_len
    batch_in = {"tokens": toks}
    if pad:
        batch_in = {"tokens": jnp.pad(toks, ((0, 0), (0, pad))),
                    "prompt_len": jnp.asarray(P, jnp.int32)}
    logits, cache = prefill(params, adapters, batch_in)
    # The decode contract: the cache stands at exactly the true prompt
    # length, so the first generated token is written at position P.
    # (Hard errors, not asserts — the contract must survive python -O —
    # but behind the debug switch: each int() is a device sync.)
    if check and int(cache["len"]) != P:
        raise RuntimeError(
            f"prefill left cache at {int(cache['len'])}, expected {P}")

    key = jax.random.PRNGKey(seed)
    out = [toks]
    steps_logits = [] if collect_logits else None
    last = logits
    for i in range(gen_len):
        if collect_logits:
            steps_logits.append(np.asarray(last))
        nxt, key = _sample(last, temperature, key)
        out.append(nxt)
        last, cache = decode(params, adapters, cache, {"tokens": nxt})
        if check and i == 0 and int(cache["len"]) != P + 1:
            raise RuntimeError(
                f"decode wrote at {int(cache['len']) - 1}, expected {P}")
    return jnp.concatenate(out, axis=1), steps_logits


def generate(mcfg, params, adapters, scfg: StepConfig, prompts, *,
             gen_len: int, max_len: int, temperature: float = 0.0,
             seed: int = 0, cache_adapters: bool = True,
             fold_gsb: bool = False, mesh=None, adapter_cache=None,
             allow_miss: bool = True, return_logits: bool = False,
             check_contract: bool | None = None):
    """prompts: int32 [B, P]. Returns tokens [B, P+gen_len] (or
    (tokens, per-step logits) when ``return_logits``).

    ``adapters`` is either an adapter tree (single-tenant, as before) or
    an :class:`~repro.core.AdapterHandle` resolved through
    ``adapter_cache`` (an :class:`~repro.core.AdapterStateCache`). A
    handle that misses the cache while ``allow_miss=False`` is rejected
    with an error naming the key fields — the guard against a caller
    swapping adapters without re-precomputing and silently serving stale
    logits. A stale handle (version behind the registry) is ALWAYS
    rejected.

    ``cache_adapters``: precompute the frozen-adapter serving state (cached
    g) before prefill — bitwise-identical tokens, no per-token norm work.
    ``fold_gsb``: additionally fold g·s into B (broadcast-free decode
    compose; last-ulp numerics difference, so off by default).
    ``mesh``: SPMD serving — the precompute pins the cached state to the
    serving shardings (gsB row-sharded like B) and prefill/decode attach
    the boundary constraints, so the sharded steps run the same
    matmul-fused compose as the single-device loop.
    """
    if isinstance(adapters, AdapterHandle):
        if adapter_cache is None:
            raise ValueError(
                f"generate() was handed the adapter handle {adapters} but "
                f"no adapter_cache to resolve it against")
        _check_cache_mesh(adapter_cache, mesh)
        adapters = adapter_cache.get_state(params, adapters,
                                           allow_miss=allow_miss)
    elif cache_adapters:
        adapters = jax.jit(make_precompute_step(
            mcfg, scfg, mesh, fold_gsb=fold_gsb))(params, adapters)

    B, P = prompts.shape
    if max_len < P + gen_len:
        raise ValueError(f"max_len={max_len} < P+gen_len={P + gen_len}")

    # Padded prefill (attention-only archs): pad the prompt to max_len and
    # pass the true P as a traced scalar — ONE compiled prefill covers
    # every prompt length in the bucket; the step rewinds the cache length
    # to P. SSM states integrate every processed token and cannot rewind,
    # so hybrid/Mamba archs prefill at the exact P.
    can_pad = all(k == "attn" for k in mcfg.layer_kinds())
    pad = max_len - P if can_pad else 0
    prefill = jax.jit(make_prefill_step(
        mcfg, scfg, mesh, batch=B, seq=max_len, padded=bool(pad)))
    decode = jax.jit(make_decode_step(mcfg, scfg, mesh, batch=B),
                     donate_argnums=(2,))
    toks = jnp.asarray(prompts, jnp.int32)
    tokens, logits = _decode_loop(
        prefill, decode, params, adapters, toks, prompt_len=P,
        gen_len=gen_len, pad=pad, temperature=temperature, seed=seed,
        collect_logits=return_logits, check_contract=check_contract)
    return (tokens, logits) if return_logits else tokens


# ---------------------------------------------------------------------------
# Multi-tenant request routing.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Request:
    """One serving request: a prompt row and the adapter it runs under
    (an :class:`AdapterHandle`, or a bare adapter-id string meaning "the
    current registered version")."""
    prompt: Any                        # int32 [P]
    adapter: AdapterHandle | str


class MultiTenantServer:
    """Request-routed serving over an :class:`AdapterStateCache`.

    ``serve(requests)`` resolves each request's adapter handle through the
    LRU (precomputing on a miss unless ``allow_miss=False``), sorts the
    batch's rows so same-adapter rows are contiguous, and runs ONE
    prefill + decode loop for the whole heterogeneous batch:

      - one distinct adapter → the single-tenant path, byte-for-byte
        today's serve loop (bitwise fast path);
      - K > 1 adapters → the per-tenant states are stacked leaf-wise
        ([n_scan, K, ...]) and the steps are compiled against the STATIC
        group signature ((start, size) per tenant); each group's rows run
        the same gsB-folded ops as the homogeneous path, so mixed batches
        are bitwise-equal (fp32) to per-tenant sequential serving for
        groups of ≥ 2 rows, and the grouped decode step's jaxpr has zero
        ``dora_wnorm`` ops (no norm work per token).

    Steps are cached per (batch, bucket, signature) — a new grouping
    signature compiles once, like a new prompt-length bucket.
    """

    def __init__(self, mcfg, scfg: StepConfig, params, *,
                 cache: AdapterStateCache, mesh=None,
                 max_cached_steps: int = 32, engine_slots: int = 8,
                 dynamic_grouping: bool = False,
                 max_active_per_adapter: int | None = None,
                 trace: TraceRecorder | None = None):
        _check_cache_mesh(cache, mesh)
        self.mcfg = mcfg
        self.scfg = scfg
        self.params = params
        self.cache = cache
        self.mesh = mesh
        # Observability pass-through: every engine this server builds
        # emits its lifecycle events into this one recorder (the static
        # batch path has no per-request scheduling to trace).
        self.trace = trace
        # Fleet knobs, threaded into every engine this server builds:
        # dynamic_grouping swaps the engine's static group signatures for
        # the traced fleet stack (one decode executable under churn);
        # max_active_per_adapter rate-limits slots per adapter id. The
        # STATIC batch path (same-length serve()) is unaffected — its
        # grouping is per-call, not per-engine.
        self.dynamic_grouping = bool(dynamic_grouping)
        self.max_active_per_adapter = max_active_per_adapter
        # Mixed-length batches route through a continuous-batching engine
        # with this FIXED slot count (requests beyond it queue and join
        # as rows retire) — decoupled from the batch size, so varying
        # batch sizes share one compiled (prefill, decode) pair and one
        # persistent per-row cache instead of one engine per size.
        self.engine_slots = int(engine_slots)
        # Compiled (prefill, decode) pairs per (batch, bucket, grouping
        # signature), LRU-bounded: churny request mixes produce many
        # signatures, and each entry pins two jitted executables — the
        # step cache must not grow unboundedly while the adapter states
        # one field away are carefully byte-bounded.
        self.max_cached_steps = max_cached_steps
        from collections import OrderedDict
        self._steps: "OrderedDict" = OrderedDict()
        # Continuous-batching engines for mixed-length batches, keyed by
        # (slots, max_len). Bounded far tighter than the step cache: each
        # entry pins a persistent [n_scan, slots, max_len, Hkv, hd] K/V
        # cache on device, not just compiled executables.
        self.max_cached_engines = 2
        self._engines: "OrderedDict" = OrderedDict()

    def _resolve(self, req: Request) -> AdapterHandle:
        if isinstance(req.adapter, AdapterHandle):
            return req.adapter
        return self.cache.current_handle(req.adapter)

    def _get_steps(self, *, batch: int, max_len: int, pad: bool,
                   groups):
        key = (batch, max_len, pad, groups)
        if key in self._steps:
            self._steps.move_to_end(key)
            return self._steps[key]
        prefill = jax.jit(make_prefill_step(
            self.mcfg, self.scfg, self.mesh, batch=batch, seq=max_len,
            padded=pad, tenant_groups=groups))
        decode = jax.jit(make_decode_step(
            self.mcfg, self.scfg, self.mesh, batch=batch,
            tenant_groups=groups), donate_argnums=(2,))
        self._steps[key] = (prefill, decode)
        while len(self._steps) > self.max_cached_steps:
            self._steps.popitem(last=False)
        return self._steps[key]

    def _get_engine(self, *, slots: int, max_len: int, temperature: float,
                    seed: int, allow_miss: bool, speculative_k: int = 0):
        from repro.launch.engine import DecodeEngine
        key = (slots, max_len)
        if key in self._engines:
            self._engines.move_to_end(key)
            eng = self._engines[key]
        else:
            eng = DecodeEngine(self.mcfg, self.scfg, self.params,
                               slots=slots, max_len=max_len,
                               adapter_cache=self.cache, mesh=self.mesh,
                               dynamic_grouping=self.dynamic_grouping,
                               max_active_per_adapter=(
                                   self.max_active_per_adapter),
                               trace=self.trace)
            self._engines[key] = eng
            while len(self._engines) > self.max_cached_engines:
                self._engines.popitem(last=False)
        eng.temperature = float(temperature)
        eng.seed = int(seed)
        eng.allow_miss = allow_miss
        eng.speculative_k = int(speculative_k)
        return eng

    def _serve_continuous(self, requests, prompts, *, gen_len, max_len,
                          temperature, seed, allow_miss,
                          speculative_k=0):
        """Mixed-length admission through the continuous-batching engine:
        every request is prefilled into a slot at its TRUE prompt length
        (per-row cache state), so no length bucketing is needed; batches
        larger than ``engine_slots`` queue and join as rows retire.
        Returns a list of 1-D [P_i + gen_len] arrays in request order.
        Sample keys fold in each request's index within THIS batch, so a
        repeated call with the same requests/temperature/seed reproduces
        its tokens even though the cached engine persists."""
        eng = self._get_engine(slots=self.engine_slots, max_len=max_len,
                               temperature=temperature, seed=seed,
                               allow_miss=allow_miss,
                               speculative_k=speculative_k)
        # Validate and resolve EVERY request before the first submit: a
        # bad one mid-batch (unregistered adapter id, empty prompt) must
        # fail this call, not strand already-queued requests in the
        # persistent cached engine.
        checked = [eng.check_request(p, adapter=self._resolve(r),
                                     max_new_tokens=gen_len)
                   for r, p in zip(requests, prompts)]
        rids = [eng.submit(p, adapter=h, max_new_tokens=gen_len, key_id=i)
                for i, (p, h) in enumerate(checked)]
        results = {res.request_id: res for res in eng.run()}
        for rid in rids:
            if results[rid].finish_reason == "error":
                # e.g. a stale/cold adapter handle at admission: surface
                # the original exception (the engine already dropped the
                # request with an errored result, so the persistent
                # engine is NOT wedged for the next call). Results carry
                # errors as strings (picklable); the live exception is
                # only present in the producing process.
                err = results[rid].error
                if err is None:
                    err = RuntimeError(f"{results[rid].error_type}: "
                                       f"{results[rid].error_message}")
                raise err
        return [np.concatenate([p, results[rid].tokens])
                for p, rid in zip(prompts, rids)]

    def serve(self, requests: Sequence[Request], *, gen_len: int,
              max_len: int, temperature: float = 0.0, seed: int = 0,
              allow_miss: bool = True, return_logits: bool = False,
              static: bool | None = None, speculative_k: int = 0,
              check_contract: bool | None = None):
        """Serve one batch. Returns tokens [B, P+gen_len] in REQUEST order
        (or (tokens, per-step logits) when ``return_logits``).

        Prompt lengths: same-length batches run the legacy STATIC path
        (one shared prefill, bitwise guarantees as documented).
        Mixed-length batches are admitted through the continuous-batching
        engine (``repro.launch.engine``) — per-row prefill at each
        request's true length, one fixed-shape decode — and return a LIST
        of 1-D [P_i + gen_len] token arrays in request order (ragged
        shapes don't stack). ``static=True`` forces the legacy path and
        keeps its same-length-bucket error; ``static=False`` forces the
        engine even for uniform lengths. ``return_logits`` is a
        static-path-only debugging hook.

        ``speculative_k > 0``: engine-path requests decode speculatively
        (k base-only drafts + one full-DoRA verify per tick; greedy
        streams stay bitwise the plain ones). A batched tick drafts one
        window shape, so k is a per-call scheduler knob, not a per-row
        one; temperature>0 calls silently fall back to plain decode (the
        engine's documented rejection-sampling gap)."""
        if not requests:
            raise ValueError("empty request batch")
        prompts = [np.asarray(r.prompt, np.int32) for r in requests]
        P = prompts[0].shape[-1]
        mixed = any(p.shape[-1] != P for p in prompts)
        if static is None:
            # speculative decode lives on the engine path (it needs the
            # rewindable per-row cache), so it routes uniform-length
            # batches there too.
            static = not mixed and not speculative_k
        if static and speculative_k:
            raise ValueError(
                "speculative_k requires the continuous-batching engine "
                "path (its rewindable per-row cache): serve with "
                "static=False/None, not static=True")
        if not static:
            if return_logits:
                raise ValueError(
                    "return_logits is only available on the static path "
                    "(the engine streams per-request tokens instead)")
            if check_contract:
                raise ValueError(
                    "check_contract is only meaningful on the static "
                    "path: the engine schedules on host mirrors and "
                    "never reads cache['len'] back, so there is no "
                    "blocking contract check to enable")
            if any(p.shape[-1] + gen_len > max_len for p in prompts):
                raise ValueError(
                    f"max_len={max_len} < P+gen_len="
                    f"{max(p.shape[-1] for p in prompts) + gen_len}")
            return self._serve_continuous(
                requests, prompts, gen_len=gen_len, max_len=max_len,
                temperature=temperature, seed=seed, allow_miss=allow_miss,
                speculative_k=speculative_k)
        if mixed:
            raise ValueError(
                f"all prompts in one batch must share a length bucket on "
                f"the legacy static path; got "
                f"{sorted({p.shape[-1] for p in prompts})} — serve with "
                f"static=None/False to admit mixed lengths through the "
                f"continuous-batching engine, or bucket requests by "
                f"prompt length before batching")
        if max_len < P + gen_len:
            raise ValueError(f"max_len={max_len} < P+gen_len={P + gen_len}")

        # Resolve handles (LRU hit / precompute-on-miss / reject), then
        # group rows by adapter: stable sort by first appearance, so
        # same-adapter rows are contiguous and the grouping signature is
        # deterministic in request order.
        handles = [self._resolve(r) for r in requests]
        order: dict[AdapterHandle, int] = {}
        for h in handles:
            order.setdefault(h, len(order))
        perm = sorted(range(len(requests)), key=lambda i: order[handles[i]])
        inv = np.argsort(perm)
        states = {h: self.cache.get_state(self.params, h,
                                          allow_miss=allow_miss)
                  for h in order}

        toks = jnp.asarray(np.stack([prompts[i] for i in perm]), jnp.int32)
        B = toks.shape[0]
        if len(order) == 1:
            adapters = next(iter(states.values()))
            groups = None          # single tenant: today's bitwise path
        else:
            adapters = stack_adapter_states(
                [states[h] for h in order], axis=1)
            sizes = [0] * len(order)
            for h in handles:
                sizes[order[h]] += 1
            groups, start = [], 0
            for n in sizes:
                groups.append((start, n))
                start += n
            groups = tuple(groups)

        can_pad = all(k == "attn" for k in self.mcfg.layer_kinds())
        pad = max_len - P if can_pad else 0
        prefill, decode = self._get_steps(batch=B, max_len=max_len,
                                          pad=bool(pad), groups=groups)
        tokens, logits = _decode_loop(
            prefill, decode, self.params, adapters, toks, prompt_len=P,
            gen_len=gen_len, pad=pad, temperature=temperature, seed=seed,
            collect_logits=return_logits, check_contract=check_contract)
        tokens = jnp.asarray(np.asarray(tokens)[inv])
        if return_logits:
            return tokens, [step[inv] for step in logits]
        return tokens


# ---------------------------------------------------------------------------
# Continuous-batching server (slot-scheduled; see repro.launch.engine).
# ---------------------------------------------------------------------------

class EngineServer:
    """Request-routed CONTINUOUS serving over one persistent
    :class:`~repro.launch.engine.DecodeEngine`.

    Where :class:`MultiTenantServer` serves one static batch at a time
    (every row enters and leaves together), ``EngineServer`` keeps a
    fixed slot table of ``slots`` decode rows alive across calls:
    ``run(requests)`` queues the requests (any mix of prompt lengths and
    adapters) and drives the engine until they drain — requests join a
    RUNNING batch through per-row prefill, retire individually on EOS /
    token budget / ``max_len``, and the freed rows admit whatever is
    waiting. The compiled surface stays one (prefill-into-slot, decode)
    pair per (slots, max_len, group-signature); per-slot adapter handles
    resolve through the same :class:`~repro.core.AdapterStateCache` LRU
    as the static server.
    """

    def __init__(self, mcfg, scfg: StepConfig, params, *,
                 cache: AdapterStateCache, slots: int, max_len: int,
                 mesh=None, temperature: float = 0.0, seed: int = 0,
                 allow_miss: bool = True, speculative_k: int = 0,
                 fault_plan=None, spec_accept_floor: float = 0.0,
                 paged: bool = False, block_size: int | None = None,
                 n_blocks: int | None = None,
                 prefill_chunk: int | None = None,
                 dynamic_grouping: bool = False,
                 max_active_per_adapter: int | None = None,
                 trace: TraceRecorder | None = None):
        from repro.launch.engine import DecodeEngine
        _check_cache_mesh(cache, mesh)
        self.cache = cache
        self.engine = DecodeEngine(mcfg, scfg, params, slots=slots,
                                   max_len=max_len, adapter_cache=cache,
                                   mesh=mesh, temperature=temperature,
                                   seed=seed, allow_miss=allow_miss,
                                   speculative_k=speculative_k,
                                   fault_plan=fault_plan,
                                   spec_accept_floor=spec_accept_floor,
                                   paged=paged, block_size=block_size,
                                   n_blocks=n_blocks,
                                   prefill_chunk=prefill_chunk,
                                   dynamic_grouping=dynamic_grouping,
                                   max_active_per_adapter=(
                                       max_active_per_adapter),
                                   trace=trace)

    def run(self, requests: Sequence[Request], *, gen_len: int,
            eos_id: int | None = None, on_token=None,
            speculative_k: int | None = None,
            deadline_ticks=None, priority=0):
        """Serve ``requests`` to completion through the slot table;
        returns a list of :class:`~repro.launch.engine.RequestResult` in
        request order (``result.tokens`` holds the generated tokens —
        possibly fewer than ``gen_len`` on EOS / ``max_len`` retirement;
        ``finish_reason == "error"`` with ``result.error`` set when a
        request's adapter failed to resolve at admission — the other
        requests still serve). ``on_token(request_id, token)`` streams
        tokens as they are sampled; the engine (``self.engine``) persists
        across calls, so throughput counters in ``self.engine.stats()``
        accumulate — sample keys fold in each request's index within THIS
        call, keeping temperature>0 runs call-reproducible.
        ``speculative_k``: override the engine's draft window for THIS
        call (0 = plain decode; None = keep the constructor's setting) —
        a batched tick has one window shape, so k is a call-level
        scheduler knob, not a per-row one.

        ``deadline_ticks`` / ``priority``: one scalar applied to every
        request, or a per-request sequence — see
        :meth:`~repro.launch.engine.DecodeEngine.submit` for the timeout
        and preemption semantics."""
        if not requests:
            raise ValueError("empty request batch")
        if speculative_k is not None:
            self.engine.speculative_k = int(speculative_k)

        def norm(v, name):
            if v is None or isinstance(v, (int, np.integer)):
                return [v] * len(requests)
            v = list(v)
            if len(v) != len(requests):
                raise ValueError(
                    f"{name} has {len(v)} entries for "
                    f"{len(requests)} requests")
            return v
        deadlines = norm(deadline_ticks, "deadline_ticks")
        priorities = norm(priority, "priority")
        # All-or-nothing submission: validate every request first, so a
        # bad one mid-batch cannot orphan earlier ones in the persistent
        # queue (they would steal slots from — and stream into — the
        # NEXT call).
        checked = [self.engine.check_request(r.prompt, adapter=r.adapter,
                                             max_new_tokens=gen_len)
                   for r in requests]
        rids = [self.engine.submit(p, adapter=h, max_new_tokens=gen_len,
                                   eos_id=eos_id, key_id=i,
                                   priority=int(priorities[i] or 0),
                                   deadline_ticks=deadlines[i])
                for i, (p, h) in enumerate(checked)]
        results = {res.request_id: res for res in self.engine.run(on_token)}
        return [results[rid] for rid in rids]


def _dump_obs(trace: TraceRecorder, engine, args) -> None:
    """Write the post-run observability artifacts requested on the CLI:
    ``--trace-out`` (JSONL if the path ends .jsonl, else Chrome
    trace_event) and ``--metrics-out`` (Prometheus text)."""
    if args.trace_out:
        if args.trace_out.endswith(".jsonl"):
            trace.to_jsonl(args.trace_out)
            kind = "jsonl"
        else:
            trace.to_chrome_trace(args.trace_out)
            kind = "chrome-trace"
        print(f"  obs: {len(trace)} events ({trace.dropped} dropped) -> "
              f"{args.trace_out} ({kind})")
    if args.metrics_out:
        # engine_metrics folds the trace-derived latency histograms in
        # when handed the recorder.
        engine_metrics(engine, trace).to_prometheus(args.metrics_out)
        print(f"  obs: metrics snapshot -> {args.metrics_out} "
              f"(prometheus text)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--alpha", type=float, default=16.0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-adapter-cache", action="store_true",
                    help="skip the frozen-adapter precompute (recompute "
                         "the factored norm every step — debug only)")
    ap.add_argument("--fold-gsb", action="store_true",
                    help="fold g*s into B in the serving state "
                         "(broadcast-free decode compose)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="N>1: multi-tenant demo — N adapter sets in one "
                         "LRU-cached batch, --batch rows EACH, served in "
                         "one grouped decode loop")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching demo: 2x--batch MIXED-length "
                         "requests through the slot-scheduled engine "
                         "(--batch slots; requests join/leave mid-decode)")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="with --continuous: draft K base-only tokens per "
                         "tick and verify them in one full-DoRA window; "
                         "asserts the greedy token streams match a plain "
                         "engine's bitwise")
    ap.add_argument("--inject", default="", metavar="SPEC",
                    help="with --continuous: deterministic fault plan, "
                         "e.g. 'nan@3' (poison every row's logits at tick "
                         "3), 'nan@3:1,evict@5,stale@2,slow@4' — see "
                         "repro.launch.faults.FaultPlan.parse")
    ap.add_argument("--deadline", type=int, default=0, metavar="N",
                    help="with --continuous: give every request a "
                         "deadline of N engine ticks (expired requests "
                         "retire with finish_reason='timeout')")
    ap.add_argument("--paged", action="store_true",
                    help="with --continuous: block-paged K/V cache + "
                         "chunked prefill (see docs/engine.md); asserts "
                         "the greedy token streams match a rectangular "
                         "engine's bitwise and the block pool drains")
    ap.add_argument("--block-size", type=int, default=0, metavar="B",
                    help="with --paged: K/V block size (0 = auto: the "
                         "largest divisor of max_len up to 16)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="fleet-serving demo: N tenants over --batch "
                         "slots, a churny mixed-adapter trace through the "
                         "TRACED dynamic-grouping engine; asserts the "
                         "greedy streams match the static-signature "
                         "engine bitwise and that the dynamic decode "
                         "held exactly ONE executable")
    ap.add_argument("--priority", type=int, default=0, metavar="N",
                    help="with --continuous: submit the LAST request at "
                         "priority N — it admits ahead of the FIFO (and "
                         "would preempt a lower-priority active row if it "
                         "arrived mid-flight with every slot busy)")
    ap.add_argument("--trace-out", default="", metavar="PATH",
                    help="with --continuous/--fleet: record the request "
                         "lifecycle and write it here — JSONL (one event "
                         "per line) when PATH ends in .jsonl, else a "
                         "Chrome trace_event timeline loadable in "
                         "Perfetto / chrome://tracing")
    ap.add_argument("--metrics-out", default="", metavar="PATH",
                    help="with --continuous/--fleet: write an engine "
                         "metrics snapshot here — Prometheus text "
                         "exposition format (counters, gauges, and "
                         "tick/seconds latency histograms)")
    args = ap.parse_args()
    compile_cache.enable()

    mcfg = get_config(args.arch, smoke=args.smoke)
    dcfg = DoRAConfig(rank=args.rank, alpha=args.alpha, mode="auto")
    scfg = StepConfig(dora=dcfg)
    params, adapters, _ = build_state(mcfg, dcfg, args.seed)

    rng = np.random.default_rng(args.seed)
    max_len = args.prompt_len + args.gen_len

    if args.fleet > 1:
        cache = AdapterStateCache.for_serving(mcfg, scfg)
        for t in range(args.fleet):
            _, ad_t, _ = build_state(mcfg, dcfg, args.seed + 1 + t)
            cache.register(f"tenant-{t}", ad_t)
        n_req = max(2 * args.batch, args.fleet)
        requests = [Request(rng.integers(
            0, mcfg.vocab_size,
            int(rng.integers(args.prompt_len // 2, args.prompt_len + 1)),
            dtype=np.int32), f"tenant-{int(rng.integers(args.fleet))}")
            for _ in range(n_req)]
        trace = (TraceRecorder()
                 if (args.trace_out or args.metrics_out) else None)
        dyn = EngineServer(mcfg, scfg, params, cache=cache,
                           slots=args.batch, max_len=max_len,
                           temperature=args.temperature, seed=args.seed,
                           dynamic_grouping=True, trace=trace)
        t0 = monotonic()
        results = dyn.run(requests, gen_len=args.gen_len)
        dt = monotonic() - t0
        st = dyn.engine.stats()
        counts = dyn.engine.compile_counts()
        assert counts["decode"] == {"dynamic": 1}, (
            f"dynamic decode grew extra executables: {counts['decode']}")
        assert counts["adapter_insert"] == 1, counts
        print(f"fleet: {n_req} requests x {args.fleet} tenants through "
              f"{args.batch} slots in {dt:.2f}s "
              f"({st.generated_tokens / dt:.1f} tok/s, "
              f"{st.stack_inserts} stack inserts, ONE dynamic decode "
              f"executable)")
        if args.temperature <= 0.0:
            # the fleet oracle: the same churny trace through a STATIC-
            # signature engine must stream bitwise-identical tokens —
            # while compiling one decode per distinct slot layout.
            static = EngineServer(mcfg, scfg, params, cache=cache,
                                  slots=args.batch, max_len=max_len,
                                  temperature=args.temperature,
                                  seed=args.seed)
            base = static.run(requests, gen_len=args.gen_len)
            for rs, rp in zip(results, base):
                assert rs.tokens.tolist() == rp.tokens.tolist(), (
                    rs.request_id, rs.tokens, rp.tokens)
            n_sigs = len(static.engine.compile_counts()["decode"])
            print(f"  dynamic greedy streams == static engine (oracle "
                  f"OK; static needed {n_sigs} decode signatures)")
        if trace is not None:
            _dump_obs(trace, dyn.engine, args)
        for r in results[:2]:
            print(f"  req{r.request_id}: P={len(r.prompt)} "
                  f"-> {r.tokens.tolist()} ({r.finish_reason})")
        return

    if args.continuous:
        from repro.launch.engine import FINISH_REASONS
        from repro.launch.faults import FaultPlan
        plan = FaultPlan.parse(args.inject) if args.inject else None
        faulty = plan is not None or args.deadline > 0 or args.priority > 0
        cache = AdapterStateCache.for_serving(mcfg, scfg)
        _, ad0, _ = build_state(mcfg, dcfg, args.seed + 1)
        cache.register("tenant-0", ad0)
        n_req = 2 * args.batch
        requests = [Request(rng.integers(
            0, mcfg.vocab_size,
            int(rng.integers(args.prompt_len // 2, args.prompt_len + 1)),
            dtype=np.int32), "tenant-0") for _ in range(n_req)]
        trace = (TraceRecorder()
                 if (args.trace_out or args.metrics_out) else None)
        server = EngineServer(mcfg, scfg, params, cache=cache,
                              slots=args.batch, max_len=max_len,
                              temperature=args.temperature, seed=args.seed,
                              speculative_k=args.speculative,
                              fault_plan=plan, paged=args.paged,
                              block_size=args.block_size or None,
                              trace=trace)
        t0 = monotonic()
        results = server.run(
            requests, gen_len=args.gen_len,
            deadline_ticks=args.deadline if args.deadline > 0 else None,
            priority=([0] * (n_req - 1) + [args.priority]
                      if args.priority > 0 else 0))
        dt = monotonic() - t0
        st = server.engine.stats()
        print(f"continuous: {n_req} mixed-length requests through "
              f"{args.batch} slots in {dt:.2f}s "
              f"({st.generated_tokens / dt:.1f} tok/s, "
              f"occupancy {st.mean_occupancy:.2f}, "
              f"{st.decode_steps} decode steps)")
        if faulty:
            # The fault-containment smoke: every request finishes exactly
            # once with a valid reason, the slot table drains, and the
            # ladder's counters are visible to the operator.
            hist: dict[str, int] = {}
            for r in results:
                hist[r.finish_reason] = hist.get(r.finish_reason, 0) + 1
            assert len(results) == n_req
            assert all(r.finish_reason in FINISH_REASONS for r in results)
            assert not server.engine.has_work(), "slot table did not drain"
            print(f"  faults: inject={args.inject or '-'} "
                  f"deadline={args.deadline or '-'} "
                  f"priority={args.priority or '-'} -> finish reasons "
                  f"{sorted(hist.items())}")
            print(f"  counters: timeouts={st.timeouts} "
                  f"quarantined={st.quarantined} "
                  f"preemptions={st.preemptions} "
                  f"injected_nans={st.injected_nans} "
                  f"forced_evictions={st.forced_evictions} "
                  f"stale_injected={st.stale_injected} "
                  f"slow_ticks={st.slow_ticks}")
        if args.paged:
            ps = server.engine.pool_stats()
            assert ps["used_blocks"] == 0, f"leaked blocks: {ps}"
            assert ps["per_slot_blocks"] == [0] * args.batch, ps
            counts = server.engine.compile_counts()
            assert counts["prefill_chunk"] == 1, counts
            print(f"  paged: block_size={ps['block_size']} "
                  f"n_blocks={ps['n_blocks']} "
                  f"chunk={ps['prefill_chunk']} "
                  f"peak_used={ps['peak_used_blocks']} blocks "
                  f"(pool drained)")
            if args.temperature <= 0.0 and not faulty:
                # the paged greedy oracle: the same requests through a
                # RECTANGULAR engine must stream bitwise-identical tokens.
                rect = EngineServer(mcfg, scfg, params, cache=cache,
                                    slots=args.batch, max_len=max_len,
                                    temperature=args.temperature,
                                    seed=args.seed)
                base = rect.run(requests, gen_len=args.gen_len)
                for rs, rp in zip(results, base):
                    assert rs.tokens.tolist() == rp.tokens.tolist(), (
                        rs.request_id, rs.tokens, rp.tokens)
                print("  paged greedy streams == rectangular engine "
                      "(oracle OK)")
        if args.speculative > 0 and args.temperature <= 0.0 and not faulty:
            # the greedy-oracle check: same requests through a PLAIN
            # engine must yield bitwise-identical token streams.
            plain = EngineServer(mcfg, scfg, params, cache=cache,
                                 slots=args.batch, max_len=max_len,
                                 temperature=args.temperature,
                                 seed=args.seed)
            base = plain.run(requests, gen_len=args.gen_len)
            for rs, rp in zip(results, base):
                assert rs.tokens.tolist() == rp.tokens.tolist(), (
                    rs.request_id, rs.tokens, rp.tokens)
            print(f"  speculative k={args.speculative}: "
                  f"{st.verify_steps} verify + {st.draft_steps} draft "
                  f"steps, {st.accepted_drafts} drafts accepted; greedy "
                  f"streams == plain engine (oracle OK)")
        if trace is not None:
            _dump_obs(trace, server.engine, args)
        for r in results[:2]:
            print(f"  req{r.request_id}: P={len(r.prompt)} "
                  f"-> {r.tokens.tolist()} ({r.finish_reason})")
        return

    if args.tenants > 1:
        cache = AdapterStateCache.for_serving(mcfg, scfg)
        requests = []
        for t in range(args.tenants):
            _, ad_t, _ = build_state(mcfg, dcfg, args.seed + t)
            cache.register(f"tenant-{t}", ad_t)
            for _ in range(args.batch):
                requests.append(Request(
                    rng.integers(0, mcfg.vocab_size, args.prompt_len,
                                 dtype=np.int32), f"tenant-{t}"))
        server = MultiTenantServer(mcfg, scfg, params, cache=cache)
        t0 = monotonic()
        toks = np.asarray(server.serve(requests, gen_len=args.gen_len,
                                       max_len=max_len,
                                       temperature=args.temperature,
                                       seed=args.seed))
        dt = monotonic() - t0
        st = cache.stats()
        print(f"served {len(requests)} requests x {args.tenants} tenants "
              f"in {dt:.2f}s ({len(requests) * args.gen_len / dt:.1f} "
              f"tok/s); cache: {st.hits} hits / {st.misses} misses / "
              f"{st.current_bytes} state bytes")
        for b in range(min(len(requests), 2)):
            print(f"  req{b}: ...{toks[b, args.prompt_len - 4:].tolist()}")
        return

    prompts = rng.integers(0, mcfg.vocab_size,
                           (args.batch, args.prompt_len), dtype=np.int32)
    t0 = monotonic()
    toks = generate(mcfg, params, adapters, scfg, prompts,
                    gen_len=args.gen_len, max_len=max_len,
                    temperature=args.temperature, seed=args.seed,
                    cache_adapters=not args.no_adapter_cache,
                    fold_gsb=args.fold_gsb)
    dt = monotonic() - t0
    toks = np.asarray(toks)
    print(f"generated [{toks.shape[0]}, {toks.shape[1]}] in {dt:.2f}s "
          f"({args.batch * args.gen_len / dt:.1f} tok/s)")
    for b in range(min(args.batch, 2)):
        print(f"  seq{b}: ...{toks[b, args.prompt_len - 4:].tolist()}")


if __name__ == "__main__":
    main()
