"""On-chip smoke of the DoRA serving engine and fine-tuning step.

    python chip_smoke.py              # one TPU chip: phases a-e below
    python chip_smoke.py --four-chip  # one 2x2 host: tensor-parallel phase

One chip, in one process (a chip belongs to one process at a time):

  a. device gate: exits non-zero, printing no result, unless JAX's first
     device is a TPU;
  b. serve: qwen2-7b at every published width, cut in depth to fit one
     16 GB chip, with two DoRA tenants at the paper's rank r=384 served
     through ``EngineServer(paged=True)``;
  c. kernel tier: prefill logits of the compiled Pallas kernels against
     the same weights on the eager tier, beside how far the adapter
     itself moves them;
  d. train: a few ``make_train_step`` steps on ``build_state`` weights,
     the functions ``launch/train.py`` runs;
  e. kernel report: ``tpu_custom_call`` counts of the precompute, decode
     and train executables.

``--four-chip`` runs only the mesh phase: the same cut served with tensor
parallelism over four chips and compared with one chip of the same
process, then the full 28-layer model, which no single chip holds.

Weights, adapters, prompts and training data all come from ``--seed``.
The last line of standard output is one JSON object naming the device:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Phase times are one cold run each, compilation included.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

# Depth of the one-chip cut. At the published 28 layers qwen2-7b holds
# 7.6B parameters (14.2 GiB in bf16), which leaves no room on a 16 GB
# chip for adapters, K/V and activations; 8 layers hold 2.96B (5.5 GiB).
# Every width stays as published.
CUT_LAYERS = 8
RANK = 384              # the paper's rank (StepConfig's default)
SLOTS = 8
MAX_LEN = 2048
GEN_LEN = 32
# Every prompt (128-1024 tokens) fits one prefill chunk, so each wave's
# slots prefill in the same tick, decode together and retire together:
# the slot -> tenant layout, and with it the compile signature of the
# grouped decode, stays one for the whole run.
PREFILL_CHUNK = 1024
TRAIN_SEQ = 4096
TRAIN_STEPS = 5
# Paper §5.1: the loss over the last 1024 tokens, which keeps the fp32
# [seq, vocab] logits of a 152k vocabulary off the chip's 16 GB.
LOSS_TOKENS = 1024
# Both tiers of phase c run the same bf16 weights and activations with
# fp32 accumulation. They differ in accumulation order inside the compose
# and the norm, and the eager tier rounds the materialized h@Bᵀ to bf16
# where the matmul-fused kernel keeps it in fp32: a projection's output
# moves by one bf16 ulp (2^-8 relative) where a rounding boundary falls
# between them, and the cut's 8 random layers compound that. A kernel
# that drops or mis-scales the adapter term moves the logits by the
# adapter's share, which phase c measures and requires to be at least
# twice the bound.
REL_TOL = 2e-2
# Tensor parallelism also rounds each row-parallel projection's partial
# sums to bf16 before the all-reduce: a CPU 4-device mesh of the same
# comparison at 8 layers (d_model 512) measured 1.5e-2. A wrong shard or
# collective moves the logits by O(1).
TP_REL_TOL = 5e-2
FINISHED_OK = ("eos", "length")


def log(msg: str) -> None:
    print(msg, flush=True)


def device_gate():
    """Phase a: the first device must be a TPU; returns its description."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: JAX's first device is on platform "
                 f"{d0.platform!r} ({d0.device_kind}), not 'tpu'; this "
                 f"smoke runs on a TPU chip only")
    log(f"[a] device: {d0.device_kind} (platform {d0.platform}), "
        f"{len(devices)} device(s), jax {jax.__version__}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def cut_config(num_layers: int):
    import jax.numpy as jnp
    from repro.configs import get_config
    mcfg = dataclasses.replace(get_config("qwen2-7b"), num_layers=num_layers)
    assert mcfg.dtype == jnp.bfloat16
    return mcfg


def is_adapter(node) -> bool:
    """One adapted projection's {"A", "B", "m"} node."""
    return isinstance(node, dict) and "B" in node


def perturb(adapters, seed: int, shardings=None):
    """Non-zero B (seed-built adapters start at B = 0, where DoRA is the
    identity and a wrong compose or norm would go unseen). The scale makes
    the LoRA term about a tenth of each projection's output."""
    import jax

    def go(tree):
        leaves, treedef = jax.tree.flatten(tree, is_leaf=is_adapter)
        out = []
        for i, leaf in enumerate(leaves):
            key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
            noise = 1e-3 * jax.random.normal(key, leaf["B"].shape)
            out.append(dict(leaf, B=noise.astype(leaf["B"].dtype)))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(go, out_shardings=shardings)(adapters)


def tenant_adapters(mcfg, dcfg, params, seed: int, shardings=None):
    """Another tenant's adapter set for ``params``, from ``seed``, with B
    perturbed; initialised straight into ``shardings``."""
    import jax
    from repro.models import init_adapters
    init = jax.jit(init_adapters, static_argnums=(1, 3),
                   out_shardings=shardings)
    return perturb(init(jax.random.PRNGKey(seed), mcfg, params, dcfg), seed,
                   shardings)


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def make_requests(rng, vocab: int, lengths, tenants):
    import numpy as np
    from repro.launch.serve import Request
    return [Request(rng.integers(0, vocab, int(n), dtype=np.int32), t)
            for n, t in zip(lengths, tenants)]


def check_results(results, tag: str) -> None:
    for r in results:
        if r.finish_reason not in FINISHED_OK:
            raise AssertionError(
                f"{tag}: request {r.request_id} finished "
                f"{r.finish_reason!r} ({r.error_type}: {r.error_message})")
    reasons = sorted({r.finish_reason for r in results})
    log(f"    {tag}: {len(results)} requests finished {reasons}, "
        f"{sum(len(r.tokens) for r in results)} tokens")


def check_drained(engine, tag: str) -> None:
    ps = engine.pool_stats()
    if ps["used_blocks"] != 0 or any(ps["per_slot_blocks"]):
        raise AssertionError(f"{tag}: block pool did not drain: {ps}")
    log(f"    {tag}: block pool drained (peak {ps['peak_used_blocks']} of "
        f"{ps['n_blocks']} blocks of {ps['block_size']})")


def custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def kernel_names(compiled) -> set[str]:
    """Names of the Pallas kernels in a compiled executable: every
    ``pallas_call`` in ``repro/kernels`` tags its custom call with
    ``metadata={"kernel": <name>}``."""
    import re
    return set(re.findall(r'kernel_metadata=\{\s*"kernel":"([\w.]+)"',
                          compiled.as_text()))


# ---------------------------------------------------------------------------
# One chip.
# ---------------------------------------------------------------------------

def serve_phase(mcfg, scfg, params, tenant_adapters, seed: int):
    """Phase b. Returns (cache, engine) for the kernel report."""
    import jax
    import numpy as np
    from repro.core import AdapterStateCache
    from repro.launch.serve import EngineServer

    log(f"[b] serve: {mcfg.name} cut to {mcfg.num_layers} of 28 layers "
        f"(d_model {mcfg.d_model}, heads {mcfg.num_heads}/"
        f"{mcfg.num_kv_heads}, d_ff {mcfg.d_ff}, vocab {mcfg.vocab_size}, "
        f"bf16) — 28 layers are 14.2 GiB of bf16 weights, leaving no room "
        f"on a 16 GB chip for adapters and K/V; {mcfg.num_layers} layers "
        f"are {mcfg.count_params() * 2 / 2**30:.1f} GiB")
    cache = AdapterStateCache.for_serving(mcfg, scfg)
    names = [f"tenant-{t}" for t in range(len(tenant_adapters))]
    for name, ad in zip(names, tenant_adapters):
        cache.register(name, ad)
    t0 = time.perf_counter()
    for name in names:
        jax.block_until_ready(
            cache.get_state(params, cache.current_handle(name)))
    log(f"    precompute (factored norm) of {len(names)} tenants at "
        f"r={scfg.dora.rank}: {time.perf_counter() - t0:.1f}s "
        f"(one cold run, compile included)")

    server = EngineServer(mcfg, scfg, params, cache=cache, slots=SLOTS,
                          max_len=MAX_LEN, paged=True,
                          prefill_chunk=PREFILL_CHUNK)
    rng = np.random.default_rng(seed)
    half = SLOTS // len(names)
    tenants = [n for n in names for _ in range(half)]
    for wave in range(2):
        lengths = rng.integers(PREFILL_CHUNK // 8, PREFILL_CHUNK + 1, SLOTS)
        reqs = make_requests(rng, mcfg.vocab_size, lengths, tenants)
        t0 = time.perf_counter()
        results = server.run(reqs, gen_len=GEN_LEN)
        dt = time.perf_counter() - t0
        check_results(results, f"wave {wave}")
        log(f"    wave {wave}: prompts {sorted(lengths.tolist())}, "
            f"{dt:.1f}s (one {'cold' if wave == 0 else 'warm'} run)")
    check_drained(server.engine, "serve")
    counts = server.engine.compile_counts()
    log(f"    executables: {counts}")
    return cache, server.engine


def kernel_tier_phase(mcfg, scfg, params, adapters, seed: int,
                      tier: str = "tpu") -> None:
    """Phase c: one prompt's prefill logits, kernels vs eager tier. The
    adapters are raw (no cached serving state), so both the factored norm
    and the compose run inside each tier's forward. The eager tier also
    runs the adapters with B = 0 (DoRA's identity), which measures how far
    the adapter moves the logits: the bound must sit well below that."""
    import jax
    import numpy as np
    from repro.launch.steps import make_prefill_step

    prompt = np.random.default_rng(seed).integers(
        0, mcfg.vocab_size, (1, PREFILL_CHUNK), dtype=np.int32)

    def prefill(force_tier):
        sc = dataclasses.replace(scfg, dora=dataclasses.replace(
            scfg.dora, force_tier=force_tier))
        step = jax.jit(make_prefill_step(mcfg, sc, None, batch=1,
                                         seq=prompt.shape[1]))
        return lambda ad: np.asarray(
            step(params, ad, {"tokens": prompt})[0][0], np.float32)

    t0 = time.perf_counter()
    fused = prefill(tier)(adapters)
    eager_step = prefill("eager")
    eager = eager_step(adapters)
    identity = eager_step(jax.tree.map(
        lambda n: dict(n, B=jax.numpy.zeros_like(n["B"])), adapters,
        is_leaf=is_adapter))
    err = rel_err(fused, eager)
    signal = rel_err(eager, identity)
    log(f"[c] kernel tier {tier!r} vs eager, prefill of {prompt.shape[1]} "
        f"tokens: rel L2 error {err:.3e} (bound {REL_TOL:g}), max |diff| "
        f"{np.max(np.abs(fused - eager)):.3e} over logits of max "
        f"{np.max(np.abs(eager)):.3f}; argmax {int(fused.argmax())} vs "
        f"{int(eager.argmax())}; the adapter itself moves the logits by "
        f"rel L2 {signal:.3e}; {time.perf_counter() - t0:.1f}s")
    if not (np.isfinite(fused).all() and err <= REL_TOL):
        raise AssertionError(f"kernel tier {tier!r} disagrees with eager: "
                             f"rel L2 {err:.3e} > {REL_TOL:g}")
    if signal < 2 * REL_TOL:
        raise AssertionError(f"the adapter moves the logits by only "
                             f"{signal:.3e}: the bound {REL_TOL:g} cannot "
                             f"tell a dropped adapter term from rounding")


def train_phase(mcfg, scfg, seed: int):
    """Phase d. Returns the compiled train step for the kernel report."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.pipeline import DataConfig, SyntheticLMDataset
    from repro.launch.steps import make_train_step
    from repro.launch.train import build_state

    t0 = time.perf_counter()
    params, adapters, opt_state = build_state(mcfg, scfg.dora, seed)
    data = SyntheticLMDataset(DataConfig(
        vocab_size=mcfg.vocab_size, seq_len=TRAIN_SEQ, global_batch=1,
        seed=seed))
    batch = {k: jnp.asarray(v) for k, v in data.global_batch_np(0).items()}
    step = jax.jit(make_train_step(mcfg, scfg, None, batch=1,
                                   seq=TRAIN_SEQ), donate_argnums=(1, 2))
    compiled = step.lower(params, adapters, opt_state, batch).compile()
    mem = compiled.memory_analysis()
    log(f"[d] train: seq {TRAIN_SEQ}, batch 1, r={scfg.dora.rank}, mode "
        f"{scfg.dora.mode!r}, loss over the last {scfg.loss_tokens} tokens; "
        f"init + compile {time.perf_counter() - t0:.1f}s; temp "
        f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, arguments "
        f"{mem.argument_size_in_bytes / 2**30:.2f} GiB")
    for i in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        if i:
            batch = {k: jnp.asarray(v)
                     for k, v in data.global_batch_np(i).items()}
        adapters, opt_state, metrics = compiled(params, adapters, opt_state,
                                                batch)
        loss = float(metrics["loss"])
        gnorm = float(metrics["grad_norm"])
        log(f"    step {i}: loss {loss:.4f}, grad norm {gnorm:.4f}, "
            f"{time.perf_counter() - t0:.2f}s")
        if not (np.isfinite(loss) and np.isfinite(gnorm)):
            raise AssertionError(f"train step {i}: loss {loss}, "
                                 f"grad norm {gnorm}")
    return compiled


def serving_executables(mcfg, scfg, params, cache, engine) -> dict:
    """The precompute and decode executables phase b ran, lowered again
    from the same step builders (the persistent compile cache hands the
    compiled programs back)."""
    import jax
    import jax.numpy as jnp
    from repro.core import stack_adapter_states
    from repro.launch.steps import make_decode_step, make_precompute_step

    sds = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    precompute = jax.jit(make_precompute_step(
        mcfg, scfg, None, fold_gsb=True)).lower(
        sds(params), sds(cache.adapters("tenant-0"))).compile()
    (groups,) = engine.compile_counts()["decode"]
    state = sds(cache.get_state(params, cache.current_handle("tenant-0")))
    adapters = state if groups is None else jax.eval_shape(
        lambda s: stack_adapter_states([s] * len(groups), axis=1), state)
    decode = jax.jit(make_decode_step(mcfg, scfg, None, batch=SLOTS,
                                      tenant_groups=groups),
                     donate_argnums=(2,), out_shardings=(None, None)).lower(
        sds(params), adapters, sds(engine.cache),
        {"tokens": jax.ShapeDtypeStruct((SLOTS, 1), jnp.int32)}).compile()
    return {"precompute": precompute, "decode": decode}


# Kernels each executable must hold (substrings of the Pallas kernel names).
REQUIRED_KERNELS = {
    "precompute": ("norm_terms_pallas", "assemble_norm_pallas"),
    "decode": ("paged_gather",),
    "train": ("norm_terms_pallas", "compose_mm_fwd_pallas",
              "compose_mm_bwd_pallas"),
}


def kernel_report(executables: dict) -> None:
    """Phase e: Pallas kernels in the precompute, decode and train
    executables."""
    log("[e] kernel report (tpu_custom_call count, Pallas kernels):")
    for name, compiled in executables.items():
        n = custom_calls(compiled)
        kernels = kernel_names(compiled)
        log(f"    {name}: {n} tpu_custom_calls, kernels {sorted(kernels)}")
        missing = sorted(set(REQUIRED_KERNELS[name]) - kernels)
        if n == 0 or missing:
            raise AssertionError(f"{name} executable lacks kernels {missing}"
                                 f" ({n} tpu_custom_calls)")
    log(f"    decode runs {SLOTS} rows: every adapted projection is below "
        f"the paper's crossover (d_out >= 2048 and rows*d_out >= 2048*6144, "
        f"core/dispatch.py), so its compose is eager by design; the paged "
        f"K/V gather is the decode's kernel")


def one_chip(seed: int) -> None:
    import jax
    from repro.launch.steps import StepConfig
    from repro.launch.train import build_state

    mcfg = cut_config(CUT_LAYERS)
    scfg = StepConfig(loss_tokens=LOSS_TOKENS)
    assert scfg.dora.rank == RANK
    t0 = time.perf_counter()
    params, raw, opt_state = build_state(mcfg, scfg.dora, seed)
    tenants = [perturb(raw, seed + 1),
               tenant_adapters(mcfg, scfg.dora, params, seed + 2)]
    del raw, opt_state
    jax.block_until_ready((params, tenants))
    log(f"    weights and 2 adapter sets from seed {seed}: "
        f"{time.perf_counter() - t0:.1f}s")

    cache, engine = serve_phase(mcfg, scfg, params, tenants, seed)
    executables = serving_executables(mcfg, scfg, params, cache, engine)
    kernel_tier_phase(mcfg, scfg, params, tenants[0], seed)
    # Training builds its own state: free the serving state first.
    del params, tenants, cache, engine
    gc.collect()
    executables["train"] = train_phase(mcfg, scfg, seed)
    kernel_report(executables)


# ---------------------------------------------------------------------------
# Four chips.
# ---------------------------------------------------------------------------

# The mesh phase serves single-tenant traffic through one slot per request,
# and every prompt fits one prefill chunk.
MESH_SLOTS = 4
MESH_MAX_LEN = 1024
MESH_CHUNK = 512
MESH_GEN = 16


def serve_mesh(mcfg, scfg, params, adapters, prompts, mesh, tag: str):
    """Serve ``prompts`` through ``EngineServer`` (tensor-parallel when
    ``mesh`` is given); returns (prefill logits of prompts[0], token
    streams, the adapters' serving state)."""
    import jax
    import numpy as np
    from repro.core import AdapterStateCache
    from repro.launch.serve import EngineServer, Request
    from repro.launch.steps import make_prefill_step

    t0 = time.perf_counter()
    cache = AdapterStateCache.for_serving(mcfg, scfg, mesh)
    cache.register("tenant-0", adapters)
    state = cache.get_state(params, cache.current_handle("tenant-0"))
    prefill = jax.jit(make_prefill_step(mcfg, scfg, mesh, batch=1,
                                        seq=len(prompts[0])))
    logits, _ = prefill(params, state, {"tokens": prompts[0][None]})
    logits = np.asarray(logits[0], np.float32)
    server = EngineServer(mcfg, scfg, params, cache=cache, slots=MESH_SLOTS,
                          max_len=MESH_MAX_LEN, mesh=mesh, paged=True,
                          prefill_chunk=MESH_CHUNK)
    results = server.run([Request(p, "tenant-0") for p in prompts],
                         gen_len=MESH_GEN)
    check_results(results, tag)
    check_drained(server.engine, tag)
    log(f"    {tag}: {time.perf_counter() - t0:.1f}s (one cold run)")
    if not np.isfinite(logits).all():
        raise AssertionError(f"{tag}: non-finite prefill logits")
    return logits, [r.tokens for r in results], state


def four_chip(seed: int) -> None:
    """Tensor parallelism over a (data=1, model=4) mesh of one host."""
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from repro.compat.mesh import make_mesh
    from repro.configs import get_config
    from repro.launch import sharding as S
    from repro.launch.steps import StepConfig, make_prefill_step
    from repro.launch.train import build_state

    devices = jax.devices()[:4]
    mesh = make_mesh((1, 4), ("data", "model"), devices=devices)
    scfg = StepConfig()
    rng = np.random.default_rng(seed)
    lengths = rng.integers(MESH_CHUNK // 4, MESH_CHUNK + 1, MESH_SLOTS)
    lengths[0] = MESH_CHUNK

    # Step 1: the one-chip cut, tensor-parallel vs one chip, same weights.
    mcfg = cut_config(CUT_LAYERS)
    prompts = [rng.integers(0, mcfg.vocab_size, int(n), dtype=np.int32)
               for n in lengths]
    t0 = time.perf_counter()
    params, raw, opt_state = build_state(mcfg, scfg.dora, seed, mesh=mesh)
    adapters = perturb(raw, seed + 1,
                       S.adapter_sharding(mcfg, scfg.dora, mesh))
    del raw, opt_state
    one = SingleDeviceSharding(devices[0])
    params1, adapters1 = jax.device_put((params, adapters), one)
    jax.block_until_ready((params1, adapters1))
    log(f"[4] step 1: {mcfg.name} cut to {mcfg.num_layers} layers, "
        f"initialised into its (data=1, model=4) shardings and copied to "
        f"chip 0: {time.perf_counter() - t0:.1f}s")
    tp_logits, tp_tokens, _ = serve_mesh(mcfg, scfg, params, adapters,
                                         prompts, mesh, "tensor-parallel x4")
    one_logits, one_tokens, state1 = serve_mesh(
        mcfg, scfg, params1, adapters1, prompts, None, "one chip")
    err = rel_err(tp_logits, one_logits)
    log(f"    prefill logits, tensor-parallel vs one chip: rel L2 error "
        f"{err:.3e} (bound {TP_REL_TOL:g}), max |diff| "
        f"{np.max(np.abs(tp_logits - one_logits)):.3e}")
    if err > TP_REL_TOL:
        raise AssertionError(f"tensor-parallel logits off by {err:.3e}")
    # Greedy streams may part only where the one-chip run's two candidate
    # tokens are within the cross-partitioning tolerance of each other.
    tie_tol = TP_REL_TOL * float(np.max(np.abs(one_logits)))
    padded = None
    agree = 0
    for i, (a, b) in enumerate(zip(tp_tokens, one_tokens)):
        n = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                 len(b))
        agree += n
        if n == len(b):
            continue
        if padded is None:
            padded = jax.jit(make_prefill_step(
                mcfg, scfg, None, batch=1, seq=MESH_MAX_LEN, padded=True))
        ctx = np.concatenate([prompts[i], b[:n]]).astype(np.int32)
        toks = np.zeros((1, MESH_MAX_LEN), np.int32)
        toks[0, :len(ctx)] = ctx
        lg, _ = padded(params1, state1, {
            "tokens": toks, "prompt_len": np.int32(len(ctx))})
        lg = np.asarray(lg[0], np.float32)
        margin = float(lg[b[n]] - lg[a[n]])
        log(f"    request {i}: streams part at token {n}: one-chip logit "
            f"margin {margin:.3e} (tie bound {tie_tol:.3e})")
        if margin > tie_tol:
            raise AssertionError(f"request {i}: greedy streams part at "
                                 f"token {n} with margin {margin:.3e}")
    log(f"    greedy tokens, tensor-parallel vs one chip: {agree} of "
        f"{sum(len(b) for b in one_tokens)} agree before any parting")
    del params, adapters, params1, adapters1, state1
    gc.collect()

    # Step 2: the full 28-layer model, which no single 16 GB chip holds.
    mcfg = get_config("qwen2-7b")
    t0 = time.perf_counter()
    params, raw, opt_state = build_state(mcfg, scfg.dora, seed, mesh=mesh)
    adapters = perturb(raw, seed + 1,
                       S.adapter_sharding(mcfg, scfg.dora, mesh))
    del raw, opt_state
    jax.block_until_ready((params, adapters))
    per_chip = [(d.memory_stats() or {}).get("bytes_in_use", 0) / 2**30
                for d in devices]
    log(f"[4] step 2: {mcfg.name}, all {mcfg.num_layers} layers "
        f"({mcfg.count_params() * 2 / 2**30:.1f} GiB of bf16 weights): "
        f"{time.perf_counter() - t0:.1f}s to initialise; GiB in use per "
        f"chip {[round(g, 2) for g in per_chip]}")
    prompts = [rng.integers(0, mcfg.vocab_size, int(n), dtype=np.int32)
               for n in lengths]
    serve_mesh(mcfg, scfg, params, adapters, prompts, mesh,
               "28 layers, tensor-parallel x4")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the tensor-parallel phase on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    device = device_gate()
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        sys.exit(f"chip_smoke: the repro package is not at {src}")
    sys.path.insert(0, src)
    from repro.launch import compile_cache
    log(f"    compile cache: {compile_cache.enable()}")

    t0 = time.perf_counter()
    if args.four_chip:
        if device["count"] < 4:
            sys.exit(f"chip_smoke: --four-chip needs 4 devices, found "
                     f"{device['count']}")
        four_chip(args.seed)
    else:
        one_chip(args.seed)
    log(f"    all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
