"""End-to-end training driver.

Wires every substrate layer together: configs → model init → DoRA adapter
init → sharding (when a mesh is requested) → synthetic data pipeline with
prefetch → AdamW over adapters → checkpoint/auto-resume → preemption +
heartbeat fault-tolerance hooks.

Runs for real on CPU with a smoke config::

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --smoke \
        --steps 50 --batch 4 --seq 64

and is the same driver a TPU deployment launches per host (the mesh comes
from ``make_production_mesh``; per-host data sharding from
``jax.process_index()``).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (CheckpointConfig, Heartbeat,
                              PreemptionHandler, latest_step,
                              restore_checkpoint, save_checkpoint)
from repro.configs import get_config
from repro.core import DoRAConfig
from repro.data import DataConfig, make_train_iterator, prefetch
from repro.launch import compile_cache
from repro.launch import sharding as S
from repro.launch.steps import StepConfig, make_train_step
from repro.models import adapter_shapes, init_adapters, init_params
from repro.obs import monotonic
from repro.optim import OptimizerConfig, adamw_init


def build_state(mcfg, dcfg, seed: int = 0, mesh=None):
    """(params, adapters, opt_state) from ``seed``, each initialised by one
    jitted program straight into its layout: the ``mesh`` shardings of
    :mod:`repro.launch.sharding` when a mesh is given (no array is ever
    whole on one device first), the default device otherwise."""
    p_sh = a_sh = o_sh = None
    if mesh is not None:
        p_sh = S.param_sharding(mcfg, mesh)
        a_sh = S.adapter_sharding(mcfg, dcfg, mesh)
        o_sh = S.opt_state_sharding(a_sh, mesh, adapter_shapes(mcfg, dcfg))
    key = jax.random.PRNGKey(seed)
    params = jax.jit(init_params, static_argnums=1,
                     out_shardings=p_sh)(key, mcfg)
    adapters = jax.jit(init_adapters, static_argnums=(1, 3),
                       out_shardings=a_sh)(
        jax.random.fold_in(key, 1), mcfg, params, dcfg)
    opt_state = jax.jit(adamw_init, out_shardings=o_sh)(adapters)
    return params, adapters, opt_state


def train(args) -> dict:
    mcfg = get_config(args.arch, smoke=args.smoke)
    dcfg = DoRAConfig(rank=args.rank, alpha=args.alpha,
                      mode=args.dora_mode, norm_impl=args.norm_impl)
    ocfg = OptimizerConfig(lr=args.lr, warmup_steps=args.warmup,
                           total_steps=args.steps,
                           clip_norm=args.clip_norm)
    scfg = StepConfig(dora=dcfg, optim=ocfg,
                      loss_tokens=args.loss_tokens,
                      grad_accum=args.grad_accum)

    params, adapters, opt_state = build_state(mcfg, dcfg, args.seed)

    ckpt = CheckpointConfig(args.ckpt_dir, every_steps=args.ckpt_every,
                            keep=args.ckpt_keep)
    start_step = 0
    if args.resume:
        restored, step = restore_checkpoint(
            ckpt, {"adapters": adapters, "opt": opt_state})
        if restored is not None:
            adapters, opt_state = restored["adapters"], restored["opt"]
            start_step = step
            print(f"resumed from step {start_step}")

    dcfg_data = DataConfig(vocab_size=mcfg.vocab_size, seq_len=args.seq,
                           global_batch=args.batch, seed=args.data_seed)
    it = prefetch(make_train_iterator(
        dcfg_data, start_step=start_step,
        process_index=jax.process_index(),
        process_count=jax.process_count()), depth=2)

    step_fn = jax.jit(make_train_step(mcfg, scfg, None,
                                      batch=args.batch, seq=args.seq),
                      donate_argnums=(1, 2))

    hb = Heartbeat(args.heartbeat_dir, jax.process_index()) \
        if args.heartbeat_dir else None
    losses = []
    t_start = monotonic()
    with PreemptionHandler() as pre:
        for step in range(start_step, args.steps):
            batch = {k: jnp.asarray(v) for k, v in next(it).items()}
            adapters, opt_state, metrics = step_fn(
                params, adapters, opt_state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if hb:
                hb.beat(step)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.3f}", flush=True)
            want_ckpt = ((step + 1) % args.ckpt_every == 0
                         or step == args.steps - 1)
            if pre.preempted:
                print(f"preemption signal at step {step}: saving + exiting")
                want_ckpt = True
            if want_ckpt and args.ckpt_dir:
                save_checkpoint(
                    ckpt, step + 1,
                    {"adapters": adapters, "opt": opt_state},
                    process_index=jax.process_index(),
                    process_count=jax.process_count(),
                    mesh_meta={"model": 1})
            if pre.preempted:
                break
    dt = monotonic() - t_start
    steps_done = len(losses)
    print(f"done: {steps_done} steps in {dt:.1f}s "
          f"({dt / max(steps_done, 1):.2f} s/step); "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "steps": steps_done, "wall_s": dt}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--rank", type=int, default=16)
    ap.add_argument("--alpha", type=float, default=32.0)
    ap.add_argument("--dora-mode", default="auto",
                    choices=["auto", "eager", "fused", "interpret"])
    ap.add_argument("--norm-impl", default="factored",
                    choices=["factored", "dense_ba", "peft_eye"])
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--clip-norm", type=float, default=1.0)
    ap.add_argument("--loss-tokens", type=int, default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--data-seed", type=int, default=1234)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--ckpt-keep", type=int, default=3)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--heartbeat-dir", default="")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()
    compile_cache.enable()
    train(args)


if __name__ == "__main__":
    main()
