"""Fine-tuning traffic on a Mamba-1 + attention hybrid: the ``train``
driver's protocol on the hybrid's model.

Set-up makes the weights and adapters from the seed (:mod:`hybrid`),
compiles the program's train step and drives it through its first three
steps on the window's own feed; the window keeps up to ``in_flight``
steps dispatched; once the program's state is freed, the first steps'
losses, first gradient and three-step change are compared with the plain
reference (:mod:`reference_hybrid`). That protocol is ``drivers/train.py``'s
own code: this module loads a private copy of it and points the copy's
``model``, ``program`` and ``R`` at the hybrid's weights, at the program
configuration below and at the hybrid reference, so that the two drivers
run one protocol.
"""
from __future__ import annotations

import types

import jax.numpy as jnp

import harness as H
import hybrid
import reference_hybrid

T = H.load_module("drivers", "train")


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of a hybrid configuration file."""
    from repro.models.config import ModelConfig
    d = hybrid.dims(cfg)
    prog = cfg["program"]
    return ModelConfig(
        name=d.name, family="hybrid", num_layers=d.layers,
        d_model=d.d_model, num_heads=d.heads, num_kv_heads=d.kv_heads,
        d_ff=d.d_ff, vocab_size=d.vocab, head_dim=d.head_dim,
        norm_eps=d.norm_eps, pos_mode="none",
        ssm=True, ssm_state=d.d_state, ssm_conv=d.d_conv,
        ssm_expand=d.d_inner // d.d_model, dt_rank=d.dt_rank,
        ssm_inner_norm=cfg["model_type"] == "jamba",
        attn_period=d.attn_period, attn_index=d.attn_offset,
        attn_chunk=prog["attn_chunk"], remat=prog["remat"],
        dtype=jnp.dtype(cfg["torch_dtype"]))


def step_config(cfg: dict, traffic: dict):
    """``StepConfig`` with the file's DoRA settings, the traffic's loss
    window and its optimizer."""
    from repro.core import DoRAConfig
    from repro.launch.steps import StepConfig
    from repro.optim import OptimizerConfig
    d = hybrid.dims(cfg)
    opt = traffic["optimizer"]
    return StepConfig(
        dora=DoRAConfig(rank=d.rank, alpha=d.alpha, rslora=d.rslora,
                        mode=cfg["program"]["dora_mode"]),
        loss_tokens=traffic["loss_tokens"],
        optim=OptimizerConfig(
            lr=opt["lr"], betas=(opt["beta1"], opt["beta2"]),
            eps=opt["eps"], weight_decay=opt["weight_decay"],
            clip_norm=opt["clip_norm"], warmup_steps=opt["warmup_steps"],
            total_steps=opt["total_steps"],
            min_lr_ratio=opt["min_lr_ratio"]))


T.model, T.R = hybrid, reference_hybrid
T.program = types.SimpleNamespace(model_config=model_config,
                                  step_config=step_config)

CHECK_STEPS = T.CHECK_STEPS
Trainer = T.Trainer
reference_readings = T.reference_readings
compare = T.compare
run = T.run
