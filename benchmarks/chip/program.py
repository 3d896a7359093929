"""The program under test, as the benchmark configures it: a configuration
file becomes the program's ``ModelConfig`` and ``StepConfig``. This is the
only module besides the drivers that imports the program."""
from __future__ import annotations

import jax.numpy as jnp

import model


def model_config(cfg: dict):
    from repro.models.config import ModelConfig
    d = model.dims(cfg)
    prog = cfg["program"]
    partial = d.rotary_dim != d.head_dim
    return ModelConfig(
        name=d.name, family="dense", num_layers=d.layers,
        d_model=d.d_model, num_heads=d.heads, num_kv_heads=d.kv_heads,
        d_ff=d.d_ff, vocab_size=d.vocab, head_dim=d.head_dim,
        qkv_bias=d.qkv_bias, rope_theta=d.rope_theta, norm_eps=d.norm_eps,
        pos_mode="rope_partial" if partial else "rope",
        rotary_dim=d.rotary_dim if partial else 0,
        attn_chunk=prog["attn_chunk"], remat=prog["remat"],
        dtype=jnp.dtype(cfg["torch_dtype"]))


def step_config(cfg: dict, traffic: dict | None = None):
    """``StepConfig`` with the file's DoRA settings and, for fine-tuning
    traffic, its loss window and optimizer."""
    from repro.core import DoRAConfig
    from repro.launch.steps import StepConfig
    from repro.optim import OptimizerConfig
    d = model.dims(cfg)
    dora = DoRAConfig(rank=d.rank, alpha=d.alpha, rslora=d.rslora,
                      mode=cfg["program"]["dora_mode"])
    if traffic is None or traffic["kind"] != "train":
        return StepConfig(dora=dora)
    opt = traffic["optimizer"]
    return StepConfig(dora=dora, loss_tokens=traffic["loss_tokens"],
                      optim=OptimizerConfig(
                          lr=opt["lr"], betas=(opt["beta1"], opt["beta2"]),
                          eps=opt["eps"], weight_decay=opt["weight_decay"],
                          clip_norm=opt["clip_norm"],
                          warmup_steps=opt["warmup_steps"],
                          total_steps=opt["total_steps"],
                          min_lr_ratio=opt["min_lr_ratio"]))
