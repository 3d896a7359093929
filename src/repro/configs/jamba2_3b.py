"""jamba2-3b [hybrid] — AI21 Jamba2-3B: Mamba-1 + attention, dense SwiGLU.
28L d_model=2560, 20 query heads of 128 over ONE KV head (MQA), d_ff=8192,
vocab=65536 (tied head), ssm_state=16, d_conv=4, expand=2 (d_inner 5120),
dt_rank=160, rms_norm_eps=1e-6.
[huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json]

Layer order as HF ``JambaConfig`` builds it: attention where
``i % attn_layer_period == attn_layer_offset`` (period 14, offset 7:
layers 7 and 21), Mamba elsewhere; ``num_experts`` 1, so every FFN is a
dense MLP. No positional encoding (the Mamba layers carry position). The
mixer is Jamba's: RMSNorms with learned scales on dt, B and C after
``x_proj`` (``ssm_inner_norm``).
"""
import jax.numpy as jnp

from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="jamba2-3b", family="hybrid",
    num_layers=28, d_model=2560, num_heads=20, num_kv_heads=1,
    d_ff=8192, vocab_size=65536,
    pos_mode="none", norm_eps=1e-6,
    ssm=True, ssm_state=16, ssm_conv=4, ssm_expand=2, dt_rank=160,
    ssm_inner_norm=True,
    attn_period=14, attn_index=7,
    attn_chunk=1024,
)

SMOKE = ModelConfig(
    name="jamba2-smoke", family="hybrid",
    num_layers=14, d_model=64, num_heads=4, num_kv_heads=1,
    d_ff=128, vocab_size=256,
    pos_mode="none", norm_eps=1e-6,
    ssm=True, ssm_state=4, ssm_conv=4, ssm_expand=2, dt_rank=8,
    ssm_inner_norm=True,
    attn_period=14, attn_index=7,
    dtype=jnp.float32,
)
