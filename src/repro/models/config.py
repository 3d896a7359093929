"""Model configuration covering all 10 assigned architectures.

One dataclass describes dense GQA transformers, MoE (shared + routed top-k),
pure Mamba-1 stacks, hybrid mamba+attention interleaves (Jamba) and the
modality-frontend backbones (VLM patches / EnCodec audio tokens). The configs
in ``repro/configs`` instantiate it with the exact published numbers.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                     # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int                  # 0 for attention-free stacks
    num_kv_heads: int
    d_ff: int                       # dense-MLP hidden (0 = no MLP sublayer)
    vocab_size: int

    head_dim: int = 0               # 0 → d_model // num_heads
    # --- attention ---
    qk_norm: bool = False
    qkv_bias: bool = False
    pos_mode: str = "rope"          # rope | rope_partial | mrope | sinusoidal
    rope_theta: float = 10000.0
    rotary_dim: int = 0             # for rope_partial (ChatGLM 2d-RoPE)
    mrope_sections: tuple[int, int, int] = (16, 24, 24)
    attn_chunk: int | None = None   # online-softmax KV chunk (None = dense)
    # --- mlp ---
    mlp_kind: str = "swiglu"        # swiglu | gelu  (gelu = plain 2-mat MLP)
    norm_kind: str = "rms"          # rms | layer
    # --- MoE ---
    moe: bool = False
    num_experts: int = 0
    top_k: int = 0
    num_shared_experts: int = 0
    shared_d_ff: int = 0            # shared-expert hidden (qwen2-moe: 5632)
    moe_d_ff: int = 0               # routed-expert hidden
    moe_period: int = 1             # MoE FFN every k-th layer (jamba: 2)
    capacity_factor: float = 1.25
    # >1: chunk-local MoE dispatch aligned with the SP shards (H2.4);
    # set by the launch layer to the model-axis size, 0/1 = global.
    moe_seq_chunks: int = 0
    renorm_topk: bool = True        # renormalize top-k gate weights
    router_aux_coef: float = 0.0    # load-balance aux loss coefficient
    # --- SSM (Mamba-1) ---
    ssm: bool = False
    ssm_state: int = 16
    ssm_conv: int = 4
    ssm_expand: int = 2
    dt_rank: int = 0                # 0 → ceil(d_model / 16)
    ssm_chunk: int = 256            # chunked-scan length (assoc impl)
    # Jamba's mixer: RMSNorm with learned scales on dt, B and C after
    # x_proj (HF JambaMambaMixer dt/b/c_layernorm).
    ssm_inner_norm: bool = False
    # "fused_chunk": w-unrolled recurrence per scan chunk, a/b computed on
    #   the fly (never materialized at [B, S, di, n]).
    # "assoc": full-S a/b materialization + associative_scan (baseline).
    ssm_impl: str = "fused_chunk"
    ssm_unroll: int = 16            # tokens per unrolled chunk (fused)
    # --- hybrid interleave (Jamba: attn every 8th layer, index 4) ---
    attn_period: int = 0            # 0 = not hybrid
    attn_index: int = 4
    # --- misc ---
    norm_eps: float = 1e-5
    frontend: str | None = None     # None | "patches" | "audio_tokens"
    dtype: Any = jnp.bfloat16
    # remat: "none" | "layer" (recompute layer internals, save boundaries)
    remat: str = "layer"

    def __post_init__(self):
        if self.head_dim == 0 and self.num_heads:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.num_heads)
        if self.dt_rank == 0 and self.ssm:
            object.__setattr__(self, "dt_rank", -(-self.d_model // 16))
        if self.moe and self.moe_d_ff == 0:
            object.__setattr__(self, "moe_d_ff", self.d_ff)

    # -- derived --
    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    def layer_kinds(self) -> list[str]:
        """Sequence-mixer kind per layer: 'attn' or 'mamba'."""
        if self.ssm and not self.attn_period:
            return ["mamba"] * self.num_layers
        if self.attn_period:
            return ["attn" if i % self.attn_period == self.attn_index
                    else "mamba" for i in range(self.num_layers)]
        return ["attn"] * self.num_layers

    def ffn_kinds(self) -> list[str]:
        """FFN kind per layer: 'moe' | 'mlp' | 'none'."""
        out = []
        for i in range(self.num_layers):
            if self.moe and i % self.moe_period == self.moe_period - 1:
                out.append("moe")
            elif self.d_ff > 0:
                out.append("mlp")
            else:
                out.append("none")
        return out

    @property
    def is_uniform(self) -> bool:
        """True when every layer is identical (single scan stack)."""
        return (len(set(self.layer_kinds())) == 1
                and len(set(self.ffn_kinds())) == 1)

    @property
    def period(self) -> int:
        """Length of the repeating layer pattern (scan unit)."""
        if self.is_uniform:
            return 1
        p = self.attn_period or 1
        if self.moe and self.moe_period > 1:
            import math
            p = p * self.moe_period // math.gcd(p, self.moe_period)
        assert self.num_layers % p == 0, (self.num_layers, p)
        return p

    def count_params(self) -> int:
        """Total parameter count (embeddings + head included)."""
        D, V = self.d_model, self.vocab_size
        total = 2 * V * D + D  # embed + head + final norm
        kinds = list(zip(self.layer_kinds(), self.ffn_kinds()))
        for lk, fk in kinds:
            total += D  # ln1
            if lk == "attn":
                total += (self.q_dim * D + 2 * self.kv_dim * D
                          + D * self.q_dim)
                if self.qkv_bias:
                    total += self.q_dim + 2 * self.kv_dim
                if self.qk_norm:
                    total += 2 * self.head_dim
            else:
                di, n, dtr = self.d_inner, self.ssm_state, self.dt_rank
                total += (2 * di * D + di * self.ssm_conv + di
                          + (dtr + 2 * n) * di + di * dtr + di
                          + di * n + di + D * di)
                if self.ssm_inner_norm:
                    total += dtr + 2 * n
            if fk != "none":
                total += D  # ln2
            if fk == "mlp":
                total += (3 if self.mlp_kind == "swiglu" else 2) * self.d_ff * D
            elif fk == "moe":
                total += self.num_experts * (3 * self.moe_d_ff * D) \
                    + self.num_experts * D
                if self.num_shared_experts:
                    total += 3 * self.shared_d_ff * D + D
        return total

    def count_active_params(self) -> int:
        """Active params per token (MoE: top-k experts only)."""
        if not self.moe:
            return self.count_params()
        full = self.count_params()
        n_moe = sum(1 for k in self.ffn_kinds() if k == "moe")
        routed_all = n_moe * self.num_experts * 3 * self.moe_d_ff * self.d_model
        routed_active = n_moe * self.top_k * 3 * self.moe_d_ff * self.d_model
        return full - routed_all + routed_active
