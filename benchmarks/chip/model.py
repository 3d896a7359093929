"""Shapes, seeded weights and model FLOP counts of a configuration file.

Nothing here imports the program under test. The weights are made by the
benchmark from ``--seed`` in one jitted call, on the device, in the types
they are served in (bf16 weights, bf16 adapter factors, fp32 magnitudes),
and laid out as the program's parameter and adapter trees take them. The
plain reference (:mod:`reference`) makes the same weights again from the
same seed after the program's state is freed, so it takes nothing that the
program has made.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

HERE = pathlib.Path(__file__).resolve().parent

# The seven adapted projections of a dense GQA + SwiGLU layer, in the
# program's tree: (sublayer, name).
PROJECTIONS = (("mixer", "wq"), ("mixer", "wk"), ("mixer", "wv"),
               ("mixer", "wo"), ("ffn", "w_gate"), ("ffn", "w_up"),
               ("ffn", "w_down"))
# Scale of the seeded adapter up-projection B. At B = 0 DoRA is the
# identity and a wrong compose or norm would go unseen; at 1e-3 the LoRA
# term is about a tenth of each projection's output at r = 384.
B_SCALE = 1e-3
# Spread of each tenant's magnitude vector m around ||W||_row.
M_SPREAD = 1e-2


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    layers: int
    vocab: int
    qkv_bias: bool
    norm_eps: float
    rope_theta: float
    rotary_dim: int
    rank: int
    alpha: float
    rslora: bool
    tied: bool = False        # the head holds the embedding's values

    @property
    def scaling(self) -> float:
        return self.alpha / (self.rank ** 0.5 if self.rslora else self.rank)

    def proj_shape(self, name: str) -> tuple[int, int]:
        """(d_out, d_in) of one adapted projection."""
        D, F = self.d_model, self.d_ff
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return {"wq": (q, D), "wk": (kv, D), "wv": (kv, D), "wo": (D, q),
                "w_gate": (F, D), "w_up": (F, D), "w_down": (D, F)}[name]

    def layer_params(self) -> int:
        """Parameters of the matrices of one layer (biases and norms
        aside)."""
        return sum(a * b for a, b in
                   (self.proj_shape(n) for _, n in PROJECTIONS))

    def adapter_params_per_layer(self) -> int:
        return sum(self.rank * (a + b) for a, b in
                   (self.proj_shape(n) for _, n in PROJECTIONS))


def load_config_file(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def dims(cfg: dict) -> Dims:
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or D // H
    dora = cfg["dora"]
    return Dims(name=cfg["name"], d_model=D, d_ff=cfg["intermediate_size"],
                heads=H, kv_heads=cfg["num_key_value_heads"], head_dim=hd,
                layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
                qkv_bias=bool(cfg.get("qkv_bias", False)),
                norm_eps=float(cfg["rms_norm_eps"]),
                rope_theta=float(cfg["rope_theta"]),
                rotary_dim=int(round(hd * cfg.get("partial_rotary_factor",
                                                  1.0))),
                rank=int(dora["rank"]), alpha=float(dora["alpha"]),
                rslora=bool(dora["rslora"]),
                tied=bool(cfg.get("tie_word_embeddings", False)))


def seed_key(seed: int):
    """A PRNG key for any whole number below 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not in [0, 2**64)")
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, np.uint32(seed >> 32))


def _normal(key, shape, std, dtype):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _scale(key, shape):
    """A norm's scale vector: 1 + N(0, 0.1), so a misapplied scale shows."""
    return (1.0 + _normal(key, shape, 0.1, jnp.float32)).astype(jnp.bfloat16)


def _make(d: Dims, tenants: int, key):
    L, D, V = d.layers, d.d_model, d.vocab
    bf16 = jnp.bfloat16
    ks = iter(jax.random.split(key, 64 + 8 * tenants * len(PROJECTIONS)))
    layer = {"ln1": {"scale": _scale(next(ks), (L, D))},
             "ln2": {"scale": _scale(next(ks), (L, D))},
             "mixer": {}, "ffn": {}}
    for sub, name in PROJECTIONS:
        d_out, d_in = d.proj_shape(name)
        layer[sub][name] = _normal(next(ks), (L, d_out, d_in),
                                   d_in ** -0.5, bf16)
    if d.qkv_bias:
        for name in ("wq", "wk", "wv"):
            d_out, _ = d.proj_shape(name)
            layer["mixer"][name + "_bias"] = _normal(next(ks), (L, d_out),
                                                     0.02, bf16)
    embed = _normal(next(ks), (V, D), 0.02, bf16)
    final_scale = _scale(next(ks), (D,))
    head = _normal(next(ks), (V, D), D ** -0.5, bf16)
    params = {"embed": embed, "stack": {"l0": layer},
              "final_norm": {"scale": final_scale},
              "head": embed if d.tied else head}
    row_norm = {name: jnp.sqrt(jnp.sum(jnp.square(
        layer[sub][name].astype(jnp.float32)), axis=-1))
        for sub, name in PROJECTIONS}
    adapters = []
    for _ in range(tenants):
        tree = {"mixer": {}, "ffn": {}}
        for sub, name in PROJECTIONS:
            d_out, d_in = d.proj_shape(name)
            bound = d_in ** -0.5
            A = jax.random.uniform(next(ks), (L, d.rank, d_in), jnp.float32,
                                   -bound, bound).astype(bf16)
            B = _normal(next(ks), (L, d_out, d.rank), B_SCALE, bf16)
            m = row_norm[name] * (1.0 + _normal(next(ks), (L, d_out),
                                                M_SPREAD, jnp.float32))
            tree[sub][name] = {"A": A, "B": B, "m": m}
        adapters.append({"stack": {"l0": tree}})
    return params, tuple(adapters)


@functools.lru_cache(maxsize=None)
def _maker(d: Dims, tenants: int):
    return jax.jit(lambda key: _make(d, tenants, key))


def make_weights(d: Dims, seed: int, tenants: int = 1):
    """(params, (adapters of each tenant)) from ``seed``: one jitted call,
    on the default device."""
    return _maker(d, tenants)(seed_key(seed))


def adapter_leaves(adapters) -> list[tuple[str, jax.Array]]:
    """(path, leaf) of an adapter tree in a fixed order:
    ``l0/mixer/wq/A`` ... ``l0/ffn/w_down/m``."""
    out = []
    unit = adapters["stack"]["l0"]
    for sub, name in PROJECTIONS:
        for k in ("A", "B", "m"):
            out.append((f"{sub}/{name}/{k}", unit[sub][name][k]))
    return out


# ---------------------------------------------------------------------------
# Model FLOPs, counted from the shapes (recomputation excluded).
# ---------------------------------------------------------------------------

def train_flops_per_step(d: Dims, *, batch: int, seq: int,
                         loss_tokens: int) -> float:
    """Forward and backward FLOPs of one DoRA fine-tuning step with the base
    frozen: the base matmuls forward and their input gradient (4 per
    parameter and token), the adapter factors forward and both gradients
    (6 per adapter parameter and token), causal attention forward and
    backward, the head over the loss tokens forward and its input gradient,
    and the weight norm ||W + sBA||_row once per step (W @ Aᵀ: 2 d_out d_in
    r per projection). Recomputation by remat is not counted."""
    tokens = batch * seq
    base = 4 * d.layer_params() * tokens
    adapter = 6 * d.adapter_params_per_layer() * tokens
    attn = 3 * 2 * batch * seq * seq * d.heads * d.head_dim   # causal
    norm = sum(2 * a * b * d.rank for a, b in
               (d.proj_shape(n) for _, n in PROJECTIONS))
    head = 4 * batch * loss_tokens * d.vocab * d.d_model
    return float(d.layers * (base + adapter + attn + norm) + head)


def decode_flops_per_step(d: Dims, *, rows: int) -> float:
    """FLOPs of one decode step over ``rows`` rows, each under one tenant's
    adapter: the base matmuls and the head (2 per parameter and row) and
    one adapter's factors per row. Attention over the cached context is
    left out (under 4% of a step at 2048 positions); so is the work of the
    adapters of the other tenants stacked beside a row's own."""
    per_row = (d.layers * (d.layer_params() + d.adapter_params_per_layer())
               + d.vocab * d.d_model)
    return float(2 * rows * per_row)
