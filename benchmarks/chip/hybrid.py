"""Shapes, seeded weights and model FLOP counts of a Mamba-1 + attention
hybrid configuration file (Jamba's layout).

The counterpart of :mod:`model` for a hybrid: the same seeding, types and
adapter initialisation, with the weights laid out as the program's period
tree takes them (``stack/l0`` ... ``stack/l{period-1}``, each leaf stacked
over the periods). Layer ``i`` is attention where ``i % attn_layer_period
== attn_layer_offset`` and a Mamba-1 mixer elsewhere; every layer has a
dense SwiGLU FFN. Nothing here imports the program under test.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

from model import B_SCALE, M_SPREAD, _normal, _scale, seed_key

ATTN = (("mixer", "wq"), ("mixer", "wk"), ("mixer", "wv"), ("mixer", "wo"))
MAMBA = (("mixer", "in_proj"), ("mixer", "out_proj"))
FFN = (("ffn", "w_gate"), ("ffn", "w_up"), ("ffn", "w_down"))
# Operations per (token, d_inner, state) element of the selective scan:
# forward dt·A, exp, a·h, u·B, +, h·C and the sum over the state (7);
# backward the cotangent's C·dy and +, the sums for d(dtx), dB, dC and
# d(dt) (a product and an add each), g·h·a, dA's product and add, and
# the carry a·g (15). The backward's recomputation of the states is not
# counted.
SCAN_FWD_OPS = 7
SCAN_BWD_OPS = 15


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
    norm_eps: float
    d_state: int
    d_conv: int
    d_inner: int
    dt_rank: int
    attn_period: int
    attn_offset: int
    rank: int
    alpha: float
    rslora: bool
    tied: bool = False        # the head holds the embedding's values

    @property
    def scaling(self) -> float:
        return self.alpha / (self.rank ** 0.5 if self.rslora else self.rank)

    @property
    def period(self) -> int:
        return self.attn_period

    def kind(self, i: int) -> str:
        """'attn' or 'mamba', for layer ``i`` (or position ``i`` of the
        period)."""
        return "attn" if i % self.attn_period == self.attn_offset \
            else "mamba"

    def projections(self, kind: str) -> tuple:
        """The adapted projections of a layer of ``kind``: (sublayer,
        name)."""
        return (ATTN if kind == "attn" else MAMBA) + FFN

    def proj_shape(self, name: str) -> tuple[int, int]:
        """(d_out, d_in) of a projection, adapted or not."""
        D, F, di = self.d_model, self.d_ff, self.d_inner
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return {"wq": (q, D), "wk": (kv, D), "wv": (kv, D), "wo": (D, q),
                "w_gate": (F, D), "w_up": (F, D), "w_down": (D, F),
                "in_proj": (2 * di, D), "out_proj": (D, di),
                "x_proj": (self.dt_rank + 2 * self.d_state, di),
                "dt_proj": (di, self.dt_rank)}[name]

    def layer_params(self, kind: str) -> int:
        """Parameters of the matrices of one layer (biases, norms, the
        conv and the scan's vectors aside), ``x_proj`` and ``dt_proj``
        included."""
        names = [n for _, n in self.projections(kind)]
        if kind == "mamba":
            names += ["x_proj", "dt_proj"]
        return sum(a * b for a, b in map(self.proj_shape, names))

    def adapter_params(self, kind: str) -> int:
        return sum(self.rank * (a + b) for a, b in
                   (self.proj_shape(n) for _, n in self.projections(kind)))


def dims(cfg: dict) -> Dims:
    D = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    dora = cfg["dora"]
    return Dims(name=cfg["name"], d_model=D, d_ff=cfg["intermediate_size"],
                heads=H, kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg.get("head_dim") or D // H,
                layers=cfg["num_hidden_layers"], vocab=cfg["vocab_size"],
                norm_eps=float(cfg["rms_norm_eps"]),
                d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
                d_inner=cfg["mamba_expand"] * D,
                dt_rank=cfg["mamba_dt_rank"],
                attn_period=cfg["attn_layer_period"],
                attn_offset=cfg["attn_layer_offset"],
                rank=int(dora["rank"]), alpha=float(dora["alpha"]),
                rslora=bool(dora["rslora"]),
                tied=bool(cfg.get("tie_word_embeddings", False)))


def _mamba_mixer(d: Dims, n: int, ks):
    """A Mamba-1 mixer's weights, each [n, ...]: projections N(0, 1/d_in);
    conv taps U(±1/√d_conv) and bias N(0, 0.02); dt_bias the inverse
    softplus of a log-uniform step in [1e-3, 1e-1] and A_log log(1..d_state)
    plus N(0, 0.1) (Mamba's initialisation, A perturbed so that a misplaced
    state column shows); skip D and the dt, B and C norm scales
    1 + N(0, 0.1)."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    di, st = d.d_inner, d.d_state
    out = {}
    for name in ("in_proj", "x_proj", "dt_proj", "out_proj"):
        d_out, d_in = d.proj_shape(name)
        out[name] = _normal(next(ks), (n, d_out, d_in), d_in ** -0.5, bf16)
    bound = d.d_conv ** -0.5
    out["conv_w"] = jax.random.uniform(next(ks), (n, d.d_conv, di), f32,
                                       -bound, bound).astype(bf16)
    out["conv_b"] = _normal(next(ks), (n, di), 0.02, bf16)
    step = jnp.exp(jax.random.uniform(next(ks), (n, di), f32,
                                      math.log(1e-3), math.log(1e-1)))
    out["dt_bias"] = (step + jnp.log(-jnp.expm1(-step))).astype(bf16)
    out["A_log"] = (jnp.log(jnp.arange(1, st + 1, dtype=f32))
                    + _normal(next(ks), (n, di, st), 0.1, f32)).astype(bf16)
    out["skip_d"] = _scale(next(ks), (n, di))
    out["dt_norm"] = _scale(next(ks), (n, d.dt_rank))
    out["b_norm"] = _scale(next(ks), (n, st))
    out["c_norm"] = _scale(next(ks), (n, st))
    return out


def _make(d: Dims, tenants: int, key):
    n, D, V = d.layers // d.period, d.d_model, d.vocab
    bf16 = jnp.bfloat16
    ks = iter(jax.random.split(key, 32 * d.period + 8
                               + 3 * 7 * d.period * tenants))
    stack, row_norm = {}, {}
    for u in range(d.period):
        kind = d.kind(u)
        layer = {"ln1": {"scale": _scale(next(ks), (n, D))},
                 "ln2": {"scale": _scale(next(ks), (n, D))},
                 "mixer": (_mamba_mixer(d, n, ks) if kind == "mamba"
                           else {}),
                 "ffn": {}}
        for sub, name in d.projections(kind):
            if name not in layer[sub]:
                d_out, d_in = d.proj_shape(name)
                layer[sub][name] = _normal(next(ks), (n, d_out, d_in),
                                           d_in ** -0.5, bf16)
            row_norm[u, name] = jnp.sqrt(jnp.sum(jnp.square(
                layer[sub][name].astype(jnp.float32)), axis=-1))
        stack[f"l{u}"] = layer
    embed = _normal(next(ks), (V, D), 0.02, bf16)
    final_scale = _scale(next(ks), (D,))
    head = _normal(next(ks), (V, D), D ** -0.5, bf16)
    params = {"embed": embed, "stack": stack,
              "final_norm": {"scale": final_scale},
              "head": embed if d.tied else head}
    adapters = []
    for _ in range(tenants):
        tree = {}
        for u in range(d.period):
            unit = tree[f"l{u}"] = {"mixer": {}, "ffn": {}}
            for sub, name in d.projections(d.kind(u)):
                d_out, d_in = d.proj_shape(name)
                bound = d_in ** -0.5
                A = jax.random.uniform(next(ks), (n, d.rank, d_in),
                                       jnp.float32, -bound, bound)
                B = _normal(next(ks), (n, d_out, d.rank), B_SCALE, bf16)
                m = row_norm[u, name] * (1.0 + _normal(
                    next(ks), (n, d_out), M_SPREAD, jnp.float32))
                unit[sub][name] = {"A": A.astype(bf16), "B": B, "m": m}
        adapters.append({"stack": tree})
    return params, tuple(adapters)


@functools.lru_cache(maxsize=None)
def _maker(d: Dims, tenants: int):
    return jax.jit(lambda key: _make(d, tenants, key))


def make_weights(d: Dims, seed: int, tenants: int = 1):
    """(params, (adapters of each tenant)) from ``seed``: one jitted call,
    on the default device."""
    return _maker(d, tenants)(seed_key(seed))


def adapter_leaves(adapters) -> list[tuple[str, jax.Array]]:
    """(path, leaf) of an adapter tree in a fixed order, by layer and then
    by name: ``l0/mixer/in_proj/A`` ... ``l{period-1}/ffn/w_up/m``."""
    out = []
    stack = adapters["stack"]
    for u in sorted(stack, key=lambda k: int(k[1:])):
        for sub in ("mixer", "ffn"):
            for name in sorted(stack[u][sub]):
                for k in ("A", "B", "m"):
                    out.append((f"{u}/{sub}/{name}/{k}",
                                stack[u][sub][name][k]))
    return out


# ---------------------------------------------------------------------------
# Model FLOPs, counted from the shapes (recomputation excluded).
# ---------------------------------------------------------------------------

def scan_ops(d: Dims, *, tokens: int) -> tuple[float, float]:
    """(forward, backward) operations of one Mamba layer's selective scan
    over ``tokens`` tokens."""
    elems = float(tokens) * d.d_inner * d.d_state
    return SCAN_FWD_OPS * elems, SCAN_BWD_OPS * elems


def train_flops_per_step(d: Dims, *, batch: int, seq: int,
                         loss_tokens: int) -> float:
    """Forward and backward FLOPs of one DoRA fine-tuning step with the base
    frozen, as :func:`model.train_flops_per_step` counts them (base matmuls
    4 per parameter and token, ``x_proj`` and ``dt_proj`` among them;
    adapter factors 6 per adapter parameter and token; causal attention;
    the head over the loss tokens; the weight norms once per step), and
    the selective scan of every Mamba layer forward and backward. The
    depthwise conv, the norms and the other element-wise work are left
    out, as they are for the dense model."""
    tokens = batch * seq
    total = 4.0 * batch * loss_tokens * d.vocab * d.d_model
    for i in range(d.layers):
        kind = d.kind(i)
        total += 4 * d.layer_params(kind) * tokens
        total += 6 * d.adapter_params(kind) * tokens
        total += sum(2 * a * b * d.rank for a, b in
                     (d.proj_shape(n) for _, n in d.projections(kind)))
        if kind == "attn":
            total += 3 * 2 * batch * seq * seq * d.heads * d.head_dim
        else:
            total += sum(scan_ops(d, tokens=tokens))
    return float(total)
