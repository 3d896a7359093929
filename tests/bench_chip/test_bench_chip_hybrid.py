"""The hybrid (Mamba-1 + attention) configuration's benchmark files at a
tiny size on the CPU: the plain float32 reference against the program in
float32, the weights laid out as the program's tree, and the FLOP and
scan-kernel cost functions (the decision on ``correct`` is in
``test_bench_chip_hybrid_correct.py``)."""
from __future__ import annotations

import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import gen  # noqa: E402
import harness  # noqa: E402
import hybrid  # noqa: E402
import reference_hybrid as RH  # noqa: E402

SEED = 2 ** 41 + 7
CELL = "train-16k-hybrid.jamba2-3b-14L"
# The numbers of the program's jamba2-3b SMOKE configuration (14 layers,
# attention at index 7), as a configuration file.
SMOKE = {"name": "tiny-hybrid", "model_type": "jamba", "hidden_size": 64,
         "intermediate_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 1, "num_hidden_layers": 14,
         "vocab_size": 256, "rms_norm_eps": 1e-6, "attn_layer_period": 14,
         "attn_layer_offset": 7, "mamba_d_state": 4, "mamba_d_conv": 4,
         "mamba_expand": 2, "mamba_dt_rank": 8, "tie_word_embeddings": True,
         "torch_dtype": "float32",
         "dora": {"rank": 8, "alpha": 16.0, "rslora": True},
         "program": {"attn_chunk": 1024, "remat": "layer",
                     "dora_mode": "eager"}}
# The same layers in a period of 4 (Mamba, Mamba, attention, Mamba), so
# that the tests that compile the program and the reference stay short.
TINY = dict(SMOKE, num_hidden_layers=4, attn_layer_period=4,
            attn_layer_offset=2)
LIMITS = json.loads((CHIP / "limits" / f"{CELL}.json").read_text())
# A sound run at this size is held to limits of its own, as the dense
# cells' tiny runs are (test_bench_chip_correct.py): bf16 rounding at
# d_model 64 and rank 8 reads far more than at the cell's widths.
TINY_LIMITS = {"grad_gap": 0.05, "update_gap": 0.2}


@pytest.fixture(autouse=True)
def _x64_off():
    """Other test modules turn on 64-bit types when they are imported; the
    benchmark runs with JAX's default 32-bit types."""
    with jax.enable_x64(False):
        yield


def driver():
    return harness.load_module("drivers", "train_hybrid")


def traffic(**kw):
    tr = json.loads((CHIP / "traffic" / "train-16k-hybrid.json").read_text())
    return dict(tr, **kw)


def f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def test_tiny_file_is_the_programs_smoke_configuration():
    from repro.configs import get_config
    smoke = get_config("jamba2-3b", smoke=True)
    got = driver().model_config(SMOKE)
    for field in ("num_layers", "d_model", "num_heads", "num_kv_heads",
                  "d_ff", "vocab_size", "ssm_state", "ssm_conv",
                  "ssm_expand", "dt_rank", "ssm_inner_norm", "attn_period",
                  "attn_index", "pos_mode", "norm_eps", "period"):
        assert getattr(got, field) == getattr(smoke, field), field
    assert got.layer_kinds() == smoke.layer_kinds()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_weights_are_laid_out_as_the_programs_tree(dtype):
    from repro.core import DoRAConfig
    from repro.models import adapter_shapes, param_shapes
    cfg = dict(TINY, torch_dtype=dtype)
    d = hybrid.dims(cfg)
    mcfg = driver().model_config(cfg)
    params, (ad,) = hybrid.make_weights(d, SEED)
    want = jax.tree.map(lambda s: s.shape, param_shapes(mcfg))
    assert jax.tree.map(lambda x: x.shape, params) == want
    dcfg = DoRAConfig(rank=d.rank)
    want = jax.tree.map(lambda s: s.shape, adapter_shapes(mcfg, dcfg))
    assert jax.tree.map(lambda x: x.shape, ad) == want
    assert np.array_equal(np.asarray(params["head"]),
                          np.asarray(params["embed"]))
    leaves = hybrid.adapter_leaves(ad)
    assert len(leaves) == 3 * (3 * 5 + 7)
    assert leaves[0][0] == "l0/mixer/in_proj/A"
    assert "l2/mixer/wk/m" in dict(leaves)


@pytest.mark.parametrize("block", [256, 16])   # 16: 3 blocks, 8 padded
def test_reference_logits_equal_the_programs_in_float32(block, monkeypatch):
    from repro.models import forward
    monkeypatch.setattr(RH, "TOKEN_BLOCK", block)
    d = hybrid.dims(TINY)
    params, (ad,) = hybrid.make_weights(d, SEED)
    tokens = np.random.default_rng(0).integers(0, 256, 40, dtype=np.int32)
    drv = driver()
    got, _, _ = forward(drv.model_config(TINY), f32(params), ad,
                        drv.step_config(TINY, traffic()).dora,
                        tokens=jnp.asarray(tokens)[None], training=False)
    want = RH.logits(d, params, ad, jnp.asarray(tokens))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_reference_step_equals_the_programs_in_float32():
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    drv = driver()
    tr = traffic(seq=48, loss_tokens=16)
    d = hybrid.dims(TINY)
    params, (ad,) = hybrid.make_weights(d, SEED)
    b = gen.TrainStream(vocab=256, seq=48, batch=1, seed=SEED).batch_np(0)
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    scfg = drv.step_config(TINY, tr)
    step = make_train_step(drv.model_config(TINY), scfg, None, batch=1,
                           seq=48)
    a32 = f32(ad)
    new, opt, metrics = jax.jit(step)(f32(params), a32, adamw_init(a32),
                                      batch)
    ref_a, mom = RH.start_training(ad)
    ref_new, _, loss, grads = RH.train_step(
        d, ref_a, mom, params, batch["tokens"], batch["labels"],
        tr["loss_tokens"], tuple(sorted(tr["optimizer"].items())), None)
    assert float(metrics["loss"]) == pytest.approx(float(loss), rel=1e-5)
    # the first moment of the first step is (1 - beta1) times the gradient
    b1 = scfg.optim.betas[0]
    for (p, g), (_, want) in zip(hybrid.adapter_leaves(opt["mu"]),
                                 hybrid.adapter_leaves(grads)):
        np.testing.assert_allclose(np.asarray(g) / (1 - b1),
                                   np.asarray(want), rtol=2e-3,
                                   atol=2e-3 * float(jnp.max(jnp.abs(want))),
                                   err_msg=p)
    # an element whose gradient is near AdamW's eps moves by a share of lr
    # (2e-4) that rounding decides: 5% of lr
    for (p, x), (_, y) in zip(hybrid.adapter_leaves(new),
                              hybrid.adapter_leaves(ref_new)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5,
                                   err_msg=p)


def test_reference_scan_equals_the_recurrence():
    k = jax.random.PRNGKey(0)
    S, di, n = 37, 8, 4
    dt = jax.nn.softplus(jax.random.normal(k, (S, di)))
    u = jax.random.normal(jax.random.fold_in(k, 1), (S, di))
    Bm = jax.random.normal(jax.random.fold_in(k, 2), (S, n))
    Cm = jax.random.normal(jax.random.fold_in(k, 3), (S, n))
    A = -jnp.exp(jax.random.normal(jax.random.fold_in(k, 4), (di, n)))
    h = np.zeros((di, n))
    want = []
    for t in range(S):
        h = np.exp(np.asarray(dt[t])[:, None] * np.asarray(A)) * h \
            + np.asarray(u[t])[:, None] * np.asarray(Bm[t])[None, :]
        want.append(h @ np.asarray(Cm[t]))
    h0 = jnp.zeros((di, n))
    _, got = RH.ssm_scan(h0, dt, u, Bm, Cm, A, jnp.arange(S))
    _, reset = RH.ssm_scan(h0, dt, u, Bm, Cm, A, jnp.arange(S), reset=8)
    np.testing.assert_allclose(np.asarray(got), np.stack(want), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(reset[:8]), np.stack(want[:8]),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(reset[8:]), np.stack(want[8:]))


# ---------------------------------------------------------------------------
# Costs from shapes
# ---------------------------------------------------------------------------

def test_scan_kernel_costs_from_shapes():
    B, S, di, n, nc, nt = 1, 16384, 5120, 16, 128, 10
    fwd = harness.load_module("kernels", "selective_scan_fwd")
    bwd = harness.load_module("kernels", "selective_scan_bwd")
    assert fwd.BOUND == bwd.BOUND == "bandwidth"
    row, col = (4, (B, S, di)), (4, (B, n, S))
    state = (4, (B, n, di))
    c = fwd.cost([row, row, col, col, (4, (n, di)), state],
                 [row, state, (4, (B, nc, n, di))])
    assert c["flops"] == 7 * S * di * n
    assert c["bytes"] == 4 * (3 * S * di + 2 * n * S + n * di + 2 * n * di
                              + nc * n * di)
    part = (4, (B, nt, n, S))
    c = bwd.cost([row, row, col, col, (4, (n, di)), (4, (B, nc, n, di)),
                  row, state], [row, row, part, part, state, state])
    assert c["flops"] == 15 * S * di * n
    assert c["bytes"] == 4 * (5 * S * di + 2 * n * S + n * di
                              + nc * n * di + 3 * n * di + 2 * nt * n * S)


def test_train_flops_by_hand():
    d = hybrid.dims(SMOKE)
    D, F, di, r, V = 64, 128, 128, 8, 256
    S, T = 32, 8
    mamba_mats = 2 * di * D + D * di + (8 + 8) * di + di * 8 + 3 * F * D
    attn_mats = 2 * D * D + 2 * 16 * D + 3 * F * D
    mamba_ad = r * (2 * di + D) + r * (D + di) + 3 * r * (F + D)
    attn_ad = 2 * r * (D + D) + 2 * r * (16 + D) + 3 * r * (F + D)
    mamba_norm = 2 * r * (2 * di * D + D * di + 3 * F * D)
    attn_norm = 2 * r * (2 * D * D + 2 * 16 * D + 3 * F * D)
    want = (13 * (4 * mamba_mats * S + 6 * mamba_ad * S + mamba_norm
                  + (7 + 15) * S * di * 4)
            + 4 * attn_mats * S + 6 * attn_ad * S + attn_norm
            + 3 * 2 * S * S * 4 * 16
            + 4 * T * V * D)
    assert hybrid.train_flops_per_step(d, batch=1, seq=S,
                                       loss_tokens=T) == want


def test_blocked_attention_equals_the_dense_reference():
    """The hybrid reference's attention (query blocks from projection to
    output projection, KV heads shared without repeating) against
    :mod:`reference`'s projections and attention, which repeat them. (Under
    float8 the two round sums taken in another order, so they agree only
    to float8's step.)"""
    import reference as R
    d = hybrid.dims(TINY)
    params, (ad,) = hybrid.make_weights(d, SEED)
    p = jax.tree.map(lambda t: t[0], params["stack"]["l2"]["mixer"])
    a = jax.tree.map(lambda t: t[0], ad["stack"]["l2"]["mixer"])
    h = jax.random.normal(jax.random.PRNGKey(0), (40, 64))
    s = d.scaling
    q, k, v = (R.dora(h, p[n], a[n], s, None, None).reshape(40, -1, 16)
               for n in ("wq", "wk", "wv"))
    o = R.attention(q, k, v, None, block=16).reshape(40, 64)
    want = R.dora(o, p["wo"], a["wo"], s, None, None)
    got = RH.attention(d, h, p, a, None, block=16)   # 3 blocks, 8 padded
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
