"""Matmul-fused compose: tier equivalence + VJP vs the fp64 eager oracle.

The fused kernel computes the LoRA up-projection h@Bᵀ on-chip and composes
delta = (g-1)⊙base + g⊙s⊙(hBᵀ) in the same pass — y_lora is never
materialized. These tests lock (a) the forward against the fp64 oracle at
the golden tolerances of the elementwise-fused kernel, (b) all three
cotangent families (d_base/d_h, d_B, d_g) against autodiff through the
eager form, on both the interpret and eager backends, including
non-multiple-of-block ranks and padded (ragged) row counts, and (c) the
dispatch crossover guard for the new plan flag.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.adapter as ad
import repro.core.dispatch as dp
from repro.core import DoRAConfig
from repro.kernels import ops, ref

jax.config.update("jax_enable_x64", True)


def _tol(dtype):
    if dtype == jnp.float32:
        return dict(rtol=1e-5, atol=1e-5)
    return dict(rtol=2e-2, atol=2e-2)


def _mk(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _inputs(key, m, n, r, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    base = _mk(k1, (m, n), dtype)
    h = _mk(k2, (m, r), dtype, 0.3)
    B = _mk(k3, (n, r), dtype, 0.3)
    g = 1.0 + 0.0015 * jax.random.normal(k4, (n,), jnp.float32)
    return base, h, B, g


# (rows, d_out, r) — ragged rows and ranks off the 128-lane / 8-sublane
# grid on purpose; the wrapper pads both.
MM_SHAPES = [(8, 128, 4), (64, 256, 16), (100, 384, 11), (17, 2048, 384),
             (256, 1024, 128), (33, 512, 129)]
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("shape", MM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_mm_fwd_matches_fp64_oracle(shape, dtype):
    m, n, r = shape
    base, h, B, g = _inputs(jax.random.PRNGKey(0), m, n, r, dtype)
    s = 1.25
    got = ops.fused_compose_mm(base, h, B, g, s, interpret=True,
                               block_m=32, block_n=128)
    want = ref.ref_compose_mm_fp64(base, h, B, g, s)
    np.testing.assert_allclose(np.asarray(got, np.float64),
                               np.asarray(want), **_tol(dtype))
    # headline equivalence metric (paper §5.9): cosine vs the fp64 oracle.
    gf = np.asarray(got, np.float64).ravel()
    wf = np.asarray(want).ravel()
    cos = gf @ wf / (np.linalg.norm(gf) * np.linalg.norm(wf))
    assert cos > 0.9999, cos


@pytest.mark.parametrize("dtype", DTYPES)
def test_mm_fwd_3d_input(dtype):
    base, h, B, g = _inputs(jax.random.PRNGKey(1), 4 * 33, 256, 7, dtype)
    base3 = base.reshape(4, 33, 256)
    h3 = h.reshape(4, 33, 7)
    got = ops.fused_compose_mm(base3, h3, B, g, 2.0, interpret=True,
                               block_m=32, block_n=128)
    want = ref.ref_compose_mm(base3, h3, B, g, 2.0)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("mag_grad", [True, False])
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", [(64, 512, 16), (37, 256, 11)])
def test_mm_grads_match_eager_autodiff(shape, dtype, mag_grad):
    """All three gradient families of the custom VJP == jax.grad through
    the eager (materialized-lora) form, incl. ragged rows/rank."""
    m, n, r = shape
    base, h, B, g = _inputs(jax.random.PRNGKey(2), m, n, r, dtype)
    s = 1.5

    def fused_loss(b, hh, bb, gg):
        out = ops.fused_compose_mm(b, hh, bb, gg, s, mag_grad=mag_grad,
                                   interpret=True, block_m=32, block_n=128)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def eager_loss(b, hh, bb, gg):
        out = ref.ref_compose_mm(b, hh, bb, gg, s)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    gf = jax.grad(fused_loss, argnums=(0, 1, 2, 3))(base, h, B, g)
    ge = jax.grad(eager_loss, argnums=(0, 1, 2, 3))(base, h, B, g)
    names = ("d_base", "d_h", "d_B", "d_g")
    for got, want, name in zip(gf, ge, names):
        if name == "d_g" and not mag_grad:
            assert np.all(np.asarray(got) == 0.0)
            continue
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            err_msg=name, **_tol(dtype))


def test_mm_grads_vs_fp64_oracle():
    """Gradients against analytic fp64 cotangents (loss = Σ delta²):
    tighter than the eager cross-check — catches a wrong-but-consistent
    pair of implementations."""
    m, n, r = 48, 384, 24
    base, h, B, g = _inputs(jax.random.PRNGKey(3), m, n, r, jnp.float32)
    s = 0.75

    def loss64(b, hh, bb, gg):
        out = ref.ref_compose_mm_fp64(b, hh, bb, gg, s)
        return jnp.sum(out ** 2)

    def loss_k(b, hh, bb, gg):
        out = ops.fused_compose_mm(b, hh, bb, gg, s, interpret=True,
                                   block_m=16, block_n=128)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g64 = jax.grad(loss64, argnums=(0, 1, 2, 3))(
        base.astype(jnp.float64), h.astype(jnp.float64),
        B.astype(jnp.float64), g.astype(jnp.float64))
    gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(base, h, B, g)
    for got, want, name in zip(gk, g64, ("d_base", "d_h", "d_B", "d_g")):
        scale = np.maximum(np.abs(np.asarray(want)), 1.0)
        err = np.abs(np.asarray(got, np.float64) - np.asarray(want)) / scale
        assert np.max(err) < 5e-5, (name, np.max(err))


@pytest.mark.parametrize("mode", ["interpret", "eager"])
def test_dora_linear_tier_equivalence(mode):
    """dora_linear through the matmul-fused plan == the mathematical
    definition — the same closed form TestDoraLinear checks for the other
    tiers (d_out=128 with rank 8 resolves matmul-fused under interpret;
    max rank pinned: at these tiny test rows the rows-aware bytes-model
    guard would otherwise route the small-M call to the materialized
    path)."""
    cfg = DoRAConfig(rank=8, alpha=16, mode=mode, mm_fused_max_rank=128)
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(42), 3)
    d_in, d_out = 96, 128
    x = jax.random.normal(k1, (4, 7, d_in), jnp.float32)
    W = jax.random.normal(k2, (d_out, d_in), jnp.float32)
    adapter = ad.init_dora_params(k3, W, cfg)
    adapter["B"] = 0.3 * jax.random.normal(k3, adapter["B"].shape)
    adapter["m"] = adapter["m"] * 1.01
    if mode == "interpret":
        plan = dp.plan_compose(cfg, training=True, rows=28, d_out=d_out,
                               rank=cfg.rank)
        assert plan.matmul_fused
    y = ad.dora_linear(x, W, adapter, cfg, training=True)
    comp = (W.astype(jnp.float64)
            + cfg.scaling * adapter["B"].astype(jnp.float64)
            @ adapter["A"].astype(jnp.float64))
    wn = jnp.linalg.norm(comp, axis=1)
    want = (adapter["m"].astype(jnp.float64) / wn
            * (x.astype(jnp.float64) @ comp.T))
    np.testing.assert_allclose(np.asarray(y, np.float64), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dora_linear_mm_grads_match_eager_tier():
    """Adapter gradients through the matmul-fused plan == eager tier
    (extends test_compose.test_eager_vs_fused_grads one fusion deeper)."""
    cfg_e = DoRAConfig(rank=8, alpha=16, mode="eager")
    cfg_f = DoRAConfig(rank=8, alpha=16, mode="interpret",
                       mm_fused_max_rank=128)  # small-M: keep mm route on
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(k1, (16, 128), jnp.float32)
    W = jax.random.normal(k2, (128, 128), jnp.float32)
    adapter = ad.init_dora_params(k3, W, cfg_e)
    adapter["B"] = 0.1 * jax.random.normal(k3, adapter["B"].shape)

    def loss(adp, cfg):
        return jnp.sum(ad.dora_linear(x, W, adp, cfg, training=True) ** 2)

    ge = jax.grad(loss)(adapter, cfg_e)
    gf = jax.grad(loss)(adapter, cfg_f)
    for name in ("A", "B", "m"):
        # The tiers reduce in different fp32 orders, so an entry's error
        # scales with the gradient's largest entry (~1e3 here), not with
        # the entry: atol is 1e-6 of that scale (~8 fp32 ulps).
        scale = float(np.max(np.abs(np.asarray(ge[name]))))
        np.testing.assert_allclose(
            np.asarray(ge[name]), np.asarray(gf[name]),
            rtol=1e-4, atol=max(1e-4, 1e-6 * scale), err_msg=name)


class TestDispatchFlag:
    @pytest.fixture(autouse=True)
    def _own_env(self, monkeypatch):
        monkeypatch.delenv("REPRO_FORCE_TIER", raising=False)
        monkeypatch.delenv("REPRO_DORA_MODE", raising=False)

    def test_flag_set_on_fused_tier(self):
        cfg = DoRAConfig(mode="interpret", rank=384)
        plan = dp.plan_compose(cfg, training=True, rows=4096, d_out=2048,
                               rank=384)
        assert plan.matmul_fused and plan.tier is dp.Tier.FUSED_BWD

    def test_rank_crossover_guard(self):
        cfg = DoRAConfig(mode="interpret")
        # 640 pads to 768 > mm_fused_max_rank=512: B-tile re-reads would
        # exceed the saved y_lora write+read.
        plan = dp.plan_compose(cfg, training=True, rows=4096, d_out=2048,
                               rank=640)
        assert plan.fused and not plan.matmul_fused
        # 384 pads to 384 ≤ 512: eligible.
        assert dp.mm_fused_eligible(384, cfg)
        assert not dp.mm_fused_eligible(None, cfg)

    def test_rows_aware_guard_decode_shaped(self):
        """Decode-shaped rows shrink the grid AND the profitable rank
        range (the B re-read stops amortizing — the committed 0.67x
        decode row of BENCH_compose.json): the bytes-model bound is
        priced at the block the call actually executes."""
        cfg = DoRAConfig(mode="interpret")
        # steady-state rows: bound 2*256 = 512, rank 64 (pads 128) fires
        assert dp.mm_fused_eligible(64, cfg, rows=4096)
        # decode rows=8: block shrinks to 8, bound 16 < 128 -> off
        assert not dp.mm_fused_eligible(64, cfg, rows=8)
        plan = dp.plan_compose(cfg, training=False, rows=8, d_out=4096,
                               rank=64)
        assert plan.fused and not plan.matmul_fused
        # an explicit pin overrides the bytes model (operator's call)
        cfg_pin = DoRAConfig(mode="interpret", mm_fused_max_rank=512)
        assert dp.mm_fused_eligible(64, cfg_pin, rows=8)

    def test_config_kill_switch(self):
        cfg = DoRAConfig(mode="interpret", compose_matmul_fused=False)
        plan = dp.plan_compose(cfg, training=True, rows=4096, d_out=2048,
                               rank=8)
        assert plan.fused and not plan.matmul_fused

    def test_never_on_eager_tier(self):
        cfg = DoRAConfig(mode="eager")
        plan = dp.plan_compose(cfg, training=True, rows=4096, d_out=2048,
                               rank=8)
        assert plan.tier is dp.Tier.EAGER and not plan.matmul_fused

    def test_bad_dout_raises_in_ops(self):
        base = jnp.zeros((8, 100), jnp.float32)
        h = jnp.zeros((8, 4), jnp.float32)
        B = jnp.zeros((100, 4), jnp.float32)
        with pytest.raises(ValueError, match="divisible by 128"):
            ops.fused_compose_mm(base, h, B, jnp.ones((100,)), 1.0,
                                 interpret=True)
