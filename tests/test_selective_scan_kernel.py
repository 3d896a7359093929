"""Pallas selective-scan kernel vs the numpy recurrence oracle
(interpret mode; shape/dtype sweep per the kernel-test requirement)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.selective_scan import selective_scan_pallas

_F32 = jnp.float32


def _inputs(key, B, S, di, n):
    ks = jax.random.split(key, 5)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, di), _F32))
    xi = jax.random.normal(ks[1], (B, S, di), _F32)
    Bm = jax.random.normal(ks[2], (B, S, n), _F32)
    Cm = jax.random.normal(ks[3], (B, S, n), _F32)
    A = -jnp.exp(0.5 * jax.random.normal(ks[4], (di, n), _F32))
    h0 = jax.random.normal(jax.random.fold_in(key, 9), (B, di, n), _F32)
    return dt, xi, Bm, Cm, A, h0


def _reference(dt, xi, Bm, Cm, A, h0):
    h = np.asarray(h0, np.float64)
    a_all = np.exp(np.asarray(dt)[..., None] * np.asarray(A))
    b_all = (np.asarray(dt) * np.asarray(xi))[..., None] \
        * np.asarray(Bm)[:, :, None, :]
    ys = []
    for t in range(dt.shape[1]):
        h = a_all[:, t] * h + b_all[:, t]
        ys.append(np.einsum("bdn,bn->bd", h, np.asarray(Cm)[:, t]))
    return np.stack(ys, axis=1), h


@pytest.mark.parametrize("B,S,di,n,block_di,chunk", [
    (1, 8, 128, 4, 128, 4),
    (2, 16, 256, 16, 128, 8),   # di tiled 2x
    (1, 32, 128, 8, 128, 32),   # single chunk
    (2, 12, 128, 16, 128, 4),   # 3 chunks
    (1, 256, 1024, 16, 512, 128),    # 2 chunks of 16 groups x 2 tiles
    (1, 256, 2048, 16, 1024, 128),   # the same at the default tile width
])
def test_kernel_matches_reference(B, S, di, n, block_di, chunk):
    dt, xi, Bm, Cm, A, h0 = _inputs(jax.random.PRNGKey(0), B, S, di, n)
    y, h_t = selective_scan_pallas(
        dt, dt * xi, Bm, Cm, jnp.transpose(A),
        jnp.transpose(h0, (0, 2, 1)),
        block_di=block_di, chunk=chunk, interpret=True)
    y_ref, h_ref = _reference(dt, xi, Bm, Cm, A, h0)
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h_t).transpose(0, 2, 1), h_ref,
                               rtol=2e-5, atol=2e-5)


def test_kernel_carries_state_across_chunks():
    """The VMEM scratch must carry h between sequential chunk steps —
    compare one 4-chunk kernel call against four chained 1-chunk calls."""
    B, S, di, n = 1, 16, 128, 4
    dt, xi, Bm, Cm, A, h0 = _inputs(jax.random.PRNGKey(1), B, S, di, n)
    A_t = jnp.transpose(A)
    h0_t = jnp.transpose(h0, (0, 2, 1))
    y_full, h_full = selective_scan_pallas(
        dt, dt * xi, Bm, Cm, A_t, h0_t, block_di=128, chunk=4,
        interpret=True)
    h = h0_t
    ys = []
    for c in range(4):
        sl = slice(4 * c, 4 * (c + 1))
        y_c, h = selective_scan_pallas(
            dt[:, sl], (dt * xi)[:, sl], Bm[:, sl], Cm[:, sl], A_t, h,
            block_di=128, chunk=4, interpret=True)
        ys.append(y_c)
    np.testing.assert_allclose(np.asarray(y_full),
                               np.asarray(jnp.concatenate(ys, axis=1)),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_full), np.asarray(h),
                               rtol=1e-6, atol=1e-6)


def test_kernel_matches_fused_chunk_xla():
    """Kernel and the XLA fused_chunk path are the same schedule."""
    from repro.models.mamba import _ssm_scan_fused
    B, S, di, n = 2, 24, 128, 16
    dt, xi, Bm, Cm, A, h0 = _inputs(jax.random.PRNGKey(2), B, S, di, n)
    y_x, h_x = _ssm_scan_fused(dt, dt * xi, Bm, Cm, A, h0, 8)
    y_k, h_k = selective_scan_pallas(
        dt, dt * xi, Bm, Cm, jnp.transpose(A),
        jnp.transpose(h0, (0, 2, 1)), block_di=128, chunk=8,
        interpret=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_x),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h_k).transpose(0, 2, 1),
                               np.asarray(h_x), rtol=2e-5, atol=2e-5)


def _sequential(dt, dtx, Bm, Cm, A, h0):
    """Token-by-token float32 recurrence in the model's layout, for
    ``jax.grad``."""
    def step(h, xs):
        dt_t, u_t, b_t, c_t = xs
        h = jnp.exp(dt_t[..., None] * A) * h + u_t[..., None] \
            * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (dt, dtx, Bm, Cm))
    h_f, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), h_f


@pytest.mark.parametrize("B,S,di,chunk,block_di", [
    pytest.param(1, 32, 256, 8, 128, id="1-32-256-8"),   # 4 chunks, 2 tiles
    pytest.param(2, 20, 128, 8, 128,      # S padded to 24 with no-op tokens
                 id="2-20-128-8"),
    pytest.param(1, 12, 128, 4, 128,      # chunks shorter than a group
                 id="1-12-128-4"),
    pytest.param(1, 256, 1024, 128, 512,  # 2 chunks of 16 groups, 2 tiles
                 id="1-256-1024-128"),
    pytest.param(1, 256, 2048, 128, 1024,  # the same at the default tile
                 id="1-256-2048-128"),     # width
])
def test_scan_vjp_matches_grad_of_sequential_scan(B, S, di, chunk, block_di):
    """Forward and every cotangent of the custom-VJP kernel pair (dt, dtx,
    B, C, A, h0) against ``jax.grad`` of the sequential scan, with a
    nonzero h0 and cotangents on both y and h_final."""
    from repro.kernels.selective_scan import selective_scan
    n = 16
    dt, xi, Bm, Cm, A, h0 = _inputs(jax.random.PRNGKey(3), B, S, di, n)
    dtx = dt * xi
    k = jax.random.PRNGKey(4)
    wy = jax.random.normal(k, (B, S, di), _F32)
    wh = jax.random.normal(jax.random.fold_in(k, 1), (B, di, n), _F32)

    def loss(fn):
        def f(*args):
            y, h_f = fn(*args)
            return jnp.sum(y * wy) + jnp.sum(h_f * wh)
        return f

    kern = lambda *a: selective_scan(*a, chunk=chunk, block_di=block_di,
                                     interpret=True)
    args = (dt, dtx, Bm, Cm, A, h0)
    (y_k, h_k), (y_r, h_r) = kern(*args), _sequential(*args)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r),
                               rtol=2e-5, atol=2e-5)
    g_k = jax.grad(loss(kern), argnums=range(6))(*args)
    g_r = jax.grad(loss(_sequential), argnums=range(6))(*args)
    for name, a, b in zip(("dt", "dtx", "B", "C", "A", "h0"), g_k, g_r):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5 * scale,
                                   err_msg=name)


def test_backward_kernel_writes_per_tile_partials_of_b_and_c():
    """d_inner tiles of the backward add up: one 256-wide tile and two
    128-wide tiles give the same dB and dC."""
    from repro.kernels.selective_scan import (selective_scan_bwd_pallas,
                                              selective_scan_pallas)
    B, S, di, n, chunk = 1, 16, 256, 8, 8
    dt, xi, Bm, Cm, A, h0 = _inputs(jax.random.PRNGKey(5), B, S, di, n)
    args = (dt, dt * xi, Bm, Cm, A.T, jnp.swapaxes(h0, 1, 2))
    _, _, hs = selective_scan_pallas(*args, chunk=chunk, save_states=True,
                                     interpret=True)
    dy = jax.random.normal(jax.random.PRNGKey(6), (B, S, di), _F32)
    dh = jnp.zeros((B, n, di), _F32)
    one, two = (selective_scan_bwd_pallas(*args[:5], hs, dy, dh,
                                          block_di=bd, chunk=chunk,
                                          interpret=True)
                for bd in (256, 128))
    for a, b in zip(one, two):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


def test_saved_states_are_the_state_at_each_chunk_start():
    from repro.kernels.selective_scan import selective_scan_pallas
    B, S, di, n, chunk = 1, 12, 128, 4, 4
    dt, xi, Bm, Cm, A, h0 = _inputs(jax.random.PRNGKey(7), B, S, di, n)
    _, _, hs = selective_scan_pallas(
        dt, dt * xi, Bm, Cm, A.T, jnp.swapaxes(h0, 1, 2), chunk=chunk,
        save_states=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(hs[:, 0]),
                                  np.asarray(jnp.swapaxes(h0, 1, 2)))
    for c in range(1, S // chunk):
        _, h = _reference(dt[:, :c * chunk], xi[:, :c * chunk],
                          Bm[:, :c * chunk], Cm[:, :c * chunk], A, h0)
        np.testing.assert_allclose(np.asarray(hs[:, c]).transpose(0, 2, 1),
                                   h, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tier,fused", [("interpret", True),
                                        ("eager", False)])
def test_mamba_block_takes_the_kernel_pair_on_a_fused_tier(tier, fused,
                                                           monkeypatch):
    """The mixer's scan goes through the Pallas pair where the dispatch
    picks a fused tier and through the XLA scan otherwise; values and
    gradients agree."""
    import dataclasses
    import repro.models.mamba as M
    from repro.configs import get_config
    from repro.core import DoRAConfig
    from repro.core.dispatch import plan_scan
    from repro.models import init_params
    for var in ("REPRO_FORCE_TIER", "REPRO_DORA_MODE", "REPRO_DORA_FUSED"):
        monkeypatch.delenv(var, raising=False)
    mcfg = dataclasses.replace(get_config("jamba2-3b", smoke=True),
                               d_model=64)
    assert mcfg.d_inner == 128
    dcfg = DoRAConfig(rank=4, force_tier=tier)
    assert plan_scan(dcfg, d_inner=mcfg.d_inner).fused is fused
    called = []
    real = M.selective_scan
    monkeypatch.setattr(M, "selective_scan",
                        lambda *a, **kw: called.append(1) or real(*a, **kw))
    params = init_params(jax.random.PRNGKey(0), mcfg)
    p = jax.tree.map(lambda t: t[0],
                     params["stack"]["l0"])["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 24, 64), _F32)

    def out(dcfg):
        return jax.value_and_grad(lambda x: jnp.sum(jnp.sin(
            M.mamba_block(x, p, None, mcfg, dcfg)[0])))(x)
    v, g = out(dcfg)
    assert bool(called) is fused
    v0, g0 = out(DoRAConfig(rank=4, force_tier="eager"))
    np.testing.assert_allclose(float(v), float(v0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g0), rtol=1e-4,
                               atol=1e-5)


def test_training_core_in_blocks_equals_one_pass(monkeypatch):
    """Training over more than SEQ_BLOCK tokens runs the mixer's core in
    checkpointed blocks that carry the state and the conv's inputs: the
    output and the input gradient equal one pass over the sequence."""
    import dataclasses
    import repro.models.mamba as M
    from repro.configs import get_config
    from repro.models import init_params
    mcfg = dataclasses.replace(get_config("jamba2-3b", smoke=True),
                               d_model=64)
    params = init_params(jax.random.PRNGKey(0), mcfg)
    p = jax.tree.map(lambda t: t[0], params["stack"]["l0"])["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 64), jnp.float32)

    def run():
        return jax.value_and_grad(lambda x: jnp.sum(jnp.sin(
            M.mamba_block(x, p, None, mcfg, None)[0])))(x)
    v_one, g_one = run()
    monkeypatch.setattr(M, "SEQ_BLOCK", 8)
    called = []
    real = M._core_in_blocks
    monkeypatch.setattr(M, "_core_in_blocks",
                        lambda *a: called.append(1) or real(*a))
    v_blk, g_blk = run()
    assert called
    np.testing.assert_allclose(float(v_blk), float(v_one), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_blk), np.asarray(g_one),
                               rtol=1e-4, atol=1e-5)
