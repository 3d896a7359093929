"""SPMD-aware matmul-fused compose: plan logic, trivial-mesh equivalence,
and sharded-vs-unsharded parity on forced multi-device CPU meshes.

The tentpole contract (ROADMAP open item #1, closed): sharded call sites
constrain the rank-space intermediate ``h`` instead of a materialized
``y_lora``, so the matmul-fused kernel keeps firing under SPMD — the
forward is shard-local (shard_map with block specs derived from the mesh
axis sizes) and the jaxpr contains no ``[M, d_out]`` y_lora dot anywhere.

Multi-device tests run in a subprocess: the
``--xla_force_host_platform_device_count`` XLA flag must be set before jax
initializes, and must not leak into this (CPU-pinned, 1-device) process.
Inside the subprocess:

  - the matmul-fused route is selected for a row-sharded d_out layer and
    the outputs (served logits, cached g) are BITWISE the unsharded
    reference's in fp32 — block shapes are pinned so both programs tile
    identically, and the serving state is precomputed once so both
    consume the same g (recomputing the norm under different GSPMD
    partitionings moves single ulps — that path is asserted allclose);
  - the jaxpr dot_general census: exactly ONE full-width dot (y_base)
    on the fused route, TWO (y_base + materialized y_lora) with the
    fusion disabled;
  - the full VJP (d_base / d_h→d_A / d_B / d_g with cross-shard psums)
    matches the fp64 eager oracle.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import repro.core.adapter as ad
import repro.core.dispatch as dp
from repro.compat.mesh import make_mesh
from repro.core import DoRAConfig, init_dora_params
from repro.core.sharding import (ComposeSharding, as_compose_sharding,
                                 plan_for_output)
from repro.kernels import dora_compose as ck

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class FakeMesh:
    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


MESH = FakeMesh(data=8, model=4)


# ---------------------------------------------------------------------------
# Plan derivation logic (pure, FakeMesh).
# ---------------------------------------------------------------------------

class TestComposeSharding:
    def test_sp_plan_derivations(self):
        """Sequence-parallel output: rows sharded, d_out replicated."""
        plan = ComposeSharding(MESH, P("data", "model", None))
        assert plan.row_axes == ("data", "model")
        assert plan.dout_axes == ()
        assert plan.dout_shards == 1 and plan.row_shards == 32
        assert plan.h_spec == P("data", "model", None)
        assert plan.b_spec == P(None, None)
        assert plan.vec_spec == P(None)
        assert plan.flat2d() == (("data", "model"), None)

    def test_tp_plan_derivations(self):
        """Row-sharded d_out: B/g congruent, h rank-replicated."""
        plan = ComposeSharding(MESH, P("data", None, "model"))
        assert plan.row_axes == ("data",)
        assert plan.dout_axes == ("model",)
        assert plan.dout_shards == 4
        assert plan.h_spec == P("data", None, None)
        assert plan.b_spec == P("model", None)
        assert plan.vec_spec == P("model")
        assert plan.flat2d() == ("data", "model")
        assert plan.local_dout(512) == 128

    def test_kernel_expressible(self):
        plan = ComposeSharding(MESH, P(None, None, "model"))
        assert plan.kernel_expressible(512)       # 512/4 = 128 ✓
        assert not plan.kernel_expressible(256)   # 256/4 = 64 < 128 lanes
        assert not plan.kernel_expressible(300)   # does not divide 4
        sp = ComposeSharding(MESH, P("data", "model", None))
        assert sp.kernel_expressible(128)         # unsharded d_out: global

    def test_as_compose_sharding(self):
        plan = ComposeSharding(MESH, P(None, "model"))
        assert as_compose_sharding(plan) is plan
        fn = lambda x: x  # noqa: E731
        assert as_compose_sharding(fn) is None
        fn.plan = plan
        assert as_compose_sharding(fn) is plan
        assert as_compose_sharding(None) is None

    def test_tuple_entry_axes(self):
        plan = ComposeSharding(MESH, P(("data", "model"), None))
        assert plan.row_shards == 32 and plan.flat2d() == (
            ("data", "model"), None)


class TestBDoutAxes:
    """The ROADMAP ``b_spec`` gap: a B whose d_out is FSDP-sharded beyond
    the output's feature axes. Declared axes widen b_spec/vec_spec, make
    the shard-local kernel inexpressible (clean materialized fallback),
    and fused_compose_mm refuses such a plan loudly."""

    def test_b_spec_widened(self):
        plan = ComposeSharding(MESH, P(None, None, "model"),
                               b_dout_axes=("data",))
        assert plan.b_spec == P(("model", "data"), None)
        assert plan.vec_spec == P(("model", "data"))
        # output-side derivations are untouched
        assert plan.dout_axes == ("model",)
        assert plan.h_spec == P(None, None, None)

    def test_b_spec_unchanged_without_declaration(self):
        plan = ComposeSharding(MESH, P(None, None, "model"))
        assert plan.b_spec == P("model", None)

    def test_congruent_axes_dedup(self):
        """b_dout_axes already carried by the output d_out are harmless
        (no double-naming, still kernel-expressible)."""
        plan = ComposeSharding(MESH, P(None, None, "model"),
                               b_dout_axes=("model",))
        assert plan.b_spec == P("model", None)
        assert plan.kernel_expressible(512)

    def test_extra_axes_break_kernel_expressibility(self):
        plan = ComposeSharding(MESH, P(None, None, "model"),
                               b_dout_axes=("data",))
        assert not plan.kernel_expressible(512)

    def test_dispatch_falls_back_cleanly(self):
        cfg = DoRAConfig(mode="interpret", rank=8)
        plan = ComposeSharding(MESH, P(None, "model"),
                               b_dout_axes=("data",))
        kp = dp.plan_compose(cfg, training=True, rows=4096, d_out=512,
                             rank=8, sharding=plan)
        assert kp.tier is dp.Tier.EAGER and kp.sharding is None

    def test_fused_compose_mm_refuses_plan_naming_spec(self):
        from repro.kernels import ops
        plan = ComposeSharding(MESH, P(None, "model"),
                               b_dout_axes=("data",))
        base = jnp.zeros((8, 512), jnp.float32)
        h = jnp.zeros((8, 8), jnp.float32)
        B = jnp.zeros((512, 8), jnp.float32)
        g = jnp.ones((512,), jnp.float32)
        with pytest.raises(ValueError) as ei:
            ops.fused_compose_mm(base, h, B, g, 2.0, interpret=True,
                                 sharding=plan)
        assert "b_spec" in str(ei.value) and "data" in str(ei.value)

    def test_plan_for_output_threads_axes(self):
        from repro.core.sharding import plan_for_output
        plan = plan_for_output(MESH, P(None, "model"),
                               b_dout_axes=("data",))
        assert plan.b_dout_axes == ("data",)
        assert hash(plan) == hash(plan)   # still lru-cache keyable

    def test_row_parallel_b_axes_derivation(self):
        from repro.launch import sharding as LS
        mcfg = __import__("repro.configs", fromlist=["get_config"]) \
            .get_config("qwen2-7b", smoke=True)
        # no FSDP axes on the debug mesh (fsdp prefers the absent 'pod',
        # and size-1 axes are dropped): the plan stays unchanged
        assert LS.row_parallel_b_axes(mcfg, FakeMesh(data=1, model=1)) == ()
        assert LS.row_parallel_b_axes(mcfg, FakeMesh(data=8, model=4)) == ()
        # a multi-pod mesh FSDP-shards d_model over pod (wo and w_down
        # agree: heads divide model=4, so wo keeps the plain fsdp role)
        pod_mesh = FakeMesh(pod=2, data=8, model=4)
        if mcfg.d_model % 2 == 0:
            assert LS.row_parallel_b_axes(mcfg, pod_mesh) == ("pod",)
        # heads do NOT divide model=3: wo degrades to fsdp_gather
        # (('pod','data')) while w_down stays fsdp (('pod',)) — the one
        # shared plan cannot declare both, so the declaration is dropped
        # rather than pinning either weight to a WRONG layout
        assert LS.row_parallel_b_axes(
            mcfg, FakeMesh(pod=2, data=8, model=3)) == ()

    def test_gsb_path_constrains_b_on_trivial_mesh(self):
        """The folded-gsB serving path applies constrain_b under a
        declared-FSDP plan; on a trivial mesh values are bitwise."""
        from repro.compat.mesh import make_mesh
        from repro.core import precompute_adapter_state
        from repro.core.sharding import plan_for_output
        cfg = DoRAConfig(rank=8, alpha=16, mode="eager")
        key = jax.random.PRNGKey(3)
        W = jax.random.normal(key, (128, 64))
        x = jax.random.normal(jax.random.fold_in(key, 1), (4, 64))
        adp = init_dora_params(jax.random.fold_in(key, 2), W, cfg)
        adp["B"] = 0.2 * jax.random.normal(jax.random.fold_in(key, 3),
                                           adp["B"].shape)
        folded = precompute_adapter_state(W, adp, cfg, fold_gsb=True)
        mesh = make_mesh((1, 1), ("data", "model"))
        plan = plan_for_output(mesh, P(None, "model"),
                               b_dout_axes=("data",))
        y_c = jax.jit(lambda x: ad.dora_linear(
            x, W, folded, cfg, training=False, constrain=plan))(x)
        y_n = jax.jit(lambda x: ad.dora_linear(
            x, W, folded, cfg, training=False))(x)
        np.testing.assert_array_equal(np.asarray(y_c), np.asarray(y_n))


class TestDispatchWithSharding:
    @pytest.fixture(autouse=True)
    def _own_env(self, monkeypatch):
        monkeypatch.delenv("REPRO_FORCE_TIER", raising=False)
        monkeypatch.delenv("REPRO_DORA_MODE", raising=False)

    def test_expressible_plan_rides_kernel_plan(self):
        cfg = DoRAConfig(mode="interpret", rank=8)
        plan = ComposeSharding(MESH, P(None, "model"))
        kp = dp.plan_compose(cfg, training=True, rows=4096, d_out=512,
                             rank=8, sharding=plan)
        assert kp.matmul_fused and kp.sharding is plan

    def test_inexpressible_plan_falls_back_to_eager(self):
        cfg = DoRAConfig(mode="interpret", rank=8)
        plan = ComposeSharding(MESH, P(None, "model"))
        kp = dp.plan_compose(cfg, training=True, rows=4096, d_out=256,
                             rank=8, sharding=plan)   # 256/4 = 64 lanes
        assert kp.tier is dp.Tier.EAGER and kp.sharding is None

    def test_plan_dropped_when_not_mm_fused(self):
        cfg = DoRAConfig(mode="interpret", compose_matmul_fused=False)
        plan = ComposeSharding(MESH, P(None, "model"))
        kp = dp.plan_compose(cfg, training=True, rows=4096, d_out=512,
                             rank=8, sharding=plan)
        assert kp.fused and not kp.matmul_fused and kp.sharding is None

    def test_indivisible_rows_fall_back_to_eager(self):
        """Rows that do not divide the plan's row axes cannot run
        shard-local; the plan is inexpressible and dispatch drops cleanly
        to the constrained materialized path instead of silently running
        a global kernel on sharded operands."""
        cfg = DoRAConfig(mode="interpret", rank=8)
        plan = ComposeSharding(MESH, P(("data", "model"), None))  # 32-way
        kp = dp.plan_compose(cfg, training=True, rows=4104, d_out=512,
                             rank=8, sharding=plan)   # 4104 % 32 != 0
        assert kp.tier is dp.Tier.EAGER and kp.sharding is None
        kp = dp.plan_compose(cfg, training=True, rows=4096, d_out=512,
                             rank=8, sharding=plan)   # 4096 % 32 == 0
        assert kp.matmul_fused and kp.sharding is plan


class TestConfigBlockKnobs:
    def test_mm_block_rows_defaults_to_block_rows(self):
        assert DoRAConfig().resolve_mm_block_rows() == 256
        assert DoRAConfig(block_rows=128).resolve_mm_block_rows() == 128
        assert DoRAConfig(mm_block_rows=64).resolve_mm_block_rows() == 64

    def test_decode_shaped_grid_shrinks(self):
        cfg = DoRAConfig()
        assert cfg.resolve_mm_block_rows(rows=2) == 8    # sublane floor
        assert cfg.resolve_mm_block_rows(rows=21) == 24  # round up to 8
        assert cfg.resolve_mm_block_rows(rows=4096) == 256

    def test_max_rank_derived_from_configured_block(self):
        assert DoRAConfig().resolve_mm_fused_max_rank() == 512
        assert DoRAConfig(block_rows=128).resolve_mm_fused_max_rank() == 256
        # mm_block_rows overrides block_rows in the derivation
        assert DoRAConfig(block_rows=128, mm_block_rows=256) \
            .resolve_mm_fused_max_rank() == 512
        # explicit pin outranks both
        assert DoRAConfig(mm_block_rows=64, mm_fused_max_rank=384) \
            .resolve_mm_fused_max_rank() == 384

    def test_mm_block_rows_validated(self):
        with pytest.raises(ValueError, match="mm_block_rows"):
            DoRAConfig(mm_block_rows=0)


class TestLocalBlockShape:
    def test_sharded_blocks_derive_from_local_shard(self):
        bm, bn = ck.local_block_shape(4096, 1024, dout_shards=4,
                                      block_m=256, block_n=1024)
        assert (bm, bn) == (256, 256)   # n_local = 256
        bm, bn = ck.local_block_shape(64, 512, row_shards=4, dout_shards=2,
                                      block_m=256, block_n=1024)
        assert (bm, bn) == (16, 256)    # m_local = 16, n_local = 256

    def test_lane_violation_raises(self):
        with pytest.raises(ValueError, match="128-lane"):
            ck.local_block_shape(64, 256, dout_shards=4)


# ---------------------------------------------------------------------------
# Trivial one-device mesh: the unsharded path IS the plan's instance.
# ---------------------------------------------------------------------------

class TestTrivialMesh:
    # max rank pinned: the rows-aware bytes-model guard would otherwise
    # route these deliberately tiny shapes to the materialized path.
    CFG = DoRAConfig(rank=8, alpha=16, mode="interpret",
                     mm_fused_max_rank=128)

    def _layer(self, d_in=96, d_out=256, rows=(4, 8)):
        k1, k2, k3 = jax.random.split(jax.random.PRNGKey(11), 3)
        x = jax.random.normal(k1, rows + (d_in,), jnp.float32)
        W = jax.random.normal(k2, (d_out, d_in), jnp.float32)
        adp = init_dora_params(k3, W, self.CFG)
        adp["B"] = 0.3 * jax.random.normal(k3, adp["B"].shape)
        return x, W, adp

    def test_one_device_plan_is_bitwise_the_unsharded_path(self):
        """A plan on a 1-device mesh must change nothing: same kernels,
        same tiles, bitwise-identical output and gradients."""
        x, W, adp = self._layer()
        mesh = make_mesh((1,), ("model",))
        plan = plan_for_output(mesh, P(None, None, "model"))
        kp = dp.plan_compose(self.CFG, training=True, rows=32, d_out=256,
                             rank=8, sharding=plan)
        assert kp.matmul_fused and kp.sharding is plan

        def f(c):
            return jax.jit(lambda x: ad.dora_linear(
                x, W, adp, self.CFG, training=True, constrain=c))(x)

        np.testing.assert_array_equal(np.asarray(f(plan)),
                                      np.asarray(f(None)))

        def make_loss(c):
            def loss(a):
                return jnp.sum(ad.dora_linear(
                    x, W, a, self.CFG, training=True, constrain=c) ** 2)
            return loss

        g_p = jax.jit(jax.grad(make_loss(plan)))(adp)
        g_n = jax.jit(jax.grad(make_loss(None)))(adp)
        for k in ("A", "B", "m"):
            np.testing.assert_allclose(
                np.asarray(g_p[k]), np.asarray(g_n[k]), rtol=1e-6,
                atol=1e-6, err_msg=k)

    def test_stacked_forwards_constrain(self):
        """dora_linear_stacked threads the plan into every slice."""
        mesh = make_mesh((1,), ("model",))
        plan = plan_for_output(mesh, P(None, "model"))
        key = jax.random.PRNGKey(5)
        W = jax.random.normal(key, (3, 128, 64))
        x = jax.random.normal(jax.random.fold_in(key, 1), (3, 16, 64))
        adp = init_dora_params(jax.random.fold_in(key, 2), W, self.CFG)
        y_p = ad.dora_linear_stacked(x, W, adp, self.CFG, constrain=plan)
        y_n = ad.dora_linear_stacked(x, W, adp, self.CFG)
        np.testing.assert_array_equal(np.asarray(y_p), np.asarray(y_n))

    def test_bare_callable_still_constrains_h_not_ylora(self):
        """A plain row-constraint callable (no .plan) routes through the
        factored path too — y_lora is never materialized just to be
        pinned (the deleted special case stays deleted)."""
        x, W, adp = self._layer()
        calls = []

        def cfn(t):
            calls.append(t.shape)
            return t

        y = ad.dora_linear(x, W, adp, self.CFG, training=True,
                           constrain=cfn)
        y_ref = ad.dora_linear(x, W, adp, self.CFG, training=True)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
        # constrained tensors: y_base [4,8,256] and the RANK-space h
        # [4,8,8] — never a [4,8,256] y_lora (y_base is the only full-width
        # constrained tensor).
        assert (4, 8, 8) in calls
        assert calls.count((4, 8, 256)) == 1


# ---------------------------------------------------------------------------
# Forced multi-device meshes (subprocess; 2- and 4-device).
# ---------------------------------------------------------------------------

def _run_subprocess(code: str, devices: int):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = SRC
    env.pop("REPRO_FORCE_TIER", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=900)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nERR:\n{out.stderr}"
    return out.stdout


_SPMD_PARITY = """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    import repro.core.adapter as ad
    import repro.core.dispatch as dp
    from repro.compat.mesh import make_mesh
    from repro.core import DoRAConfig, init_dora_params, \\
        precompute_adapter_state
    from repro.kernels import ops, ref

    NDEV = {ndev}
    assert jax.device_count() == NDEV
    mesh = make_mesh((NDEV,), ("model",))
    d_in, d_out, rank = 96, 512, 8
    rows = (4, 8)
    M = 32
    # Pin the tile shapes so the sharded and unsharded programs tile
    # identically (block_n = the smallest local shard's width, block_m
    # = the smallest local row count): bitwise parity is then exact.
    # (mm_fused_max_rank pinned: the tiny block_m would otherwise derive
    # a sub-128 rank bound and disable the fusion we are testing.)
    cfg = DoRAConfig(rank=rank, alpha=16, mode="interpret",
                     block_cols=512 // NDEV, mm_block_rows=8,
                     mm_fused_max_rank=512)

    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k1, rows + (d_in,), jnp.float32)
    W = jax.random.normal(k2, (d_out, d_in), jnp.float32)
    adp = init_dora_params(k3, W, cfg)
    adp["B"] = 0.3 * jax.random.normal(k3, adp["B"].shape)
    served = precompute_adapter_state(W, adp, cfg, act_dtype=jnp.float32)

    tp_plan = dp.ComposeSharding(mesh, P(None, None, "model"))
    sp_plan = dp.ComposeSharding(mesh, P(None, "model", None))

    # 1. the matmul-fused route is selected for the row-sharded d_out layer
    kp = dp.plan_compose(cfg, training=False, rows=M, d_out=d_out,
                         rank=rank, sharding=tp_plan)
    assert kp.matmul_fused and kp.sharding is tp_plan, kp
    assert kp.tier is dp.Tier.FUSED_FWD

    # 2. served logits: bitwise vs the unsharded reference, both layouts
    def logits(adapters, plan):
        return jax.jit(lambda x: ad.dora_linear(
            x, W, adapters, cfg, training=False, constrain=plan))(x)

    # Parity to fp32 rounding, not bitwise: XLA's CPU dot sums the
    # d_out-sharded base matmul x@Wᵀ in another order than the unsharded
    # one (docs/numerics.md, caveat 4). 8 ulps of the largest logit.
    y_ref = logits(served, None)
    tol = 8 * np.finfo(np.float32).eps * float(jnp.max(jnp.abs(y_ref)))
    for name, plan in (("tp", tp_plan), ("sp", sp_plan)):
        y = logits(served, plan)
        err = float(jnp.max(jnp.abs(y - y_ref)))
        assert err <= tol, (name, err, tol)
    print("LOGITS_OK")

    # 3. training path (norm recomputed under GSPMD): tight allclose
    def train_out(plan):
        return jax.jit(lambda x: ad.dora_linear(
            x, W, adp, cfg, training=True, constrain=plan))(x)

    y_train = np.asarray(train_out(None))
    np.testing.assert_allclose(np.asarray(train_out(tp_plan)), y_train,
                               rtol=2e-6,
                               atol=8 * np.finfo(np.float32).eps
                               * float(np.max(np.abs(y_train))))
    print("TRAIN_ALLCLOSE_OK")

    # 4. jaxpr census: exactly ONE full-width dot_general (y_base) on the
    #    fused route; TWO (y_base + materialized y_lora) with fusion off.
    def count_full_dots(fn, *args):
        count = 0
        def walk(jaxpr):
            nonlocal count
            for eq in jaxpr.eqns:
                if eq.primitive.name == "dot_general":
                    for v in eq.outvars:
                        if tuple(v.aval.shape) in ((M, d_out),
                                                   rows + (d_out,)):
                            count += 1
                for sub in eq.params.values():
                    subs = sub if isinstance(sub, (list, tuple)) else [sub]
                    for s2 in subs:
                        if hasattr(s2, "jaxpr"):
                            walk(s2.jaxpr)
        walk(jax.make_jaxpr(fn)(*args).jaxpr)
        return count

    n_fused = count_full_dots(lambda x: ad.dora_linear(
        x, W, served, cfg, training=False, constrain=tp_plan), x)
    cfg_off = DoRAConfig(rank=rank, alpha=16, mode="interpret",
                         compose_matmul_fused=False)
    n_off = count_full_dots(lambda x: ad.dora_linear(
        x, W, served, cfg_off, training=False, constrain=tp_plan), x)
    assert n_fused == 1 and n_off == 2, (n_fused, n_off)
    print("JAXPR_OK")

    # 5. sharded VJP vs the fp64 eager oracle (all four cotangents,
    #    including the cross-shard psums of d_h / d_B / d_g).
    jax.config.update("jax_enable_x64", True)
    base = jax.random.normal(jax.random.fold_in(k1, 1), (M, d_out),
                             jnp.float32)
    h = 0.3 * jax.random.normal(jax.random.fold_in(k1, 2), (M, rank),
                                jnp.float32)
    B = 0.3 * jax.random.normal(jax.random.fold_in(k1, 3), (d_out, rank),
                                jnp.float32)
    g = 1.0 + 0.0015 * jax.random.normal(jax.random.fold_in(k1, 4),
                                         (d_out,), jnp.float32)
    plan2d = dp.ComposeSharding(mesh, P(None, "model"))
    s = 1.25

    def loss_k(b, hh, bb, gg):
        out = ops.fused_compose_mm(b, hh, bb, gg, s, interpret=True,
                                   block_m=8, block_n=128,
                                   sharding=plan2d)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss64(b, hh, bb, gg):
        return jnp.sum(ref.ref_compose_mm_fp64(b, hh, bb, gg, s) ** 2)

    gk = jax.jit(jax.grad(loss_k, argnums=(0, 1, 2, 3)))(base, h, B, g)
    g64 = jax.grad(loss64, argnums=(0, 1, 2, 3))(
        base.astype(jnp.float64), h.astype(jnp.float64),
        B.astype(jnp.float64), g.astype(jnp.float64))
    for got, want, name in zip(gk, g64, ("d_base", "d_h", "d_B", "d_g")):
        scale = np.maximum(np.abs(np.asarray(want)), 1.0)
        err = np.abs(np.asarray(got, np.float64) - np.asarray(want)) / scale
        assert np.max(err) < 5e-5, (name, np.max(err))
    print("VJP_OK")
"""


@pytest.mark.slow
@pytest.mark.parametrize("ndev", [2, 4])
def test_spmd_matmul_fused_parity(ndev):
    """Acceptance: forced {2,4}-device CPU mesh — matmul-fused route
    selected for a row-sharded d_out layer, fp32 logits parity to 8 ulps
    (both TP and SP layouts), no y_lora in the jaxpr, VJP vs fp64."""
    out = _run_subprocess(_SPMD_PARITY.format(ndev=ndev), ndev)
    for marker in ("LOGITS_OK", "TRAIN_ALLCLOSE_OK", "JAXPR_OK", "VJP_OK"):
        assert marker in out, out
