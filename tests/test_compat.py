"""The compat layer itself: tree-path round-trips, compiler-params
construction, probes, and forced-tier dispatch selection. These tests
guard the guarantee every other module relies on: each drifting JAX API
is named in one place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.compat import mesh as cmesh
from repro.compat import pallas as cpal
from repro.compat import probes
from repro.compat import tree as ctree
from repro.core import DoRAConfig, dispatch


# ---------------------------------------------------------------------------
# tree
# ---------------------------------------------------------------------------

TREE = {"stack": {"l0": {"A": 1, "B": [2, 3]}}, "m": 4}


def test_flatten_with_path_round_trip():
    flat, treedef = ctree.flatten_with_path(TREE)
    rebuilt = ctree.unflatten(treedef, [leaf for _, leaf in flat])
    assert rebuilt == TREE


def test_paths_match_plain_flatten_order():
    flat, treedef = ctree.flatten_with_path(TREE)
    plain, plain_def = ctree.flatten(TREE)
    assert [leaf for _, leaf in flat] == plain
    assert treedef == plain_def


def test_path_str_names():
    flat, _ = ctree.flatten_with_path(TREE)
    names = [ctree.path_str(p) for p, _ in flat]
    assert "stack/l0/A" in names
    assert "stack/l0/B/0" in names
    assert "m" in names


def test_map_matches_jax_tree_map():
    got = ctree.map(lambda x: x * 10, TREE)
    want = jax.tree_util.tree_map(lambda x: x * 10, TREE)
    assert got == want


def test_flatten_with_path_honors_is_leaf():
    spec = {"a": ("linear", (4, 2)), "b": {"c": ("zeros", (3,))}}
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], str)
    flat, _ = ctree.flatten_with_path(spec, is_leaf=is_leaf)
    assert sorted(ctree.path_str(p) for p, _ in flat) == ["a", "b/c"]
    assert all(isinstance(leaf, tuple) for _, leaf in flat)


# ---------------------------------------------------------------------------
# pallas compiler params
# ---------------------------------------------------------------------------

def test_compiler_params_constructs_on_installed_jax():
    """The kernels' compiler params construct on the installed JAX and
    pallas_call accepts them (interpret mode, CPU)."""
    params = cpal.pltpu.CompilerParams(dimension_semantics=("parallel",))

    def kern(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    x = jnp.ones((8, 128), jnp.float32)
    out = cpal.pl.pallas_call(
        kern,
        grid=(1,),
        in_specs=[cpal.pl.BlockSpec((8, 128), lambda i: (0, 0))],
        out_specs=cpal.pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        compiler_params=params,
        interpret=True,
    )(x)
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_resolve_interpret_follows_backend():
    assert cpal.resolve_interpret(True) is True
    assert cpal.resolve_interpret(False) is False
    assert cpal.resolve_interpret(None) == (not probes.is_tpu())


# ---------------------------------------------------------------------------
# mesh
# ---------------------------------------------------------------------------

def test_make_mesh_single_device():
    mesh = cmesh.make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] == 1


def test_shard_map_resolves():
    assert callable(cmesh.shard_map)


# ---------------------------------------------------------------------------
# xla introspection
# ---------------------------------------------------------------------------

def test_peak_memory_and_cost_dict_on_installed_jax():
    from repro.compat import xla as cxla
    compiled = jax.jit(lambda x: x @ x).lower(
        jnp.ones((64, 64), jnp.float32)).compile()
    assert cxla.peak_memory_bytes(compiled) >= 0
    cost = cxla.cost_analysis_dict(compiled)
    assert isinstance(cost, dict)
    assert cost.get("flops", 0.0) > 0


# ---------------------------------------------------------------------------
# probes
# ---------------------------------------------------------------------------

def test_probes_consistent():
    assert probes.backend_platform() in ("cpu", "gpu", "tpu")
    if probes.backend_platform() != "tpu":
        assert not probes.is_tpu()
        assert "tpu" not in dispatch.available_backends()
    assert "eager" in dispatch.available_backends()
    assert "interpret" in dispatch.available_backends()


# ---------------------------------------------------------------------------
# forced-tier dispatch
# ---------------------------------------------------------------------------

def _plan(cfg, d_out=256, rows=1 << 20, training=True):
    return dispatch.plan_compose(cfg, training=training, rows=rows,
                                 d_out=d_out)


def test_force_tier_env_interpret(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_TIER", "interpret")
    plan = _plan(DoRAConfig(mode="auto"))
    assert plan.tier is dispatch.Tier.FUSED_BWD
    assert plan.backend == "interpret"
    assert plan.interpret is True


def test_force_tier_env_eager(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_TIER", "eager")
    plan = _plan(DoRAConfig(mode="fused"))
    assert plan.tier is dispatch.Tier.EAGER
    assert plan.interpret is False


def test_force_tier_env_beats_config_field(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_TIER", "eager")
    plan = _plan(DoRAConfig(force_tier="interpret"))
    assert plan.tier is dispatch.Tier.EAGER


def test_force_tier_config_field(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_TIER", raising=False)
    plan = _plan(DoRAConfig(force_tier="interpret"))
    assert plan.backend == "interpret"
    assert plan.interpret is True


def test_force_tier_tpu_raises_off_tpu(monkeypatch):
    """A forced compiled-kernel tier never runs the interpreter in its
    place: off a TPU it raises, naming why the tier is unavailable."""
    monkeypatch.delenv("REPRO_FORCE_TIER", raising=False)
    monkeypatch.setattr(probes, "backend_platform", lambda: "cpu")
    with pytest.raises(RuntimeError, match="backend is 'cpu', not 'tpu'"):
        _plan(DoRAConfig(force_tier="tpu"))
    with pytest.raises(RuntimeError, match="forced but is unavailable"):
        _plan(DoRAConfig(mode="fused"))


def test_force_tier_rejects_unknown_env(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_TIER", "warpdrive")
    with pytest.raises(ValueError, match="REPRO_FORCE_TIER"):
        _plan(DoRAConfig())


def test_dora_mode_env_validated_and_aliased(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_TIER", raising=False)
    monkeypatch.setenv("REPRO_DORA_MODE", "tpu")   # tier alias accepted
    assert DoRAConfig().resolve_mode() == "fused"
    monkeypatch.setenv("REPRO_DORA_MODE", "auto")
    assert DoRAConfig(mode="eager").resolve_mode() == "auto"
    monkeypatch.setenv("REPRO_DORA_MODE", "warpdrive")
    with pytest.raises(ValueError, match="REPRO_DORA_MODE"):
        DoRAConfig().resolve_mode()


def test_force_tier_rejects_unknown_config():
    with pytest.raises(ValueError, match="force_tier"):
        DoRAConfig(force_tier="warpdrive")


def test_shape_guard_beats_forced_fused(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_TIER", "interpret")
    plan = _plan(DoRAConfig(), d_out=100)  # not a multiple of 128
    assert plan.tier is dispatch.Tier.EAGER


def test_inference_gets_forward_tier(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_TIER", "interpret")
    plan = _plan(DoRAConfig(), training=False)
    assert plan.tier is dispatch.Tier.FUSED_FWD


def test_auto_mode_on_cpu_is_eager(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_TIER", raising=False)
    if probes.is_tpu():
        pytest.skip("auto on TPU picks the fused tier")
    plan = _plan(DoRAConfig(mode="auto"))
    assert plan.tier is dispatch.Tier.EAGER


def test_norm_plan_matches_compose_backend(monkeypatch):
    monkeypatch.setenv("REPRO_FORCE_TIER", "interpret")
    plan = dispatch.plan_norm(DoRAConfig(), d_out=256)
    assert plan.tier is dispatch.Tier.FUSED_FWD
    assert plan.interpret is True
    assert dispatch.plan_norm(DoRAConfig(), d_out=100).tier \
        is dispatch.Tier.EAGER


# ---------------------------------------------------------------------------
# end-to-end: forced interpret tier ≡ eager tier on CPU (acceptance)
# ---------------------------------------------------------------------------

def test_forced_interpret_matches_eager_end_to_end(monkeypatch, rng_key):
    from repro.core import dora_linear, init_dora_params
    cfg = DoRAConfig(rank=8, alpha=16.0)
    W = jax.random.normal(rng_key, (256, 128), jnp.float32)
    adapter = init_dora_params(jax.random.fold_in(rng_key, 1), W, cfg)
    adapter["B"] = 0.02 * jax.random.normal(
        jax.random.fold_in(rng_key, 2), adapter["B"].shape, jnp.float32)
    x = jax.random.normal(jax.random.fold_in(rng_key, 3), (4, 128),
                          jnp.float32)

    monkeypatch.setenv("REPRO_FORCE_TIER", "interpret")
    y_interp = dora_linear(x, W, adapter, cfg, training=True)
    monkeypatch.setenv("REPRO_FORCE_TIER", "eager")
    y_eager = dora_linear(x, W, adapter, cfg, training=True)
    np.testing.assert_allclose(np.asarray(y_interp), np.asarray(y_eager),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# compiled kernels under a multi-device mesh
# ---------------------------------------------------------------------------

def test_compiled_kernels_leave_partitioned_programs(monkeypatch):
    """XLA cannot partition a Mosaic kernel: traced under a mesh of more
    than one device, the compiled tier routes the norm, the gather and an
    un-sharded compose to eager. One device, or the interpreter (plain
    jnp), keeps the kernels."""
    monkeypatch.delenv("REPRO_FORCE_TIER", raising=False)
    monkeypatch.setattr(probes, "backend_platform", lambda: "tpu")
    cfg = DoRAConfig(mode="fused")

    def plans(c):
        return (dispatch.plan_norm(c, d_out=256),
                dispatch.plan_gather(c, head_elems=512),
                _plan(c, rows=4096, training=False))

    assert all(p.fused and p.backend == "tpu" for p in plans(cfg))
    four = jax.sharding.AbstractMesh((4,), ("model",))
    with jax.sharding.use_abstract_mesh(four):
        assert all(p.tier is dispatch.Tier.EAGER for p in plans(cfg))
        interp = plans(DoRAConfig(mode="interpret"))
        assert all(p.fused and p.interpret for p in interp)
    one = jax.sharding.AbstractMesh((1,), ("model",))
    with jax.sharding.use_abstract_mesh(one):
        assert all(p.fused and p.backend == "tpu" for p in plans(cfg))
