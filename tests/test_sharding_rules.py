"""Sharding rules: divisibility fallback, role tables, tree congruence.

Pure-logic tests use a duck-typed FakeMesh (pick_axes/spec_for only read
``axis_names`` and ``shape``); tree-structure tests use a real 1-device
debug mesh.
"""
from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P
import pytest

from repro.configs import get_config
from repro.core import DoRAConfig
from repro.launch import sharding as S
from repro.launch.mesh import make_debug_mesh
from repro.models import adapter_shapes, param_shapes


class FakeMesh:
    def __init__(self, **axes):
        self.axis_names = tuple(axes)
        self.shape = dict(axes)


PROD = FakeMesh(pod=2, data=16, model=16)
SINGLE = FakeMesh(data=16, model=16)


class TestPickAxes:
    def test_tp_divisible(self):
        assert S.pick_axes(4096, "tp", PROD, set()) == "model"

    def test_tp_not_divisible_replicates(self):
        assert S.pick_axes(40 * 64 + 8, "tp", PROD, set()) is None

    def test_fsdp_falls_back_progressively(self):
        # 60 % 32 != 0, 60 % 16 != 0, 60 % 2 == 0 -> pod only
        assert S.pick_axes(60, "expert", PROD, set()) == "pod"
        # 16 % 32 != 0, 16 % 16 == 0 -> data
        assert S.pick_axes(16, "expert", PROD, set()) == "data"
        # 64 divisible by 32 -> (pod, data)
        assert S.pick_axes(64, "expert", PROD, set()) == ("pod", "data")

    def test_used_axes_not_reused(self):
        used = {"model"}
        assert S.pick_axes(4096, "tp", PROD, used) is None

    def test_single_pod_mesh_drops_pod(self):
        assert S.pick_axes(60, "expert", SINGLE, set()) is None
        assert S.pick_axes(32, "expert", SINGLE, set()) == "data"


class TestSpecFor:
    def test_each_axis_used_once(self):
        spec = S.spec_for((64, 4096, 2048), ("expert", "tp", "fsdp"), PROD)
        assert spec == P(("pod", "data"), "model", None)

    def test_fallback_chain(self):
        # expert=60 takes pod; weight-fsdp is pod-only (H1.3) and pod is
        # taken -> D replicates
        spec = S.spec_for((60, 1408, 2048), ("expert", "tp", "fsdp"), PROD)
        assert spec == P("pod", "model", None)


class TestLeafRoles:
    def test_gqa_nontp_gets_gather_fsdp(self):
        # 28 heads / kv=4 — neither divides 16: d_out gather-FSDP (H2.2)
        mcfg = get_config("qwen2-7b")
        assert S.leaf_roles(mcfg, "wq", 2, PROD) == ("fsdp_gather", "repl")
        assert S.leaf_roles(mcfg, "wk", 2, PROD) == ("fsdp_gather", "repl")
        assert S.leaf_roles(mcfg, "wo", 2, PROD) == ("fsdp_gather", "repl")

    def test_heads_shard_when_divisible(self):
        mcfg = get_config("qwen3-32b")  # 64 heads, kv=8
        assert S.leaf_roles(mcfg, "wq", 2, PROD)[0] == "tp"
        assert S.leaf_roles(mcfg, "wk", 2, PROD)[0] == "fsdp_gather"
        assert S.leaf_roles(mcfg, "wo", 2, PROD) == ("fsdp", "tp")

    def test_moe_roles(self):
        mcfg = get_config("qwen2-moe-a2.7b")
        assert S.leaf_roles(mcfg, "gate", 3, PROD) == ("expert", "tp",
                                                       "fsdp")
        assert S.leaf_roles(mcfg, "down", 3, PROD) == ("expert", "fsdp",
                                                       "tp")

    def test_unknown_leaf_replicates(self):
        mcfg = get_config("qwen2-7b")
        assert S.leaf_roles(mcfg, "scale", 1, PROD) == ("repl",)


@pytest.mark.parametrize("arch", ["qwen3-32b", "jamba-v0.1-52b",
                                  "qwen2-moe-a2.7b", "falcon-mamba-7b",
                                  "jamba2-3b"])
def test_param_sharding_tree_matches_shapes(arch):
    mcfg = get_config(arch)
    mesh = make_debug_mesh(1, 1)
    shapes = param_shapes(mcfg)
    shardings = S.param_sharding(mcfg, mesh)
    assert (jax.tree.structure(shapes)
            == jax.tree.structure(shardings))
    # every spec rank matches its leaf rank
    for sds, sh in zip(jax.tree.leaves(shapes), jax.tree.leaves(shardings)):
        assert len(sh.spec) <= len(sds.shape)


@pytest.mark.parametrize("arch", ["qwen3-32b", "falcon-mamba-7b"])
def test_adapter_sharding_congruent(arch):
    mcfg = get_config(arch)
    dcfg = DoRAConfig(rank=384)
    mesh = make_debug_mesh(1, 1)
    shapes = adapter_shapes(mcfg, dcfg)
    shardings = S.adapter_sharding(mcfg, dcfg, mesh)
    assert (jax.tree.structure(shapes)
            == jax.tree.structure(shardings))


@pytest.mark.parametrize("leaf", ["dt_norm", "b_norm", "c_norm"])
def test_jamba_inner_norm_scales_are_replicated(leaf):
    """Jamba's dt/B/C norm scales span dt_rank or the state size, which no
    model axis splits: replicated on every mesh."""
    mcfg = get_config("jamba2-3b")
    assert mcfg.ssm_inner_norm
    for mesh in (PROD, SINGLE):
        assert S.leaf_roles(mcfg, leaf, 1, mesh) == ("repl",)


def test_adapter_tp_congruence_rules():
    """B row-sharded iff W out-sharded; A col-sharded iff W in-sharded —
    whatever axis W's dim takes, the adapter dim takes the same one. On
    this tp=1 mesh qwen3-32b crosses the per-chip budget, so its fsdp
    role resolves to 'fsdp_data' (H3.5) and the fsdp dims land on
    ``data`` rather than replicating."""
    mcfg = get_config("qwen3-32b")
    dcfg = DoRAConfig(rank=384)
    mesh = FakeMeshAsReal()
    sh = S.adapter_sharding(mcfg, dcfg, mesh)
    unit = sh["stack"]["l0"]
    # wq [q_dim, D]: out TP -> B/m model-sharded; A d_in congruent with
    # W's d_in (data-FSDP for this over-budget model on tp=1)
    wq_roles = S.leaf_roles(mcfg, "wq", 2, mesh)
    assert wq_roles == ("tp", "fsdp_data")
    assert unit["mixer"]["wq"]["B"].spec == P(None, "model", None)
    assert unit["mixer"]["wq"]["m"].spec == P(None, "model")
    assert unit["mixer"]["wq"]["A"].spec == P(None, None, "data")
    # w_down [D, ff]: in TP -> A col-sharded over model; B congruent with
    # W's d_out fsdp axis
    assert unit["ffn"]["w_down"]["A"].spec == P(None, None, "model")
    assert unit["ffn"]["w_down"]["B"].spec == P(None, "data", None)


def test_serving_state_sharding_congruent():
    """serving=True emits the frozen-adapter cache leaves: g shards like m
    (congruent with W's d_out) and the folded gsB row-shards exactly like
    the raw B — the broadcast-free decode compose must consume a
    correctly-sharded cached B, not all-gather it per token."""
    mcfg = get_config("qwen3-32b")
    dcfg = DoRAConfig(rank=384)
    mesh = FakeMeshAsReal()
    sh = S.adapter_sharding(mcfg, dcfg, mesh, serving=True)
    unit = sh["stack"]["l0"]
    for leaf in (unit["mixer"]["wq"], unit["ffn"]["w_down"]):
        assert leaf["g"].spec == leaf["m"].spec
        assert leaf["gsB"].spec == leaf["B"].spec
    # wq is TP out-sharded on this mesh: the cached B lands model-sharded
    assert unit["mixer"]["wq"]["gsB"].spec == P(None, "model", None)
    # default (serving=False) trees stay exactly as before
    raw = S.adapter_sharding(mcfg, dcfg, mesh)
    assert "g" not in raw["stack"]["l0"]["mixer"]["wq"]
    assert "gsB" not in raw["stack"]["l0"]["mixer"]["wq"]


def test_boundary_constraint_carries_compose_plan():
    """make_boundary_constraint attaches the ComposeSharding plan the
    adapted linears use to pin the rank-space LoRA intermediate."""
    from repro.core.sharding import as_compose_sharding
    mesh = FakeMeshAsReal()
    cst = S.make_boundary_constraint(mesh, batch=256, seq=4096)
    plan = as_compose_sharding(cst)
    assert plan is not None and plan.mesh is mesh
    assert plan.out_spec == S.activation_spec(mesh, batch=256, seq=4096)
    assert plan.h_spec == P(*(tuple(plan.out_spec)[:-1] + (None,)))


def test_adapter_pod_fsdp_on_multipod_mesh():
    mcfg = get_config("qwen3-32b")
    dcfg = DoRAConfig(rank=384)
    roles = S.leaf_roles(mcfg, "wq", 2, PROD)
    assert roles == ("tp", "fsdp")
    # wq d_in -> pod on the multi-pod FakeMesh
    assert S.spec_for((8192, 5120), roles, PROD) == P("model", "pod")


def FakeMeshAsReal():
    """A real (1,1) mesh named like production but sized 1 — divisibility
    always passes, so the chosen axes reflect the pure role logic."""
    from repro.compat.mesh import make_mesh
    return make_mesh((1, 1), ("data", "model"))


class TestBatchAndCache:
    def test_batch_sharded_when_divisible(self):
        assert S.batch_spec(PROD, batch=256) == P(("pod", "data"), None)
        assert S.batch_spec(SINGLE, batch=256) == P("data", None)

    def test_batch_replicated_when_indivisible(self):
        # long_500k global_batch=1 does not divide the 32-way dp axes
        assert S.batch_spec(PROD, batch=1) == P(None, None)
        assert S.batch_spec(SINGLE, batch=1) == P(None, None)

    def test_activation_spec_sequence_parallel(self):
        assert S.activation_spec(SINGLE, batch=256, seq=4096) \
            == P("data", "model", None)
        # decode: seq 1 cannot shard; batch 128 divides 16
        assert S.activation_spec(SINGLE, batch=128, seq=1) \
            == P("data", None, None)
        # odd seq cannot shard over model
        assert S.activation_spec(SINGLE, batch=128, seq=4095) \
            == P("data", None, None)

    def test_cache_kv_seq_sharded_over_model(self):
        mcfg = get_config("qwen3-32b")
        mesh = FakeMeshAsReal()
        c = S.cache_sharding(mcfg, mesh, batch=128)
        kv = c["stack"]["l0"]["k"]
        assert kv.spec == P(None, "data", "model", None, None)

    def test_cache_mamba_di_sharded(self):
        mcfg = get_config("falcon-mamba-7b")
        mesh = FakeMeshAsReal()
        c = S.cache_sharding(mcfg, mesh, batch=128)
        assert c["stack"]["l0"]["h"].spec == P(None, "data", "model", None)
