"""Roofline analyzer: HLO parsing, trip-count multipliers, dot flops,
collective traffic factors — validated against hand-built HLO snippets and
a real compiled module.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.roofline import HW, analyze_hlo_text, hw_for, model_flops, \
    roofline_terms
from repro.roofline.analysis import _shape_bytes_and_dims

HLO_DOT = """
ENTRY %main (p0: f32[8,16], p1: f32[32,16]) -> f32[8,32] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %p1 = f32[32,16]{1,0} parameter(1)
  ROOT %dot.1 = f32[8,32]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={1}
}
"""

HLO_WHILE = """
%body (param: (s32[], f32[8,16], f32[16,16])) -> (s32[], f32[8,16], f32[16,16]) {
  %param = (s32[], f32[8,16]{1,0}, f32[16,16]{1,0}) parameter(0)
  %gte0 = f32[8,16]{1,0} get-tuple-element(%param), index=1
  %gte1 = f32[16,16]{1,0} get-tuple-element(%param), index=2
  %dot.2 = f32[8,16]{1,0} dot(%gte0, %gte1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,16]{1,0}, f32[16,16]{1,0}) tuple(%gte0, %dot.2, %gte1)
}

%cond (param.1: (s32[], f32[8,16], f32[16,16])) -> pred[] {
  %param.1 = (s32[], f32[8,16]{1,0}, f32[16,16]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (arg: (s32[], f32[8,16], f32[16,16])) -> (s32[], f32[8,16], f32[16,16]) {
  %arg = (s32[], f32[8,16]{1,0}, f32[16,16]{1,0}) parameter(0)
  ROOT %while.1 = (s32[], f32[8,16]{1,0}, f32[16,16]{1,0}) while(%arg), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"12"}}
}
"""

HLO_COLLECTIVE = """
ENTRY %main (p: f32[128]) -> f32[128] {
  %p = f32[128]{0} parameter(0)
  %ar = f32[128]{0} all-reduce(%p), channel_id=1, replica_groups=[2,4]<=[8], to_apply=%add
  ROOT %ag = f32[512]{0} all-gather(%ar), channel_id=2, replica_groups=[2,4]<=[8], dimensions={0}
}
"""


def test_shape_parsing():
    assert _shape_bytes_and_dims("f32[8,16]{1,0}") == (512, [8, 16])
    assert _shape_bytes_and_dims("bf16[4]") == (8, [4])
    b, dims = _shape_bytes_and_dims("(s32[], f32[8,16], bf16[2,2])")
    assert b == 4 + 512 + 8
    assert dims == []  # first entry s32[] is scalar


def test_dot_flops_counted():
    ana = analyze_hlo_text(HLO_DOT)
    assert ana.flops == 2 * 8 * 32 * 16


def test_while_trip_count_multiplies():
    ana = analyze_hlo_text(HLO_WHILE)
    assert ana.flops == 12 * 2 * 8 * 16 * 16


def test_collective_traffic_factors():
    ana = analyze_hlo_text(HLO_COLLECTIVE)
    # all-reduce 512B x 2(n-1)/n with n=4 -> 768; all-gather shard 512B x
    # (n-1) = 1536
    assert ana.by_collective["all-reduce"] == pytest.approx(768.0)
    assert ana.by_collective["all-gather"] == pytest.approx(1536.0)
    assert ana.link_bytes == pytest.approx(768.0 + 1536.0)


def test_roofline_terms_dominance():
    ana = analyze_hlo_text(HLO_DOT)
    terms = roofline_terms(ana, HW(peak_flops=1.0, hbm_bw=1e30,
                                   link_bw=1e30, hbm_bytes=1.0))
    assert terms["dominant"] == "compute"
    assert terms["roofline_fraction"] == 1.0


def test_peaks_keyed_by_device_kind():
    """The peaks table is keyed by jax's device_kind; a kind it does not
    hold is an error, never a silent v5e default."""
    v5e = hw_for("TPU v5 lite")
    assert v5e.peak_flops == 197e12 and v5e.hbm_bw == 819e9
    with pytest.raises(ValueError, match="no peaks for device kind 'cpu'"):
        hw_for("cpu")


def test_model_flops_train_vs_serve():
    from repro.configs import get_config
    mcfg = get_config("qwen2-7b")
    t = model_flops(mcfg, tokens=100, kind="train")
    s = model_flops(mcfg, tokens=100, kind="serve")
    assert t == pytest.approx(3 * s)


def test_moe_active_params_used():
    from repro.configs import get_config
    moe = get_config("llama4-scout-17b-a16e")
    assert moe.count_active_params() < 0.45 * moe.count_params()


def test_against_real_compiled_module():
    """End-to-end: a jitted scan matmul must yield flops ~= trip x 2MNK
    (XLA's own cost_analysis misses the trip count; ours must not)."""
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), ()
        y, _ = jax.lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    ana = analyze_hlo_text(compiled.as_text())
    want = 5 * 2 * 8 * 64 * 64
    assert ana.flops == pytest.approx(want, rel=0.05)
