"""The benchmark's reduction from a profiler trace to per-layer metrics, on
a small synthetic trace (CPU, no chip)."""
from __future__ import annotations

import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import tracing  # noqa: E402
from tracing import Event, TraceView  # noqa: E402

MS = 1_000_000


def view():
    """A 100 ms window: device ops busy 10-40 and 50-70 ms (two of them
    overlapping), a decode module run, host spans around the idle gaps."""
    ops = [Event(10 * MS, 30 * MS, "fusion.1", "jit_decode_step"),
           Event(20 * MS, 40 * MS, "custom-call.2", "jit_decode_step",
                 kernel="paged_gather",
                 operands=((4, (8, 128)), (2, (1024, 16, 512))),
                 results=((2, (8, 128, 16, 512)),)),
           Event(50 * MS, 70 * MS, "fusion.3", "jit_prefill_chunk"),
           Event(150 * MS, 160 * MS, "fusion.4", "jit_x"),   # after window
           Event(50 * MS, 70 * MS, "while.9", "jit_prefill_chunk")]   # loop
    modules = [Event(10 * MS, 40 * MS, "jit_decode_step(12)"),
               Event(50 * MS, 70 * MS, "jit_prefill_chunk(3)")]
    spans = [Event(0, 100 * MS, "window"),
             Event(0, 12 * MS, "submit"),
             Event(15 * MS, 52 * MS, "engine.step"),
             Event(41 * MS, 49 * MS, "wait")]
    return TraceView(ops, modules, spans)


def test_union_merges_overlaps():
    assert tracing.union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4),
                                                               (5, 10)]


def test_busy_and_idle_share_are_the_union_inside_the_window():
    v = view()
    assert v.window_s == pytest.approx(0.1)
    assert v.busy_s() == pytest.approx(0.05)
    assert v.idle_share() == pytest.approx(0.5)


def test_idle_gaps_are_named_by_the_innermost_open_host_span():
    gaps = view().idle_gaps()
    assert gaps[0] == ("none", pytest.approx(0.03))      # 70-100 ms
    assert ("submit", pytest.approx(0.01)) in gaps        # 0-10 ms
    assert ("wait", pytest.approx(0.01)) in gaps          # 40-50, mid 45
    assert sum(g for _, g in gaps) == pytest.approx(0.05)


def test_kernel_time_and_module_runs():
    v = view()
    assert [e.dur for e in v.kernel_ops("paged_gather")] == [20 * MS]
    assert len(v.module_runs("decode_step")) == 1
    assert v.module_runs("prefill_chunk")[0].dur == 20 * MS
    top = dict(v.top_ops())
    assert top["paged_gather"] == pytest.approx(0.02)
    assert top["jit_decode_step:fusion"] == pytest.approx(0.02)
    assert "jit_x:fusion" not in top
    assert "jit_prefill_chunk:while" not in top


def test_breakdown_lists_at_most_ten_of_each():
    b = view().breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert all(len(b[k]) <= 10 for k in b)
    assert all(isinstance(n, str) and s > 0 for n, s in b["idle_gaps"])


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="window"):
        TraceView([], [], [Event(0, 1, "submit")])


def ctx_for(v, **kw):
    import harness
    import model
    import json
    cfg = json.loads((CHIP / "configs" / "qwen2-7b-4L.json").read_text())
    traffic = json.loads((CHIP / "traffic" / "chat.json").read_text())
    return dict({"trace": v, "dims": model.dims(cfg), "traffic": traffic,
                 "chips": 1, "peaks": {"bf16_flops": 1e12,
                                       "hbm_bytes_per_s": 1e11},
                 "kernel_cost": lambda k: harness.load_module("kernels", k)},
                **kw)


def read(name, ctx):
    import harness
    return harness.load_module("metrics", name).read(ctx)


# The slots' blocks before and after the one engine tick of view(): row 0
# grew a block, row 2 was admitted with two.
TICKS = [([3, 0, 0, 0, 0, 0, 0, 0], [4, 0, 2, 0, 0, 0, 0, 0])]
BLOCK = 16 * 512 * 2


def test_serving_readers_on_the_synthetic_trace():
    import model
    v = view()
    ctx = ctx_for(v, ticks=TICKS)
    assert read("decode_step_ms.chat", ctx) == pytest.approx(30.0)
    assert read("idle_share.chat", ctx) == pytest.approx(50.0)
    flops = model.decode_flops_per_step(ctx["dims"], rows=8)
    assert read("mfu.decode.chat", ctx) == pytest.approx(
        100 * flops / (0.03 * 1e12))
    # one gather call in the decode module: the table read, 8 rows x 128
    # blocks of 16 positions x 4 kv heads x 128 channels of bf16 written,
    # and the pool blocks fetched: row 0's 4 and a block 0 for its tail,
    # row 2's 2 and a block 0 (rows 1 and 3-7 reuse the block 0 before)
    gather = 8 * 128 * BLOCK + 4 * 8 * 128 + (5 + 3) * BLOCK
    assert read("paged_gather_roofline", ctx) == pytest.approx(
        100 * gather / 1e11 / 0.02)
    # no record of the ticks, or one that does not match the trace's
    assert read("paged_gather_roofline", ctx_for(v)) is None
    assert read("paged_gather_roofline", ctx_for(v, ticks=TICKS * 2)) is None


def test_gather_of_chunk_steps_reads_the_rows_their_chunks_grew():
    chunk = dict(kernel="paged_gather",
                 operands=((4, (1, 128)), (2, (1024, 16, 512))),
                 results=((2, (1, 128, 16, 512)),))
    ops = [Event(10 * MS, 11 * MS, "custom-call.1", "jit_chunk", **chunk),
           Event(20 * MS, 22 * MS, "custom-call.1", "jit_chunk", **chunk)]
    modules = [Event(9 * MS, 12 * MS, "jit_chunk"),
               Event(19 * MS, 23 * MS, "jit_chunk")]
    spans = [Event(0, 100 * MS, "window"), Event(5 * MS, 30 * MS,
                                                 "engine.step")]
    ticks = [([64, 10, 0, 0], [127, 11, 30, 0])]     # grew 63, 1, 30
    ctx = ctx_for(TraceView(ops, modules, spans), ticks=ticks)
    # the first chunk step's row grew most (127 blocks), the second's next
    # (30); each fetches its blocks and a block 0 for its tail
    moved = [128 * BLOCK + 4 * 128 + (n + 1) * BLOCK for n in (127, 30)]
    assert read("paged_gather_roofline", ctx) == pytest.approx(
        100 * sum(moved) / 1e11 / 0.003)


def test_readers_return_nothing_when_nothing_is_traced():
    empty = TraceView([], [], [Event(0, MS, "window")])
    ctx = ctx_for(empty)
    for name in ("decode_step_ms.chat", "mfu.decode.chat",
                 "paged_gather_roofline", "compose_mm_fwd_roofline",
                 "compose_mm_bwd_roofline"):
        assert read(name, ctx) is None, name


def test_trace_ops_name_their_kernel_and_shapes():
    text = (
        '%closed_call.7 = bf16[4096,3584]{1,0:T(8,128)(2,1)} custom-call('
        'bf16[4096,3584]{1,0:T(8,128)(2,1)} %a, bf16[4096,384]{1,0} %b, '
        'bf16[3584,384]{1,0} %c, f32[1,3584]{1,0} %d), '
        'custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={bf16[4096,3584]{1,0}, '
        'bf16[4096,384]{1,0}, bf16[3584,384]{1,0}, f32[1,3584]{1,0}}, '
        'frontend_attributes={kernel_metadata={\n"kernel":'
        '"compose_mm_fwd_pallas"\n}}')
    op = tracing.parse_op(text)
    assert (op.name, op.kernel) == ("closed_call.7", "compose_mm_fwd_pallas")
    assert op.operands == ((2, (4096, 3584)), (2, (4096, 384)),
                           (2, (3584, 384)), (4, (1, 3584)))
    assert op.results == ((2, (4096, 3584)),)
    plain = tracing.parse_op("%fusion.12 = bf16[8,128]{1,0} fusion(%p)")
    assert (plain.name, plain.kernel) == ("fusion.12", "")
    import dataclasses
    ops = [dataclasses.replace(op, start=10 * MS, end=12 * MS),
           dataclasses.replace(op, start=20 * MS, end=22 * MS)]
    v = TraceView(ops, [], [Event(0, 100 * MS, "window")])
    ctx = ctx_for(v, peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9})
    import harness
    cost = harness.load_module("kernels", "compose_mm_fwd").cost(
        list(op.operands), list(op.results))
    least = max(cost["flops"] / 197e12, cost["bytes"] / 819e9)
    assert read("compose_mm_fwd_roofline", ctx) == pytest.approx(
        100 * least / 0.002)
