"""The benchmark's trace reduction with the serving engine's own profiler
spans (``engine.tick``, ``engine.fetch``, ...) among the host spans, on a
synthetic trace (CPU, no chip): every existing reader reads the same, and
an idle gap inside a tick is named by the innermost engine span."""
from __future__ import annotations

import json
import pathlib
import sys

import pytest

CHIP = pathlib.Path(__file__).resolve().parents[2] / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

from tracing import Event, TraceView  # noqa: E402

MS = 1_000_000
GATHER = dict(kernel="paged_gather",
              operands=((4, (8, 128)), (2, (1024, 16, 512))),
              results=((2, (8, 128, 16, 512)),))


def view(program_spans: bool) -> TraceView:
    """A 100 ms window and two engine ticks, each under the benchmark's
    ``engine.step``. The first (15-52 ms): a chunk at 16-20 ms, the decode
    run at 22-40 ms, its logits read back until 43 ms, then host sampling
    and delivery while the device idles until the second tick's decode
    at 60-70 ms. With ``program_spans`` the engine's spans of both ticks
    are in the trace too."""
    ops = [Event(16 * MS, 20 * MS, "fusion.1", "jit_prefill_chunk"),
           Event(22 * MS, 34 * MS, "fusion.2", "jit_decode_step"),
           Event(30 * MS, 40 * MS, "custom-call.3", "jit_decode_step",
                 **GATHER),
           Event(60 * MS, 70 * MS, "while.4", "jit_decode_step")]
    modules = [Event(16 * MS, 20 * MS, "jit_prefill_chunk(3)"),
               Event(22 * MS, 40 * MS, "jit_decode_step(12)"),
               Event(60 * MS, 70 * MS, "jit_decode_step(12)")]
    spans = [Event(0, 100 * MS, "window"),
             Event(0, 12 * MS, "submit"),
             Event(15 * MS, 52 * MS, "engine.step"),
             Event(55 * MS, 75 * MS, "engine.step")]
    if program_spans:
        spans += [Event(15 * MS + 10, 52 * MS - 10, "engine.tick"),
                  Event(15 * MS + 20, 16 * MS, "engine.chunk"),
                  Event(21 * MS + MS // 2, 22 * MS, "engine.decode"),
                  Event(22 * MS, 43 * MS, "engine.fetch"),
                  Event(43 * MS, 51 * MS, "engine.sample"),
                  Event(51 * MS, 51 * MS + MS // 2, "engine.deliver"),
                  Event(55 * MS + 10, 74 * MS, "engine.tick"),
                  Event(59 * MS, 60 * MS, "engine.decode"),
                  Event(60 * MS, 71 * MS, "engine.fetch"),
                  Event(71 * MS, 73 * MS, "engine.sample")]
    return TraceView(ops, modules, spans)


def ctx_for(v: TraceView) -> dict:
    import harness
    import model
    cfg = json.loads((CHIP / "configs" / "qwen2-7b-4L.json").read_text())
    traffic = json.loads((CHIP / "traffic" / "chat.json").read_text())
    ticks = [([3, 0, 0, 0, 0, 0, 0, 0], [4, 0, 2, 0, 0, 0, 0, 0]),
             ([4, 0, 2, 0, 0, 0, 0, 0], [4, 0, 2, 0, 0, 0, 0, 0])]
    return {"trace": v, "dims": model.dims(cfg), "traffic": traffic,
            "chips": 1, "ticks": ticks,
            "peaks": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e11},
            "kernel_cost": lambda k: harness.load_module("kernels", k)}


# The chat cell's per-layer readers that read no program span.
CHAT_READERS = ("decode_step_ms.chat", "mfu.decode.chat",
                "paged_gather_roofline", "idle_share.chat")


@pytest.mark.parametrize("name", CHAT_READERS)
def test_chat_readers_read_the_same_with_program_spans(name):
    import harness
    reader = harness.load_module("metrics", name)
    plain = reader.read(ctx_for(view(False)))
    assert plain is not None, name
    assert reader.read(ctx_for(view(True))) == plain


def test_busy_idle_and_top_ops_ignore_program_spans():
    plain, spanned = view(False), view(True)
    assert spanned.busy_s() == plain.busy_s()
    assert spanned.idle_share() == plain.idle_share()
    assert spanned.top_ops() == plain.top_ops()
    assert sorted(g for _, g in spanned.idle_gaps()) \
        == sorted(g for _, g in plain.idle_gaps())


def test_idle_gaps_inside_a_tick_are_named_by_the_engine_span():
    plain = dict((round(g * 1e3, 6), n) for n, g in view(False).idle_gaps())
    named = dict((round(g * 1e3, 6), n) for n, g in view(True).idle_gaps())
    # 40-60 ms: the first tick's read-back tail and host sampling, then
    # the wait for the second tick; its middle (50 ms) is under
    # engine.sample
    assert plain[20.0] == "engine.step"
    assert named[20.0] == "engine.sample"
    # 20-22 ms: between the chunk and the decode dispatch, under the tick
    assert plain[2.0] == "engine.step"
    assert named[2.0] == "engine.tick"
    # 0-16 ms and 70-100 ms lie outside every tick: named as before
    assert named[16.0] == plain[16.0] == "submit"
    assert named[30.0] == plain[30.0] == "none"
    assert len(named) == len(plain) == 4
