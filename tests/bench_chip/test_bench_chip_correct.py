"""The benchmark's decision on ``correct``, at a size a test run can hold
(CPU, no chip): sound runs of the program pass, the float8 control fails,
and so does a run with the timed path broken underneath, once for each
fault the cells can have.

Each test drives a whole run of a driver (set-up, window, reference,
comparison) past the harness's look for a chip, with the cell's own limits.
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "benchmarks" / "chip"))

import harness  # noqa: E402

SEED = 2 ** 31 + 11
TINY = {"name": "tiny", "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 2, "vocab_size": 256, "rms_norm_eps": 1e-6,
        "rope_theta": 10000.0, "partial_rotary_factor": 0.75,
        "torch_dtype": "bfloat16", "qkv_bias": True,
        "dora": {"rank": 8, "alpha": 16.0, "rslora": True},
        "program": {"attn_chunk": 1024, "remat": "layer",
                    "dora_mode": "auto"}}



@pytest.fixture(autouse=True)
def _x64_off():
    """Other test modules turn on 64-bit types when they are imported; the
    benchmark runs with JAX's default 32-bit types."""
    import jax
    with jax.enable_x64(False):
        yield

def traffic(name, **kw):
    return dict(json.loads((ROOT / "benchmarks" / "chip" / "traffic"
                            / f"{name}.json").read_text()), **kw)


# A step of the rate the adapters' bf16 values can hold at d_in = 64.
TRAIN = traffic("train-4k", seq=64, loss_tokens=32, optimizer=dict(
    traffic("train-4k")["optimizer"], lr=2e-3))
CHAT = traffic("chat", rate=6.0,
               prompt={"median": 16, "sigma": 1.0, "min": 4, "max": 40},
               output={"median": 8, "sigma": 0.8, "min": 4, "max": 24},
               tenants=2, slots=4, max_len=64, block_size=8,
               prefill_chunk=16, warmup_prompts=[8, 40], warmup_tokens=2,
               check_requests=6)
TRAIN_LIMITS = json.loads((ROOT / "benchmarks" / "chip" / "limits" /
                           "train-4k.qwen2-7b-8L.json").read_text())
# A sound run at this size is held to limits of its own: bf16 rounding at
# d_model 64 and rank 8 reads about ten times what it does at the cells'
# widths (a grad_gap of 0.004-0.01 here, 0.0002-0.0012 there).
TINY_TRAIN_LIMITS = {"grad_gap": 0.05, "update_gap": 0.2}
CHAT_LIMITS = json.loads((ROOT / "benchmarks" / "chip" / "limits" /
                          "chat.qwen2-7b-4L.json").read_text())


def run_cell(kind, tr, limits, seconds=1.0):
    cell = harness.Cell(workload={"name": "tiny", "chips": 1}, config=TINY,
                        traffic=tr, limits=limits, seed=SEED,
                        seconds=seconds, trace=False, peaks={},
                        t_start=time.perf_counter())
    return harness.load_module("drivers", kind).run(cell)


def checks(out):
    return {c.name: c.value for c in out.checks}


def test_train_sound_run_is_correct():
    out = run_cell("train", TRAIN, TINY_TRAIN_LIMITS)
    assert out.correct, checks(out)
    assert out.attempted > 0 and out.failed == 0


def test_train_float8_control_is_not_correct():
    drv = harness.load_module("drivers", "train")
    low = drv.reference_readings(TINY, TRAIN, SEED, quant="fp8")
    ref = drv.reference_readings(TINY, TRAIN, SEED)
    gaps = drv.compare(low, ref)
    assert any(gaps[k] > v for k, v in TRAIN_LIMITS.items()), gaps


def test_train_step_returning_its_state_unchanged_is_not_correct(
        monkeypatch):
    import repro.launch.steps as steps
    make = steps.make_train_step

    def frozen(*a, **kw):
        step = make(*a, **kw)

        def unchanged(params, adapters, opt_state, batch):
            _, _, metrics = step(params, adapters, opt_state, batch)
            return adapters, opt_state, metrics
        return unchanged
    monkeypatch.setattr(steps, "make_train_step", frozen)
    out = run_cell("train", TRAIN, TRAIN_LIMITS)
    assert not out.correct
    assert checks(out)["update_gap"] == pytest.approx(1.0)


def test_train_loss_over_half_the_tokens_is_not_correct(monkeypatch):
    import repro.launch.steps as steps
    ce = steps.cross_entropy
    monkeypatch.setattr(steps, "cross_entropy", lambda logits, labels: ce(
        logits[:, ::2], labels[:, ::2]))
    out = run_cell("train", TRAIN, TRAIN_LIMITS)
    assert not out.correct, checks(out)


def test_chat_sound_run_is_correct():
    out = run_cell("chat", CHAT, CHAT_LIMITS, seconds=2.0)
    assert out.correct, checks(out)
    assert out.attempted == round(CHAT["rate"] * 2.0) and out.failed == 0
    assert np.isfinite(out.e2e["itl_p99_ms"]) and out.e2e["itl_p99_ms"] > 0


def test_chat_float8_control_is_not_correct():
    drv = harness.load_module("drivers", "chat")
    rng = np.random.default_rng(0)
    checked = [(t % 2, rng.integers(0, 256, 24, dtype=np.int32),
                rng.integers(0, 256, 32, dtype=np.int32)) for t in range(8)]
    served, low = drv.reference_gaps(TINY, CHAT, SEED, checked, quant="fp8")
    assert np.max(low) > CHAT_LIMITS["widest_gap"], np.max(low)


def test_chat_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from repro.launch.engine import DecodeEngine
    sample = DecodeEngine._sample_rows

    def altered(self, rows, keys):
        toks = sample(self, rows, keys)
        return [(t + 1) % self.mcfg.vocab_size for t in toks]
    monkeypatch.setattr(DecodeEngine, "_sample_rows", altered)
    out = run_cell("chat", CHAT, CHAT_LIMITS, seconds=2.0)
    assert not out.correct
    assert checks(out)["widest_gap"] > CHAT_LIMITS["widest_gap"]
