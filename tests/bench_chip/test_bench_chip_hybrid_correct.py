"""The hybrid cell's decision on ``correct`` at a tiny size (CPU, no chip):
a sound run of the program passes; the float8 control, the program
without Jamba's inner norms and the reference with its scan's state
restarted every 16 tokens fail the cell's limits. Each run drives the
whole ``train_hybrid`` driver past the harness's look for a chip."""
from __future__ import annotations

import time

# the shared file puts benchmarks/chip on the path
from test_bench_chip_hybrid import (LIMITS, SEED, TINY, TINY_LIMITS,
                                    _x64_off, driver, traffic)  # noqa: F401

import harness  # noqa: E402
import hybrid  # noqa: E402
import reference_hybrid as RH  # noqa: E402

TRAIN = traffic(seq=64, loss_tokens=32, optimizer=dict(
    traffic()["optimizer"], lr=2e-3))
BF16 = dict(TINY, torch_dtype="bfloat16",
            program=dict(TINY["program"], dora_mode="auto"))


def run_cell(limits, seconds=1.0):
    cell = harness.Cell(workload={"name": "tiny", "chips": 1}, config=BF16,
                        traffic=TRAIN, limits=limits, seed=SEED,
                        seconds=seconds, trace=False, peaks={},
                        t_start=time.perf_counter())
    return driver().run(cell)


def checks(out):
    return {c.name: c.value for c in out.checks}


def test_hybrid_sound_run_is_correct():
    out = run_cell(TINY_LIMITS)
    assert out.correct, checks(out)
    assert out.attempted > 0 and out.failed == 0
    assert out.context["flops_per_step"] == hybrid.train_flops_per_step(
        hybrid.dims(BF16), batch=1, seq=64, loss_tokens=32)


def test_hybrid_float8_control_is_not_correct():
    drv = driver()
    low = drv.reference_readings(BF16, TRAIN, SEED, quant="fp8")
    ref = drv.reference_readings(BF16, TRAIN, SEED)
    gaps = drv.compare(low, ref)
    assert any(gaps[k] > v for k, v in LIMITS.items()), gaps


def test_hybrid_without_the_inner_norms_is_not_correct(monkeypatch):
    """The planted fault: the program's mixer without Jamba's dt/B/C
    norms (plain Mamba's)."""
    import dataclasses
    drv = driver()
    make = drv.T.program.model_config
    monkeypatch.setattr(drv.T.program, "model_config", lambda cfg: (
        dataclasses.replace(make(cfg), ssm_inner_norm=False)))
    cell = harness.Cell(workload={"name": "tiny", "chips": 1}, config=BF16,
                        traffic=TRAIN, limits=LIMITS, seed=SEED,
                        seconds=1.0, trace=False, peaks={},
                        t_start=time.perf_counter())
    out = drv.run(cell)
    assert not out.correct, checks(out)


def test_hybrid_scan_state_not_carried_is_not_correct(monkeypatch):
    """The planted fault of ``control_hybrid.py``: the reference's state
    restarts from zero every 16 tokens."""
    import control_hybrid
    monkeypatch.setattr(control_hybrid, "RESET", 16)
    drv = driver()
    ref = drv.reference_readings(BF16, TRAIN, SEED)
    got = control_hybrid.scan_reset_readings(drv, BF16, TRAIN, SEED)
    assert drv.T.R is RH
    gaps = drv.compare(got, ref)
    assert any(gaps[k] > v for k, v in LIMITS.items()), gaps
