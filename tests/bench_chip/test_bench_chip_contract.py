"""BENCHMARK.json against the rules the benchmark is held to, discovery of
every cell's files by name, the result line, and the exits without a chip
or without the program (CPU, no chip)."""
from __future__ import annotations

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
CHIP = ROOT / "benchmarks" / "chip"
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
# Keys that name a width, which a cut may never change.
WIDTH = re.compile(r"(_size|_dim|_rank)$|intermediate|latent|expansion"
                   r"|experts_per_tok")
# Keys whose change is a cut to the chip's share of a deployment.
DEPTH = {"num_hidden_layers"}


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_configs_name_their_file_and_every_reduced_key():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        path = ROOT / c["file"]
        assert path.is_file() and path.is_relative_to(CHIP)
        cfg = json.loads(path.read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(c["reduced"]) <= set(cfg["published"])
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
            # any key that is not a cut is a departure of the program's,
            # named with its reason among the file's assumptions
            assert k in DEPTH or any(a.startswith(k + ":")
                                     for a in cfg["assumed"]), k
    assert len({c["file"] for c in BENCH["configs"]}) == len(BENCH["configs"])


def test_cells_find_their_files_by_name():
    configs = {c["name"] for c in BENCH["configs"]}
    used = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        traffic = harness.data_file("traffic", w["traffic"])
        assert (CHIP / "drivers" / f"{traffic['kind']}.py").is_file()
        assert harness.data_file("limits", w["name"])
        used.add(w["config"])
    assert used == configs
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    fours = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert fours <= max(1, len(BENCH["workloads"]) // 2)


def test_metrics_are_well_formed_and_have_readers():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        assert (CHIP / "metrics" / f"{m['name']}.py").is_file()
        assert set(m["workloads"]) <= cells
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", cells))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.add(m["layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    for w in cells:
        e, p = harness.cell_metrics(BENCH, w)
        assert "setup_s" in {m["name"] for m in e} and len(e) >= 2
        assert p, w


@pytest.mark.parametrize("kernel", ["compose_mm_fwd", "compose_mm_bwd",
                                    "paged_gather", "norm_terms",
                                    "assemble_norm"])
def test_every_kernel_has_a_cost_function_stating_its_bound(kernel):
    mod = harness.load_module("kernels", kernel)
    assert mod.BOUND in ("compute", "bandwidth")
    assert callable(mod.cost)


def test_kernel_costs_from_shapes():
    fwd = harness.load_module("kernels", "compose_mm_fwd").cost(
        [(2, (4096, 3584)), (2, (4096, 384)), (2, (3584, 384)),
         (4, (1, 3584))], [(2, (4096, 3584))])
    assert fwd["flops"] == 2 * 4096 * 3584 * 384 + 4 * 4096 * 3584
    assert fwd["bytes"] == 2 * (2 * 4096 * 3584 + 4096 * 384 + 3584 * 384) \
        + 4 * 3584
    gather = harness.load_module("kernels", "paged_gather").cost(
        [(4, (8, 128)), (2, (1024, 16, 512))], [(2, (8, 128, 16, 512))],
        [128, 0, 5, 0, 0, 0, 0, 0])
    # row 0 full; row 1 starts block 0's run; row 2 fetches 5 and block 0
    # again; rows 3-7 reuse it
    assert gather == {"flops": 0.0,
                      "bytes": 8 * 128 * 16 * 512 * 2 + 4 * 8 * 128
                      + (128 + 1 + 6) * 16 * 512 * 2}


@pytest.mark.parametrize("counts,fetched", [
    ([0, 0, 0], 1),            # one run of block 0 from the first step
    ([4, 4], 10),              # each row's blocks and its tail's block 0
    ([8, 0, 8], 17),           # full rows: no block 0 until row 1
    ([8, 8], 16)])
def test_paged_gather_fetches_each_change_of_block(counts, fetched):
    mod = harness.load_module("kernels", "paged_gather")
    assert mod.fetched_blocks(counts, 8) == fetched


def test_result_line_has_the_keys_and_the_checks_last():
    line = json.loads(harness.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1},
        checks=[harness.Check("widest_gap", 0.1, 0.5)],
        breakdown={"device_ops": [], "idle_gaps": []}))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "breakdown", "checks"]
    assert line["checks"] == {"widest_gap": {"value": 0.1, "limit": 0.5}}
    assert not harness.Check("x", float("nan"), 1.0).ok


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2 ** 33 + 1),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_result_without_a_chip():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "not 'tpu'" in p.stderr


def test_no_result_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in BENCH["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "program under test" in p.stderr


def test_host_watch_sees_the_process_held_and_the_collector():
    import gc
    import time
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1.0)      # the busy loop below keeps the GIL
    try:
        with harness.HostWatch() as watch:
            time.sleep(0.05)
            t = time.perf_counter()
            while time.perf_counter() - t < 0.3:
                pass
            gc.collect()
    finally:
        sys.setswitchinterval(switch)
    assert not watch._thread.is_alive()
    assert watch.longest[1] > 0.2 and len(watch.late) == 1
    assert any(g == 2 for g, _ in watch.collections)
    assert "1 wake-ups over 100 ms late" in watch.note()
