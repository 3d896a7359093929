"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Loads and warms up (set-up), measures for ``--seconds``, checks what the
timed path produced against the plain reference, and prints one JSON object
as the last line of standard output, with the numbers compared beside their
limits as the last lines of standard error. ``--trace 0`` reports the
cell's end-to-end metrics; ``--trace 1`` traces the window with the JAX
profiler and reports its per-layer metrics. Exits non-zero with no result
when the program is not in the checkout or the chips are not there.
"""
from __future__ import annotations

import argparse
import sys
import time

T_START = time.perf_counter()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    import harness as H
    src = H.ROOT / "src"
    if not (src / "repro").is_dir():
        H.log(f"benchmark: the program under test is not at {src}; "
              f"no result")
        return 2
    sys.path.insert(0, str(src))
    bench = H.load_benchmark()
    wl = {w["name"]: w for w in bench["workloads"]}.get(args.workload)
    if wl is None:
        H.log(f"benchmark: no workload {args.workload!r} in BENCHMARK.json")
        return 2
    cfg_entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    try:
        device = H.device_gate(wl["chips"])
    except H.NoDevice as e:
        H.log(str(e))
        return 3
    from peaks import peaks_for
    import model
    cell = H.Cell(workload=wl,
                  config=model.load_config_file(H.ROOT / cfg_entry["file"]),
                  traffic=H.data_file("traffic", wl["traffic"]),
                  limits=H.data_file("limits", wl["name"]),
                  seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), peaks=peaks_for(device["kind"]),
                  t_start=T_START)

    import jax
    from repro.launch import compile_cache
    H.log(f"compile cache: {compile_cache.enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    driver = H.load_module("drivers", cell.traffic["kind"])
    out = driver.run(cell)

    e2e, per_layer = H.cell_metrics(bench, wl["name"])
    metrics = {}
    breakdown = None
    if not cell.trace:
        for m in e2e:
            metrics[m["name"]] = {"value": out.e2e[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = dict(out.context, trace=out.trace, cell=cell,
                   peaks=cell.peaks, chips=wl["chips"],
                   kernel_cost=lambda k: H.load_module("kernels", k))
        for m in per_layer:
            value = H.load_module("metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device = dict(device, busy_s=out.trace.busy_s(),
                      window_s=out.trace.window_s)
        breakdown = out.trace.breakdown()
    device = dict(device, memory_peak_bytes=out.memory_peak_bytes)
    for note in out.notes:
        H.log(note)
    for c in out.checks:
        H.log(f"check {c.name}: {c.value!r} (limit {c.limit!r})"
              f"{'' if c.ok else '  FAILED'}")
    print(H.result_line(correct=out.correct, attempted=out.attempted,
                        failed=out.failed, metrics=metrics, device=device,
                        checks=out.checks, breakdown=breakdown), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
