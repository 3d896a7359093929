"""Readings that the limits of ``limits/<workload>.json`` are set from.

    python3 benchmarks/chip/control.py --workload <name> --seeds 1 2 ... \\
        [--control-seeds 1 2 3] [--seconds 15] [--out <file.json>]

For each seed of ``--seeds``, the numbers a run of the cell compares, read
from the program at the cell's own sizes (the lower readings); for each
seed of ``--control-seeds``, the same numbers with the reference computed
in float8 in the program's place (the upper readings). Fine-tuning cells
need no window; serving cells serve ``--seconds`` of their traffic per
seed. All seeds run in one process. The benchmark's own runs never run
this. Prints one JSON object per reading and writes them all to ``--out``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[],
                    help="seeds on which to read the cell's planted fault "
                         "(train: loss over half the tokens; chat: a served "
                         "token altered)")
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--sweep", type=float, nargs="*", default=[],
                    help="serving cells: offered rates (requests/s) to "
                         "serve --seconds each, after the readings")
    ap.add_argument("--out")
    args = ap.parse_args()

    import harness as H
    sys.path.insert(0, str(H.ROOT / "src"))
    bench = H.load_benchmark()
    wl = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg_file = {c["name"]: c for c in bench["configs"]}[wl["config"]]["file"]
    H.device_gate(wl["chips"])
    import jax
    import model
    from repro.launch import compile_cache
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg = model.load_config_file(H.ROOT / cfg_file)
    traffic = H.data_file("traffic", wl["traffic"])
    driver = H.load_module("drivers", traffic["kind"])
    read = train_readings if traffic["kind"] == "train" else chat_readings

    out = []
    for seed in args.seeds:
        out.append(read(driver, cfg, traffic, seed, None, args.seconds))
        print(json.dumps(out[-1]), flush=True)
    for seed in args.control_seeds:
        out.append(read(driver, cfg, traffic, seed, "fp8", args.seconds))
        print(json.dumps(out[-1]), flush=True)
    for seed in args.fault_seeds:
        out.append(read(driver, cfg, traffic, seed, "fault", args.seconds))
        print(json.dumps(out[-1]), flush=True)
    for rate in args.sweep:
        out.append(sweep_point(driver, cfg, traffic, args.seeds[0], rate,
                               args.seconds))
        print(json.dumps(out[-1]), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


def train_readings(driver, cfg, traffic, seed, side, seconds) -> dict:
    t = time.perf_counter()
    if side is None:
        trainer = driver.Trainer(cfg, traffic, seed)
        got = trainer.first_steps()
        trainer.free()
        del trainer
    elif side == "fault":
        got = driver.reference_readings(cfg, traffic, seed,
                                        fault="half_tokens")
    else:
        got = driver.reference_readings(cfg, traffic, seed, quant=side)
    ref = driver.reference_readings(cfg, traffic, seed)
    gaps = driver.compare(got, ref)
    return {"seed": seed, "side": side or "program", **gaps,
            "loss": got["loss"], "ref_loss": ref["loss"],
            "grad": got["grad"].tolist(), "ref_grad": ref["grad"].tolist(),
            "delta": got["delta"].tolist(),
            "ref_delta": ref["delta"].tolist(),
            "seconds": time.perf_counter() - t}


def chat_readings(driver, cfg, traffic, seed, side, seconds) -> dict:
    import gen
    import model
    import numpy as np
    t = time.perf_counter()
    d = model.dims(cfg)
    srv = driver.Server(cfg, traffic, seed)
    srv.warm_up(traffic, d.vocab, seed)
    schedule = gen.chat_schedule(traffic, seed=seed, seconds=seconds,
                                 vocab=d.vocab)
    _, results, _, _, _ = driver.serve_window(srv, schedule)
    sample = driver.pick_sample(schedule, results, traffic, seed)
    checks = [(schedule[i].tenant, schedule[i].prompt,
               np.asarray(results[i].tokens)) for i in sample]
    srv.free()
    del srv
    if side == "fault":
        # one served token of each checked request altered where it is
        # produced: the fourth (or the last, if fewer)
        for _, _, toks in checks:
            k = min(3, len(toks) - 1)
            toks[k] = (toks[k] + 1) % d.vocab
    quant = side if side not in (None, "fault") else None
    served, low = driver.reference_gaps(cfg, traffic, seed, checks,
                                        quant=quant)
    gaps = low if quant is not None else served
    return {"seed": seed, "side": side or "program",
            "widest_gap": float(np.max(gaps)),
            "program_widest_gap": float(np.max(served)),
            "tokens": int(served.size), "requests": len(sample),
            "seconds": time.perf_counter() - t}


def sweep_point(driver, cfg, traffic, seed, rate, seconds) -> dict:
    """Serve ``seconds`` of the traffic at ``rate``: how long the requests
    due in the window took to drain after it, and their latencies. A rate
    the system sustains drains within about one request's service time;
    above it the backlog, and the drain, grow with the window."""
    import gen
    import model
    import numpy as np
    d = model.dims(cfg)
    tr = dict(traffic, rate=rate)
    srv = driver.Server(cfg, tr, seed)
    srv.warm_up(tr, d.vocab, seed)
    schedule = gen.chat_schedule(tr, seed=seed, seconds=seconds,
                                 vocab=d.vocab)
    times, results, late, t0, t1 = driver.serve_window(srv, schedule)
    ttft, itl = driver.latencies(schedule, times, results, t0)
    stats = srv.engine.stats()
    srv.free()
    half = len(schedule) // 2
    return {"rate": rate, "requests": len(schedule),
            "drain_s": t1 - t0 - schedule[-1].due_s,
            "ttft_p50_ms": gen.percentile(ttft, 50),
            "ttft_p90_ms": gen.percentile(ttft, 90),
            "ttft_p90_first_half_ms": gen.percentile(ttft[:half], 90),
            "ttft_p90_second_half_ms": gen.percentile(ttft[half:], 90),
            "itl_p50_ms": gen.percentile(itl, 50),
            "itl_p99_ms": gen.percentile(itl, 99),
            "tokens_per_s": stats.generated_tokens / (t1 - t0),
            "occupancy": stats.mean_occupancy,
            "late_max_ms": 1e3 * float(np.max(late))}


if __name__ == "__main__":
    sys.exit(main())
