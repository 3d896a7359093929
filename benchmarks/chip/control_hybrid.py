"""Readings that the limits of a hybrid fine-tuning cell are set from.

    python3 benchmarks/chip/control_hybrid.py --workload <name> \\
        --seeds 1 2 ... [--control-seeds 1 2 3] [--fault-seeds 1 2 3] \\
        [--out <file.json>]

For each seed of ``--seeds``, the numbers a run of the cell compares, read
from the program at the cell's own sizes (the lower readings); for each
seed of ``--control-seeds``, the same numbers with the reference computed
in float8 in the program's place (the upper readings); for each seed of
``--fault-seeds``, the reference with a fault planted in it: the loss over
every second loss token, and the scan's state restarted from zero every
``RESET`` tokens. A seed's plain reference is computed once for all of
them. All seeds run in one process, and ``--out`` is rewritten after each
reading. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types

# The planted scan fault: the state is not carried across chunks of this
# many tokens (the kernels' chunk).
RESET = 128


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
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
    drv = H.load_module("drivers", traffic["kind"])

    refs: dict[int, dict] = {}
    out = []

    def emit(seed, side, got, t):
        if seed not in refs:
            refs[seed] = drv.reference_readings(cfg, traffic, seed)
        ref = refs[seed]
        out.append({"seed": seed, "side": side, **drv.compare(got, ref),
                    "loss": got["loss"], "ref_loss": ref["loss"],
                    "grad": got["grad"].tolist(),
                    "ref_grad": ref["grad"].tolist(),
                    "seconds": time.perf_counter() - t})
        print(json.dumps({k: v for k, v in out[-1].items()
                          if k not in ("grad", "ref_grad")}), flush=True)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(out, f, indent=1)

    for seed in args.seeds:
        t = time.perf_counter()
        trainer = drv.Trainer(cfg, traffic, seed)
        got = trainer.first_steps()
        trainer.free()
        del trainer
        emit(seed, "program", got, t)
    for seed in args.control_seeds:
        t = time.perf_counter()
        emit(seed, "fp8", drv.reference_readings(cfg, traffic, seed,
                                                 quant="fp8"), t)
    for seed in args.fault_seeds:
        t = time.perf_counter()
        emit(seed, "half_tokens", drv.reference_readings(
            cfg, traffic, seed, fault="half_tokens"), t)
        t = time.perf_counter()
        emit(seed, "scan_reset", scan_reset_readings(drv, cfg, traffic,
                                                     seed), t)
    return 0


def scan_reset_readings(drv, cfg, traffic, seed) -> dict:
    """The reference's readings with its scan's state restarted every
    ``RESET`` tokens, through the driver's own protocol."""
    R = drv.T.R
    drv.T.R = types.SimpleNamespace(
        start_training=R.start_training,
        train_step=lambda *a: R.train_step(*a, RESET))
    try:
        return drv.reference_readings(cfg, traffic, seed)
    finally:
        drv.T.R = R


if __name__ == "__main__":
    sys.exit(main())
