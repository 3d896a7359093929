"""Hillclimb profiler: compile one (arch x shape) cell and print the
per-op roofline breakdown (top HBM ops, top collectives, top dots).

    PYTHONPATH=src python scripts/profile_cell.py falcon-mamba-7b \
        prefill_32k [--multi-pod] [--norm-impl factored] [--rank 384]
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402
import argparse
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

from repro.compat import xla as cxla
from repro.core import DoRAConfig
from repro.launch.dryrun import MODELED_DEVICE_KIND
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import StepConfig, cell_specs
from repro.roofline import analyze_hlo_text, hw_for, roofline_terms


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("arch")
    ap.add_argument("shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--norm-impl", default="factored")
    ap.add_argument("--cache-base-norm", action="store_true")
    ap.add_argument("--rank", type=int, default=384)
    ap.add_argument("--loss-tokens", type=int, default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--top", type=int, default=14)
    ap.add_argument("--dump-hlo", default=None)
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    scfg = StepConfig(
        dora=DoRAConfig(rank=args.rank, alpha=args.rank / 2.0,
                        norm_impl=args.norm_impl,
                        cache_base_norm=args.cache_base_norm),
        loss_tokens=args.loss_tokens, grad_accum=args.grad_accum)
    cell = cell_specs(args.arch, args.shape, mesh, scfg=scfg)
    with mesh:
        j = jax.jit(cell["step"], in_shardings=cell["in_shardings"],
                    out_shardings=cell["out_shardings"],
                    donate_argnums=cell["donate"])
        compiled = j.lower(*cell["args"]).compile()
    hlo = compiled.as_text()
    if args.dump_hlo:
        with open(args.dump_hlo, "w") as f:
            f.write(hlo)
    ana = analyze_hlo_text(hlo)
    terms = roofline_terms(ana, hw_for(MODELED_DEVICE_KIND))
    mem = compiled.memory_analysis()
    print(f"== {args.arch} x {args.shape} "
          f"({'2x16x16' if args.multi_pod else '16x16'}) "
          f"norm={args.norm_impl}, modeling {MODELED_DEVICE_KIND} ==")
    print(f"compute {terms['compute_s']*1e3:.1f} ms | memory "
          f"{terms['memory_s']*1e3:.1f} ms | collective "
          f"{terms['collective_s']*1e3:.1f} ms -> {terms['dominant']}")
    print(f"peak {(cxla.peak_memory_bytes(compiled) + mem.argument_size_in_bytes - mem.alias_size_in_bytes)/2**30:.2f} GiB")
    print(ana.report(args.top))


if __name__ == "__main__":
    main()
