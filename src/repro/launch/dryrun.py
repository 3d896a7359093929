"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The meshes are fake CPU devices; the roofline terms model the chip named
by :data:`MODELED_DEVICE_KIND`, whose peaks come from
``repro.roofline.PEAKS``.

MUST be the first two lines (jax locks device count on first init):
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402
import argparse
import json
import traceback

import jax

from repro.compat import xla as cxla
from repro.configs import ARCH_IDS, SHAPES, applicable_shapes, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import StepConfig, cell_specs
from repro.obs import monotonic
from repro.roofline import analyze_hlo_text, hw_for, model_flops, \
    roofline_terms

MODELED_DEVICE_KIND = "TPU v5 lite"


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             out_dir: str | None = None, scfg: StepConfig | None = None,
             verbose: bool = True, keep_hlo: bool = False) -> dict:
    """Lower + compile one cell on the production mesh; return the record
    (memory analysis, cost analysis, roofline terms)."""
    t0 = monotonic()
    mesh = make_production_mesh(multi_pod=multi_pod)
    scfg = scfg or StepConfig()
    cell = cell_specs(arch, shape_name, mesh, scfg=scfg)
    with mesh:
        jitted = jax.jit(cell["step"],
                         in_shardings=cell["in_shardings"],
                         out_shardings=cell["out_shardings"],
                         donate_argnums=cell["donate"])
        lowered = jitted.lower(*cell["args"])
        compiled = lowered.compile()
    t_compile = monotonic() - t0

    mem = compiled.memory_analysis()
    peak_bytes = cxla.peak_memory_bytes(compiled)
    cost = cxla.cost_analysis_dict(compiled)
    hlo = compiled.as_text()
    ana = analyze_hlo_text(hlo)
    hw = hw_for(MODELED_DEVICE_KIND)
    terms = roofline_terms(ana, hw)

    mcfg = cell["mcfg"]
    spec = cell["shape"]
    chips = mesh.devices.size
    tokens = spec.global_batch * (1 if spec.kind == "decode"
                                  else spec.seq_len)
    mf = model_flops(mcfg, tokens=tokens,
                     kind="train" if spec.kind == "train" else "serve")
    mf_per_chip = mf / chips

    record = {
        "arch": arch, "shape": shape_name, "kind": spec.kind,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "device_kind_modeled": MODELED_DEVICE_KIND,
        "compile_s": round(t_compile, 1),
        "memory": {
            "peak_bytes": peak_bytes,
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            # donated args alias outputs — they are not double-counted
            "fits_16g": bool(peak_bytes
                             + mem.argument_size_in_bytes
                             - mem.alias_size_in_bytes < hw.hbm_bytes),
        },
        "xla_cost": {"flops": cost.get("flops"),
                     "bytes": cost.get("bytes accessed")},
        "hlo": {
            "flops_per_chip": ana.flops,
            "hbm_bytes_per_chip": ana.hbm_bytes,
            "link_bytes_per_chip": ana.link_bytes,
            "by_collective": ana.by_collective,
        },
        "roofline": terms,
        "model_flops_per_chip": mf_per_chip,
        "useful_fraction": (mf_per_chip / ana.flops) if ana.flops else None,
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{record['mesh']}"
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(record, f, indent=1, default=float)
        if keep_hlo:
            with open(os.path.join(out_dir, tag + ".hlo"), "w") as f:
                f.write(hlo)
    if verbose:
        gib = 1 << 30
        print(f"[{record['mesh']}, modeling {MODELED_DEVICE_KIND}] "
              f"{arch} x {shape_name}: compile "
              f"{t_compile:.0f}s | peak {record['memory']['peak_bytes']/gib:.2f}"
              f" GiB (args {record['memory']['argument_bytes']/gib:.2f}) | "
              f"compute {terms['compute_s']*1e3:.2f} ms, memory "
              f"{terms['memory_s']*1e3:.2f} ms, collective "
              f"{terms['collective_s']*1e3:.2f} ms -> {terms['dominant']}"
              f" | useful {record['useful_fraction'] and round(record['useful_fraction'], 3)}",
              flush=True)
        print(f"  memory_analysis: {mem}", flush=True)
        print(f"  cost_analysis: flops={cost.get('flops'):.3e} "
              f"bytes={cost.get('bytes accessed'):.3e}", flush=True)
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch id (default all)")
    ap.add_argument("--shape", default=None,
                    help="one shape name (default: all applicable)")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out-dir", default="results/dryrun")
    ap.add_argument("--loss-tokens", type=int, default=None)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--rank", type=int, default=384)
    ap.add_argument("--norm-impl", default="factored",
                    choices=["factored", "dense_ba", "peft_eye"])
    ap.add_argument("--keep-hlo", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from repro.core import DoRAConfig
    scfg = StepConfig(
        dora=DoRAConfig(rank=args.rank, alpha=args.rank / 2.0,
                        norm_impl=args.norm_impl, mode="auto"),
        loss_tokens=args.loss_tokens, grad_accum=args.grad_accum)

    archs = [args.arch] if args.arch else ARCH_IDS
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    failures = []
    n_ok = 0
    for arch in archs:
        mcfg = get_config(arch)
        shapes = ([args.shape] if args.shape
                  else applicable_shapes(mcfg))
        for shape_name in shapes:
            for mp in meshes:
                tag = (f"{arch}_{shape_name}_"
                       f"{'2x16x16' if mp else '16x16'}")
                if args.skip_existing and os.path.exists(
                        os.path.join(args.out_dir, tag + ".json")):
                    print(f"skip {tag} (exists)", flush=True)
                    n_ok += 1
                    continue
                try:
                    run_cell(arch, shape_name, multi_pod=mp,
                             out_dir=args.out_dir, scfg=scfg,
                             keep_hlo=args.keep_hlo)
                    n_ok += 1
                except Exception as e:  # noqa: BLE001 — report, keep going
                    traceback.print_exc()
                    failures.append((tag, repr(e)))
    print(f"\n=== dry-run: {n_ok} ok, {len(failures)} failed ===")
    for tag, err in failures:
        print(f"  FAIL {tag}: {err[:200]}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
