import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST run before any other import (jax locks the device
count at first init); 512 placeholder CPU devices back both the 16x16
single-pod mesh and the 2x16x16 multi-pod mesh.  Nothing here allocates
real tensors — inputs are ShapeDtypeStructs (specs.input_specs).

Per cell this records:
  * compiled.memory_analysis()  (per-device bytes — proves HBM fit),
  * compiled.cost_analysis()    (XLA's own numbers, loop bodies unscaled),
  * hlo_analysis.analyze()      (trip-scaled flops / HBM bytes / collective
                                 wire bytes — the roofline inputs),
  * the three roofline terms + bottleneck (core.tpu.RooflineTerms).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-8b \
      --shape train_4k --mesh both
  ... --out results/dryrun  (JSON per cell; reused unless --force)
"""
import argparse
import json
import time
import traceback


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    import jax

    from ..core import tpu
    from . import hlo_analysis, specs
    from .mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    cell = specs.input_specs(arch, shape_name, mesh)

    t0 = time.perf_counter()
    # jax 0.8: set_mesh (not the bare `with mesh:` resource env) is what
    # makes bare-PartitionSpec sharding constraints inside the model resolve
    with jax.sharding.set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings,
                         donate_argnums=cell.donate)
        lowered = jitted.lower(*cell.args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    stats = hlo_analysis.analyze(compiled.as_text())

    terms = tpu.RooflineTerms(
        cell=f"{arch}/{shape_name}/{'multi' if multi_pod else 'single'}",
        chips=chips,
        hlo_flops=stats.flops * chips,          # per-device -> global
        hlo_bytes=stats.hbm_bytes * chips,
        collective_bytes=stats.wire_bytes * chips,
        model_flops=cell.model_flops,
    )
    per_dev_bytes = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips, "kind": cell.shape.kind,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "per_device_total": per_dev_bytes,
            "fits_hbm": bool(per_dev_bytes < tpu.V5E.hbm_bytes),
        },
        "xla_cost_analysis": {
            "flops_unscaled": cost.get("flops"),
            "bytes_accessed_unscaled": cost.get("bytes accessed"),
        },
        "hlo_stats": stats.as_dict(),
        "trip_counts": stats.trip_counts,
        "roofline": terms.as_dict(),
    }


def main() -> None:
    from ..configs import ARCH_IDS
    from . import specs

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all", help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help=f"one of {list(SHAPES)} or 'all'")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    results, failures = [], []
    for arch, shape_name in specs.all_cells():
        if arch not in archs:
            continue
        if args.shape != "all" and shape_name != args.shape:
            continue
        for multi in meshes:
            tag = f"{arch}_{shape_name}_{'multi' if multi else 'single'}"
            path = os.path.join(args.out, tag + ".json")
            if os.path.exists(path) and not args.force:
                print(f"[skip-cached] {tag}")
                continue
            print(f"[run] {tag} ...", flush=True)
            try:
                rec = run_cell(arch, shape_name, multi)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                r = rec["roofline"]
                print(f"  ok: compile={rec['compile_s']}s "
                      f"bottleneck={r['bottleneck']} "
                      f"mfu={r['roofline_fraction']:.3f} "
                      f"fits={rec['memory']['fits_hbm']}", flush=True)
                results.append(tag)
            except Exception as e:
                failures.append((tag, repr(e)))
                print(f"  FAIL {tag}: {e}")
                traceback.print_exc()

    # note the assignment-mandated skips
    skips = [{"arch": a, "shape": s, "reason": r}
             for a, s, r in specs.skipped_cells()]
    with open(os.path.join(args.out, "_skips.json"), "w") as f:
        json.dump(skips, f, indent=1)
    print(f"\ndone: {len(results)} cells ok, {len(failures)} failed, "
          f"{len(skips)} skipped-by-assignment")
    if failures:
        for tag, err in failures:
            print(f"  FAILED {tag}: {err}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
