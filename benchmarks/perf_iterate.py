"""Perf-iteration harness (EXPERIMENTS.md §Perf).

Lowers one (arch x shape) cell under a named variant, prints the roofline
terms, and appends the record to results/perf/<cell>.jsonl — the
hypothesis -> change -> measure log lives in EXPERIMENTS.md.

    PYTHONPATH=src python -m benchmarks.perf_iterate \
        --arch granite-8b --shape train_4k --variant baseline

Variants are ModelConfig overrides (plus env toggles) registered below; add
new ones as the hillclimb progresses.

STT cells (ISSUE 2: benchmarks ride the front door): an ``--stt
<algebra>`` cell generates (algebra x named STT) through
``repro.generate`` instead, timing cold generation, cached re-generation
and kernel wall time, and appends the record the same way:

    PYTHONPATH=src python -m benchmarks.perf_iterate \
        --stt gemm --dataflow output_stationary

Tune cells (ISSUE 6: measured autotuning): ``--tune`` runs the
timing-driven tuner over registry cells — all six algebras, or the
two-cell ``--smoke`` subset CI runs — and writes the machine-readable
``BENCH_tune.json`` at the repo root (modeled vs measured cycles, tuned
vs untuned wall clock, the fitted calibration).  Exits nonzero when any
tuned pick is slower than its untuned baseline or the emitted document
fails the schema validator:

    PYTHONPATH=src python -m benchmarks.perf_iterate --tune [--smoke]
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import argparse
import json


VARIANTS = {
    "baseline": {},
    "no_sp": {"sequence_parallel": False},
    "no_remat": {"remat": False},
    "no_sp_no_remat": {"sequence_parallel": False, "remat": False},
    # chunked attention at 4k (smaller transient scores)
    "chunked_attn": {"_attn_full_max": 2048},
    # bigger kv chunks for the 32k paths
    "attn_bkv_4096": {"_attn_bkv": 4096},
    # beyond-paper: STT-scheduled explicit shard_map collectives
    "explicit": {"explicit_collectives": True},
    "explicit_chunked": {"explicit_collectives": True,
                         "_attn_full_max": 2048},
    "explicit_no_remat": {"explicit_collectives": True, "remat": False},
}


def run_variant(arch: str, shape: str, variant: str, multi: bool = False):
    from repro.launch import dryrun
    from repro.models import attention

    over = dict(VARIANTS[variant])
    full_max = over.pop("_attn_full_max", None)
    bkv = over.pop("_attn_bkv", None)
    old_max = attention.FULL_SCORES_MAX_LEN
    if full_max is not None:
        attention.FULL_SCORES_MAX_LEN = full_max
    if bkv is not None:
        os.environ["REPRO_ATTN_BKV"] = str(bkv)
    try:
        import repro.launch.specs as specs_mod
        orig = specs_mod.input_specs

        def patched(a, s, m, overrides=None):
            return orig(a, s, m, overrides={**(overrides or {}), **over})

        specs_mod.input_specs = patched
        try:
            rec = dryrun.run_cell(arch, shape, multi)
        finally:
            specs_mod.input_specs = orig
    finally:
        attention.FULL_SCORES_MAX_LEN = old_max
        os.environ.pop("REPRO_ATTN_BKV", None)
    rec["variant"] = variant
    return rec


def run_stt_cell(name: str, kind: str, interpret: bool = True) -> dict:
    """One (algebra x named STT) cell through the front door."""
    import time

    import repro
    from repro import compile as rcompile
    from repro.core import algebra

    alg = algebra.get_algebra(name)

    rcompile.cache_clear()
    t0 = time.perf_counter()
    acc = repro.generate(alg, kind, interpret=interpret, validate=False)
    t_cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    repro.generate(alg, kind, interpret=interpret, validate=False)
    t_cached = time.perf_counter() - t0

    # kernel wall time through the shared measurement harness (the same
    # warmup + median-of-k loop the autotuner persists numbers from)
    from repro.tune.measure import measure
    operands = alg.random_operands(0)
    meas = measure(acc, operands, warmup=1, repeats=3)
    t_first = meas.warmup_s
    t_steady = meas.median_s

    r = acc.cost_report()
    return {
        "cell": f"stt_{name}_{kind}",
        "algebra": name, "dataflow": acc.dataflow.name,
        "template": acc.template, "blocks": list(acc.kernel.blocks),
        "lower_cold_s": t_cold, "lower_cached_s": t_cached,
        "exec_first_s": t_first, "exec_steady_s": t_steady,
        "cache": rcompile.cache_info(),
        "model_cycles": r.cycles, "model_perf": r.normalized_perf,
    }


#: the two-cell CI smoke subset: the canonical dense algebra plus the
#: batch-folded one the tuner's headline speedup is measured on
SMOKE_TUNE_CELLS = ("gemm", "batched_gemv")
#: measured speedup the tuned batched_gemv pick must reach over the
#: untuned analytical pick (ISSUE 6 acceptance)
GEMV_MIN_SPEEDUP = 1.5


def run_tune_cells(smoke: bool, out_path: str = "BENCH_tune.json") -> dict:
    """Tune registry cells, emit BENCH_tune.json, return the document.

    Raises SystemExit (nonzero) when a tuned pick is slower than its
    untuned baseline, the batched_gemv speedup misses the floor (smoke),
    or the document fails its own schema validator.
    """
    import jax.numpy as jnp  # noqa: F401  (forces the backend up early)

    from repro import tune as rtune
    from repro.core.algebra import PAPER_ALGEBRAS, get_algebra
    from repro.core.tiling import ArrayConfig
    from repro.tune import report as rreport

    names = SMOKE_TUNE_CELLS if smoke else tuple(sorted(PAPER_ALGEBRAS))
    cfg = ArrayConfig()
    cells = []
    for name in names:
        alg = get_algebra(name)
        res = rtune.tune(alg, search=2, cfg=cfg, interpret=True)
        kernel = res.kernel
        rep = kernel.cost_report()
        cal = rtune.load_calibration()
        scale = cal.scale_for(kernel.template, alg.name)
        cells.append(rreport.cell_entry(
            cell=f"tune_{name}", algebra=name,
            dataflow=res.dataflow.name, template=kernel.template,
            variant={"blocks": res.variant.blocks,
                     "grid_order": res.variant.grid_order,
                     "accum": res.variant.accum},
            model_cycles=rep.cycles,
            calibrated_cycles=rep.cycles * scale,
            measured_cycles=(res.tuned_s or 0.0) * cfg.freq_mhz * 1e6,
            untuned_s=res.untuned_s or 0.0, tuned_s=res.tuned_s or 0.0,
            tune_cache_hit=res.cache_hit))
        c = cells[-1]
        print(f"tune/{name}: {c['dataflow']} {c['variant']['blocks']} "
              f"go={c['variant']['grid_order']} accum={c['variant']['accum']}"
              f" untuned={c['untuned_s'] * 1e3:.3f}ms "
              f"tuned={c['tuned_s'] * 1e3:.3f}ms "
              f"speedup={c['speedup']:.2f}x"
              + (" (cache hit)" if c["tune_cache_hit"] else ""))
        print(f"  cycles: model={c['model_cycles']:.0f} "
              f"calibrated={c['calibrated_cycles']:.0f} "
              f"measured={c['measured_cycles']:.0f}")

    cal = rtune.load_calibration()
    doc = {
        "version": rreport.BENCH_SCHEMA_VERSION,
        "smoke": bool(smoke),
        "interpret": True,
        "cells": cells,
        "calibration": {
            "per_template": dict(cal.per_template),
            "anchors": [{"template": t, "algebra": a, "scale": s}
                        for (t, a), s in sorted(cal.anchors.items())],
        },
    }

    errors = rreport.validate_bench(doc)
    if errors:
        raise SystemExit("BENCH_tune.json failed schema validation:\n  "
                         + "\n  ".join(errors))
    slow = [c["cell"] for c in cells if c["speedup"] < 1.0]
    if slow:
        raise SystemExit(f"tuned pick slower than untuned for: {slow}")
    for c in cells:
        if (c["algebra"] == "batched_gemv"
                and c["speedup"] < GEMV_MIN_SPEEDUP):
            raise SystemExit(
                f"tuned batched_gemv speedup {c['speedup']:.2f}x below "
                f"the {GEMV_MIN_SPEEDUP}x floor")
        if c["measured_cycles"] > 0 and not (
                0.5 <= c["calibrated_cycles"] / c["measured_cycles"] <= 2.0):
            raise SystemExit(
                f"{c['cell']}: calibrated prediction "
                f"{c['calibrated_cycles']:.0f} not within 2x of measured "
                f"{c['measured_cycles']:.0f}")

    with open(out_path, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"\nwrote -> {out_path} ({len(cells)} cells, all tuned picks "
          f">= untuned)")
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    ap.add_argument("--multi", action="store_true")
    ap.add_argument("--out", default="results/perf")
    ap.add_argument("--stt", metavar="ALGEBRA",
                    help="run an (algebra x STT) compile-pipeline cell "
                         "instead of an (arch x shape) model cell")
    ap.add_argument("--dataflow", default="output_stationary",
                    help="named STT for --stt cells")
    ap.add_argument("--tune", action="store_true",
                    help="run measured-autotuning cells and emit "
                         "BENCH_tune.json at the repo root")
    ap.add_argument("--smoke", action="store_true",
                    help="with --tune: the two-cell CI subset")
    args = ap.parse_args()

    if args.tune:
        run_tune_cells(args.smoke)
        return

    if args.stt:
        from repro.core.algebra import PAPER_ALGEBRAS
        if args.stt not in PAPER_ALGEBRAS:
            ap.error(f"unknown algebra {args.stt!r}; "
                     f"choose from {sorted(PAPER_ALGEBRAS)}")
        rec = run_stt_cell(args.stt, args.dataflow)
        print(f"\nstt/{args.stt} [{args.dataflow}]")
        print(f"  template      {rec['template']} blocks={rec['blocks']}")
        print(f"  lower cold    {rec['lower_cold_s'] * 1e3:.1f} ms")
        print(f"  lower cached  {rec['lower_cached_s'] * 1e6:.0f} us")
        print(f"  exec first    {rec['exec_first_s'] * 1e3:.1f} ms")
        print(f"  exec steady   {rec['exec_steady_s'] * 1e3:.1f} ms")
        print(f"  model perf    {rec['model_perf']:.3f}")
        os.makedirs(args.out, exist_ok=True)
        path = os.path.join(args.out, f"{rec['cell']}.jsonl")
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(f"appended -> {path}")
        return

    if not (args.arch and args.shape):
        ap.error("--arch and --shape are required unless --stt is given")
    rec = run_variant(args.arch, args.shape, args.variant, args.multi)
    r = rec["roofline"]
    print(f"\n{args.arch}/{args.shape} [{args.variant}]")
    print(f"  compute_s    {r['compute_s']:.4f}")
    print(f"  memory_s     {r['memory_s']:.4f}")
    print(f"  collective_s {r['collective_s']:.4f}")
    print(f"  bottleneck   {r['bottleneck']}")
    print(f"  MFU          {r['roofline_fraction']:.4f}")
    print(f"  useful ratio {r['useful_flops_ratio']:.3f}")
    print(f"  temp GiB     {rec['memory']['temp_bytes'] / 2**30:.1f} "
          f"(fits={rec['memory']['fits_hbm']})")
    os.makedirs(args.out, exist_ok=True)
    path = os.path.join(args.out,
                        f"{args.arch}_{args.shape}.jsonl")
    with open(path, "a") as f:
        f.write(json.dumps(rec) + "\n")
    print(f"appended -> {path}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
