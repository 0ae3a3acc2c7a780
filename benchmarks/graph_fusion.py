"""Graph fusion benchmark: merged-megakernel vs sequential dispatch.

    PYTHONPATH=src python -m benchmarks.graph_fusion [--smoke]

Gates (CI tier-1 smoke, PR 8 + ISSUE 9 + ISSUE 10):
  * the fused plan's HBM-bytes proxy beats the unfused pricing of the
    same chain by >= 1.3x (``GraphCostReport.hbm_ratio``),
  * execution matches the fp32 explicit-schedule oracle
    (``repro.models.chains``) AND sequential per-node dispatch
    (``build(merge=False)``) within ``PARITY_RTOL`` of the output scale,
  * the merged megakernel's *measured* wall clock (``tune/measure.py``
    harness: warmup + median-of-repeats around ``block_until_ready``)
    beats sequential dispatch by >= 1.2x,
  * the whole dense-family layer graph (``graph/from_model.py``) merges
    into one megakernel spanning attention and the MLP (residual tap
    exported), matches ``models.transformer.dense_layer_forward`` and
    sequential dispatch within ``PARITY_RTOL``, and its measured
    layer-forward speedup clears >= 1.2x.

``--smoke`` runs the small shapes only; the full run adds larger ones.
Emits ``BENCH_graph.json`` (schema v4: ``measured_speedup`` and the
oracle/sequential relative errors per chain plus the ``model_layer``
entry) at the repo root.
"""
from __future__ import annotations

import argparse
import json
import pathlib

import numpy as np

ROOT = pathlib.Path(__file__).parent.parent

#: minimum fused-vs-unfused HBM traffic ratio the chain must clear
HBM_RATIO_FLOOR = 1.3
#: minimum measured merged-vs-sequential wall-clock speedup
MEASURED_SPEEDUP_FLOOR = 1.2
#: minimum measured whole-layer-forward speedup over sequential dispatch
MODEL_SPEEDUP_FLOOR = 1.2
#: max |error| / max |oracle| allowed between fp32 paths.  Merged,
#: sequential and XLA execution sum the same products in different
#: orders (Mosaic on the chip, XLA on the host), which moves fp32 results
#: by about k * 2^-24 ~ 4e-6 of the output scale for these k <= 128
#: dots; a bf16 stage would sit near 2^-8 ~ 4e-3.
PARITY_RTOL = 1e-5
#: calls per timed sample — amortizes timer granularity; the harness
#: still takes the median over ``repeats`` samples
CALLS_PER_SAMPLE = 10


def run_chain(lq, lkv, d, dv, f, *, repeats=7) -> dict:
    import repro
    from repro.graph import executor as graph_executor
    from repro.models import chains
    from repro.tune.measure import measure

    g = chains.attention_mlp_graph(lq=lq, lkv=lkv, d=d, dv=dv, f=f)
    acc = repro.generate(g)
    seq = graph_executor.build(g, interpret=True, merge=False)
    rep = acc.cost_report()
    ops = g.random_operands(1)
    got = np.asarray(acc(ops))
    got_seq = np.asarray(seq(ops))
    want = np.asarray(chains.attention_mlp_oracle(
        {k: v for k, v in ops.items()}))
    max_err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())

    def loop(fn):
        def run():
            out = None
            for _ in range(CALLS_PER_SAMPLE):
                out = fn(ops)
            return out
        return run

    t_merged = measure(loop(acc), warmup=1,
                       repeats=repeats).median_s / CALLS_PER_SAMPLE
    t_seq = measure(loop(seq), warmup=1,
                    repeats=repeats).median_s / CALLS_PER_SAMPLE
    return {
        "shape": {"lq": lq, "lkv": lkv, "d": d, "dv": dv, "f": f},
        "hbm_bytes": rep.hbm_bytes,
        "hbm_bytes_unfused": rep.hbm_bytes_unfused,
        "hbm_ratio": rep.hbm_ratio,
        "fused_edges": list(rep.fused_edges),
        "cycles": rep.cycles,
        "cycles_unfused": rep.cycles_unfused,
        "merged_groups": list(acc.group_kernels),
        "oracle_rel_err": max_err / scale,
        "sequential_rel_err": float(np.abs(got - got_seq).max()) / scale,
        "max_err": max_err,
        "t_merged_s": t_merged,
        "t_sequential_s": t_seq,
        "measured_speedup": t_seq / t_merged,
    }


def run_model_layer(l, d, dv, f, *, repeats=7) -> dict:
    """One dense-family transformer layer as a fused graph vs sequential
    per-node dispatch, compared against the model-side fp32 oracle."""
    import repro
    from repro.graph import executor as graph_executor
    from repro.graph import from_model
    from repro.tune.measure import measure

    g = from_model.transformer_layer_graph(l=l, d=d, dv=dv, f=f)
    acc = repro.generate(g)
    seq = graph_executor.build(g, interpret=True, merge=False)
    rep = acc.cost_report()
    ops = g.random_operands(1)
    got = np.asarray(acc(ops))
    got_seq = np.asarray(seq(ops))
    want = np.asarray(from_model.layer_oracle(ops))
    max_err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())

    def loop(fn):
        def run():
            out = None
            for _ in range(CALLS_PER_SAMPLE):
                out = fn(ops)
            return out
        return run

    t_merged = measure(loop(acc), warmup=1,
                       repeats=repeats).median_s / CALLS_PER_SAMPLE
    t_seq = measure(loop(seq), warmup=1,
                    repeats=repeats).median_s / CALLS_PER_SAMPLE
    return {
        "shape": {"l": l, "d": d, "dv": dv, "f": f},
        "hbm_bytes": rep.hbm_bytes,
        "hbm_bytes_unfused": rep.hbm_bytes_unfused,
        "hbm_ratio": rep.hbm_ratio,
        "fused_edges": list(rep.fused_edges),
        "tapped_edges": list(rep.tapped_edges),
        "tap_hbm_bytes": rep.tap_hbm_bytes,
        "merged_groups": list(acc.group_kernels),
        "oracle_rel_err": max_err / scale,
        "sequential_rel_err": float(np.abs(got - got_seq).max()) / scale,
        "max_err": max_err,
        "t_merged_s": t_merged,
        "t_sequential_s": t_seq,
        "measured_speedup": t_seq / t_merged,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small chain only")
    args = ap.parse_args(argv)

    shapes = [(32, 32, 32, 32, 64)]
    if not args.smoke:
        shapes.append((64, 64, 64, 64, 128))

    rows = []
    for lq, lkv, d, dv, f in shapes:
        row = run_chain(lq, lkv, d, dv, f)
        rows.append(row)
        print(f"chain lq={lq} lkv={lkv} d={d} dv={dv} f={f}: "
              f"hbm {row['hbm_bytes']:.0f}B vs unfused "
              f"{row['hbm_bytes_unfused']:.0f}B "
              f"(ratio {row['hbm_ratio']:.2f}), "
              f"merged={row['merged_groups']}, "
              f"measured {row['t_merged_s'] * 1e3:.2f}ms vs sequential "
              f"{row['t_sequential_s'] * 1e3:.2f}ms "
              f"({row['measured_speedup']:.2f}x), "
              f"oracle_rel_err={row['oracle_rel_err']:.1e} "
              f"sequential_rel_err={row['sequential_rel_err']:.1e}")

    layer_shape = (32, 32, 32, 64) if args.smoke else (64, 64, 64, 128)
    model = run_model_layer(*layer_shape)
    print(f"model layer l={layer_shape[0]} d={layer_shape[1]} "
          f"dv={layer_shape[2]} f={layer_shape[3]}: "
          f"merged={model['merged_groups']}, "
          f"taps={model['tapped_edges']}, "
          f"measured {model['t_merged_s'] * 1e3:.2f}ms vs sequential "
          f"{model['t_sequential_s'] * 1e3:.2f}ms "
          f"({model['measured_speedup']:.2f}x), "
          f"oracle_rel_err={model['oracle_rel_err']:.1e} "
          f"sequential_rel_err={model['sequential_rel_err']:.1e}")

    doc = {"version": 4, "floor": HBM_RATIO_FLOOR,
           "parity_rtol": PARITY_RTOL,
           "measured_floor": MEASURED_SPEEDUP_FLOOR,
           "model_floor": MODEL_SPEEDUP_FLOOR,
           "chains": rows, "model_layer": model}
    (ROOT / "BENCH_graph.json").write_text(json.dumps(doc, indent=2))
    print(f"wrote {ROOT / 'BENCH_graph.json'}")

    problems = []
    for row in rows:
        if row["oracle_rel_err"] > PARITY_RTOL:
            problems.append(f"{row['shape']}: off the explicit-schedule "
                            f"oracle by {row['oracle_rel_err']:.3e} "
                            f"(relative) > {PARITY_RTOL}")
        if row["sequential_rel_err"] > PARITY_RTOL:
            problems.append(f"{row['shape']}: merged kernel off "
                            f"sequential dispatch by "
                            f"{row['sequential_rel_err']:.3e} > "
                            f"{PARITY_RTOL}")
        if not row["merged_groups"]:
            problems.append(f"{row['shape']}: no merged group lowered")
        if row["hbm_ratio"] < HBM_RATIO_FLOOR:
            problems.append(f"{row['shape']}: hbm_ratio "
                            f"{row['hbm_ratio']:.2f} < floor "
                            f"{HBM_RATIO_FLOOR}")
        if row["measured_speedup"] < MEASURED_SPEEDUP_FLOOR:
            problems.append(f"{row['shape']}: measured_speedup "
                            f"{row['measured_speedup']:.2f} < floor "
                            f"{MEASURED_SPEEDUP_FLOOR}")
    if model["oracle_rel_err"] > PARITY_RTOL:
        problems.append(f"model_layer {model['shape']}: off "
                        f"models.transformer.dense_layer_forward by "
                        f"{model['oracle_rel_err']:.3e} > {PARITY_RTOL}")
    if model["sequential_rel_err"] > PARITY_RTOL:
        problems.append(f"model_layer {model['shape']}: merged kernel "
                        f"off sequential dispatch by "
                        f"{model['sequential_rel_err']:.3e} > "
                        f"{PARITY_RTOL}")
    if not model["merged_groups"]:
        problems.append(f"model_layer {model['shape']}: no merged group "
                        f"lowered (whole-layer fusion regressed)")
    if not model["tapped_edges"]:
        problems.append(f"model_layer {model['shape']}: no residual tap "
                        f"exported")
    if model["measured_speedup"] < MODEL_SPEEDUP_FLOOR:
        problems.append(f"model_layer {model['shape']}: measured_speedup "
                        f"{model['measured_speedup']:.2f} < floor "
                        f"{MODEL_SPEEDUP_FLOOR}")
    if problems:
        raise SystemExit("graph_fusion gates failed:\n  "
                         + "\n  ".join(problems))
    print("graph_fusion gates passed "
          f"(hbm_ratio floor {HBM_RATIO_FLOOR}, measured_speedup floor "
          f"{MEASURED_SPEEDUP_FLOOR}, model_layer floor "
          f"{MODEL_SPEEDUP_FLOOR}, parity rtol {PARITY_RTOL})")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
