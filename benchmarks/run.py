"""Benchmark aggregator — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--smoke]

Sections:
  fig5   — normalized dataflow performance per tensor algebra (cycle model)
  fig6   — GEMM / depthwise-conv design-space area+power sweep
  sparse — block-sparse GEMM: BSR kernel parity + compressed-format costs
  batch_fold — grid-folded vs block-diagonal batch execution (MAC ratio +
         wall time; oracle parity)
  tune   — measured autotuning smoke: tuned vs untuned wall clock per cell,
         calibrated cycle model, BENCH_tune.json emission
  graph  — fused vs unfused attention+MLP chain (HBM-bytes proxy floor +
         fp32 parity vs the explicit-schedule oracle, BENCH_graph.json)
  table3 — MM throughput comparison (XLA baselines + TPU roofline projection)
  roofline — aggregated dry-run roofline table (if results/dryrun exists)

``--smoke`` is the CI bench-regress entry point: same sections, smoke
subsets everywhere, so the emitted BENCH_*.json artifacts stay cheap
enough to regenerate on every PR (``benchmarks/check_regress.py``
validates them and enforces the regression floors afterwards).
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def _section(title):
    print("\n" + "=" * 72)
    print(f"== {title}")
    print("=" * 72)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI subset: smoke flags for every section "
                         "(the sections below already default to their "
                         "smoke variants; the flag is the bench-regress "
                         "contract and gates the graph section's size)")
    args = ap.parse_args(argv)
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.time()
    failures = []

    _section("Fig. 5 — dataflow performance (paper cycle model)")
    try:
        from benchmarks import fig5_dataflow_perf
        fig5_dataflow_perf.main()
    except Exception:
        failures.append("fig5")
        traceback.print_exc()

    _section("Fig. 6 — design-space exploration (area / power)")
    try:
        from benchmarks import fig6_dse
        fig6_dse.main()
    except Exception:
        failures.append("fig6")
        traceback.print_exc()

    _section("Block-sparse GEMM — BSR kernel + compressed-format costs")
    try:
        from benchmarks import sparse_gemm
        sys.argv = ["sparse_gemm"]
        sparse_gemm.main()
    except Exception:
        failures.append("sparse")
        traceback.print_exc()

    _section("Batch fold — grid-folded vs block-diagonal execution")
    try:
        from benchmarks import batch_fold
        sys.argv = ["batch_fold", "--smoke"]
        batch_fold.main()
    except Exception:
        failures.append("batch_fold")
        traceback.print_exc()

    _section("Measured autotuning — tuned vs untuned + calibration")
    try:
        from benchmarks import perf_iterate
        perf_iterate.run_tune_cells(smoke=True)
    except Exception:
        failures.append("tune")
        traceback.print_exc()

    _section("Graph fusion — fused vs unfused attention+MLP chain")
    try:
        from benchmarks import graph_fusion
        graph_fusion.main(["--smoke"] if args.smoke else [])
    except Exception:
        failures.append("graph")
        traceback.print_exc()

    _section("Table III — matmul throughput comparison")
    try:
        from benchmarks import table3_comparison
        table3_comparison.main()
    except Exception:
        failures.append("table3")
        traceback.print_exc()

    _section("Roofline — dry-run aggregate (single-pod)")
    try:
        from benchmarks import roofline_report
        sys.argv = ["roofline_report"]
        roofline_report.main()
    except Exception:
        failures.append("roofline")
        traceback.print_exc()

    print(f"\nbenchmarks done in {time.time() - t0:.1f}s; "
          f"failures: {failures or 'none'}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
