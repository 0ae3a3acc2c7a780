"""Block-sparse GEMM benchmark: BSR kernel + compressed-format cost model.

Sweeps block density for a fixed GEMM and dataflow and reports, per
density,

  * cost-model cycles / runtime and operand + metadata traffic (the
    compressed-format terms the DSE ranks with),
  * the BSR grid size (nonzero blocks only) vs the dense grid,
  * end-to-end parity of the BSR Pallas kernel against the masked dense
    oracle (interpret mode, shrunk bounds — exact on integer operands),
    plus its measured wall time through the shared harness
    (``repro.tune.measure``: warmup + median-of-k).

Asserts the acceptance properties: model cycles and total traffic are
monotonically non-increasing as density decreases, and the executed
kernel matches the masked dense oracle at every density (with density
1.0 reproducing the dense path bit-exactly).

    PYTHONPATH=src python -m benchmarks.sparse_gemm [--smoke]

``--smoke`` runs one small size and two densities (< ~15 s; the CI
sparse step runs it on every push).
"""
from __future__ import annotations

import argparse

import numpy as np

import repro
from repro.core import stt
from repro.core.algebra import Sparsity, gemm
from repro.core.costmodel import PaperCycleModel
from repro.tune.measure import measure

#: validated execution bounds (loop-nest oracle + interpret-mode Pallas)
EXEC_SIZE, EXEC_BLOCK = 16, 4
#: cost-model sweep size (no execution at this size)
MODEL_SIZE, MODEL_BLOCK = 512, 32

DENSITIES = (1.0, 0.5, 0.25, 0.125)
SMOKE_DENSITIES = (1.0, 0.25)


def model_rows(densities, size=MODEL_SIZE, block=MODEL_BLOCK):
    g = gemm(size, size, size)
    df = stt.apply_stt(g, g.loops, stt.stt_from_name("output_stationary"))
    model = PaperCycleModel()
    rows = []
    for density in densities:
        sp = Sparsity.random((size, size), (block, block), density, seed=0)
        rep = model.evaluate(g.with_sparsity(A=sp), df)
        rows.append({
            "density": density,
            "nnz_blocks": sp.nnz_blocks,
            "cycles": rep.cycles,
            "runtime_ms": rep.runtime_ms,
            "traffic_mb": sum(rep.traffic_bytes.values()) / 1e6,
            "meta_kb": sum(rep.metadata_bytes.values()) / 1e3,
            "work_density": rep.work_density,
        })
    return rows


def execute_rows(densities, size=EXEC_SIZE, block=EXEC_BLOCK):
    rows = []
    dense_out = None
    for density in densities:
        sp = Sparsity.random((size, size), (block, block), density, seed=0)
        acc = repro.generate("gemm", bounds=dict(m=size, n=size, k=size),
                             sparsity={"A": sp}, interpret=True)
        err = acc.validate()
        operands = {k: np.asarray(v, np.float32) for k, v in
                    gemm(size, size, size).random_operands(seed=5).items()}
        if density == 1.0:
            dense = repro.generate("gemm",
                                   bounds=dict(m=size, n=size, k=size),
                                   interpret=True)
            dense_out = np.asarray(dense(operands))
        rows.append({
            "density": density,
            "mode": acc.kernel.sparse_mode,
            "grid_blocks": sp.nnz_blocks,
            "dense_grid": (size // block) ** 2,
            "max_err": err,
            "exec_ms": measure(acc, operands, warmup=1,
                               repeats=3).median_s * 1e3,
            "bit_exact_vs_dense": (
                bool((np.asarray(acc(operands)) == dense_out).all())
                if density == 1.0 else None),
        })
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small size, two densities (CI sparse step)")
    args = ap.parse_args()
    densities = SMOKE_DENSITIES if args.smoke else DENSITIES
    msize = 128 if args.smoke else MODEL_SIZE
    mblock = 16 if args.smoke else MODEL_BLOCK

    print(f"cost model (gemm {msize}^3, {mblock}x{mblock} blocks, "
          f"MNK-SST):")
    print("density,nnz_blocks,cycles,runtime_ms,traffic_mb,meta_kb")
    mrows = model_rows(densities, msize, mblock)
    for r in mrows:
        print(f"{r['density']},{r['nnz_blocks']},{r['cycles']:.0f},"
              f"{r['runtime_ms']:.4f},{r['traffic_mb']:.3f},"
              f"{r['meta_kb']:.2f}")
    for prev, cur in zip(mrows, mrows[1:]):
        assert cur["cycles"] <= prev["cycles"], "cycles not monotone"
        assert (cur["traffic_mb"] + cur["meta_kb"] / 1e3 <=
            prev["traffic_mb"] + prev["meta_kb"] / 1e3), "traffic not monotone"

    print(f"\nexecution (gemm {EXEC_SIZE}^3, {EXEC_BLOCK}x{EXEC_BLOCK} "
          f"blocks, interpret mode, masked dense oracle):")
    print("density,mode,grid_blocks,dense_grid,max_err,exec_ms,"
          "bit_exact_vs_dense")
    for r in execute_rows(densities):
        assert r["max_err"] <= 1e-3, r
        assert r["bit_exact_vs_dense"] in (None, True), r
        be = "-" if r["bit_exact_vs_dense"] is None else "yes"
        print(f"{r['density']},{r['mode']},{r['grid_blocks']},"
              f"{r['dense_grid']},{r['max_err']:.1e},"
              f"{r['exec_ms']:.3f},{be}")
    print("\nsparse_gemm: all parity and monotonicity checks passed")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
