"""Paper Fig. 6: design-space exploration — area/power scatter for GEMM and
Depthwise-Conv2D on a 16x16 INT16 array.

The paper reports 148 GEMM points and 33 depthwise points, a ~1.8x energy
spread vs ~1.16x area spread, MMT/MMS-style dataflows costing the most
energy, reduction trees being cheap, and stationary tensors costing area.
Our enumeration universe is stated in core/dse.py; this benchmark prints the
sweep summary + the same qualitative checks.

The enumeration now runs on the DSE fast path (per-selection nullspace
caching, duplicate-basis short-circuiting — ISSUE 1) and the benchmark
times it; ``--baseline`` additionally times the original per-T pipeline
for an A/B speedup print.  The best pareto point is then carried through
the front door (``repro.generate``) to a validated accelerator — plan to
kernel, not just plan to scatter plot.
"""
from __future__ import annotations

import argparse
import time
from collections import Counter

import repro
from repro.core import algebra, dse, stt


def sweep_algebra(alg, selections=None):
    pairs = dse.sweep_with_dataflows(alg, selections=selections)
    reports = [r for r, _ in pairs]
    good = [r for r in reports if r.normalized_perf >= 0.5]
    return reports, good, {id(r): df for r, df in pairs}


def summarize(name, reports, good):
    powers = sorted(r.power_mw for r in good)
    areas = sorted(r.area_units for r in good)
    letters = Counter(r.dataflow_name.split("-")[1] for r in reports)
    print(f"\n== {name} ==")
    print(f"distinct dataflow points: {len(reports)} "
          f"(letter-combos: {len(letters)})")
    print(f"efficient points (perf>=0.5): {len(good)}")
    if good:
        print(f"power range: {powers[0]:.1f} .. {powers[-1]:.1f} mW "
              f"({powers[-1] / powers[0]:.2f}x; paper: 35..63 = 1.8x)")
        print(f"area  range: {areas[0]:.0f} .. {areas[-1]:.0f} units "
              f"({areas[-1] / areas[0]:.2f}x; paper: 1.16x)")
    front = dse.pareto_front(good)
    print(f"pareto front size: {len(front)}")
    for r in sorted(front, key=lambda r: r.cycles)[:5]:
        print(f"  {r.dataflow_name:12s} perf={r.normalized_perf:.3f} "
              f"area={r.area_units:.0f} power={r.power_mw:.1f}mW")
    return powers, areas, front


def lower_winner(alg, front, df_of):
    """Carry the best pareto point through the front door at shrunk
    bounds: the generated accelerator must actually run.  ``df_of`` maps
    report identity -> Dataflow (names are not unique across a sweep)."""
    if not front:
        return
    best = min(front, key=lambda r: r.cycles)
    df = df_of.get(id(best))
    if df is None:
        return
    small = alg.with_bounds(**{l: min(b, 8) for l, b in
                               zip(alg.loops, alg.bounds)})
    sdf = stt.apply_stt(small, df.selected, df.T)
    acc = repro.generate(small, sdf, interpret=True, validate=True)
    print(f"generated pareto winner {df.name}: template={acc.template} "
          f"blocks={acc.kernel.blocks} validated={acc.kernel.validated}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline", action="store_true",
                    help="also time the original (per-T apply_stt) "
                         "enumeration for an A/B speedup print")
    args = ap.parse_args()

    g = algebra.gemm(256, 256, 256)
    t0 = time.perf_counter()
    reports, good, df_of = sweep_algebra(g, selections=[("m", "n", "k")])
    t_sweep = time.perf_counter() - t0
    powers, areas, front = summarize("GEMM (16x16, INT16)", reports, good)
    print(f"sweep time (fast enumeration + costing): {t_sweep:.2f}s")
    if args.baseline:
        t0 = time.perf_counter()
        ref = dse.enumerate_dataflows_reference(g, selections=[("m", "n", "k")])
        t_ref = time.perf_counter() - t0
        t0 = time.perf_counter()
        fast = dse.enumerate_dataflows(g, selections=[("m", "n", "k")])
        t_fast = time.perf_counter() - t0
        assert set(ref) == set(fast)
        print(f"enumeration A/B: seed path {t_ref:.2f}s, fast path "
              f"{t_fast:.2f}s -> {t_ref / max(t_fast, 1e-9):.1f}x")
    lower_winner(g, front, df_of)

    # paper claims
    mmt = [r for r in good if r.dataflow_name.endswith("MMT")]
    sst = [r for r in good if r.dataflow_name.endswith("SST")]
    checks = [
        ("energy spread > area spread",
         powers[-1] / powers[0] > areas[-1] / areas[0]),
        ("multicast-input dataflows (MMT) cost more power than systolic (SST)",
         mmt and sst and min(m.power_mw for m in mmt) >
         min(s.power_mw for s in sst)),
    ]

    dw = algebra.depthwise_conv(256, 28, 28, 3, 3)
    sels = [("k", "x", "y"), ("k", "p", "x"), ("x", "y", "p")]
    t0 = time.perf_counter()
    reports_dw, good_dw, df_of_dw = sweep_algebra(dw, selections=sels)
    t_dw = time.perf_counter() - t0
    _, _, front_dw = summarize("Depthwise-Conv2D (16x16, INT16)", reports_dw,
                               good_dw)
    print(f"sweep time: {t_dw:.2f}s")
    lower_winner(dw, front_dw or reports_dw, df_of_dw)

    print("\npaper-claim validation:")
    for desc, ok in checks:
        print(f"  [{'PASS' if ok else 'FAIL'}] {desc}")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
