"""bench-regress gate: validate emitted BENCH_*.json + enforce floors.

    PYTHONPATH=src python -m benchmarks.check_regress

Run after ``benchmarks/run.py --smoke`` (the CI bench-regress job does).
Re-validates every benchmark artifact against its schema and fails the
job when a performance ratio regresses below its floor:

  * BENCH_tune.json  — schema ``repro.tune.report.validate_bench``;
    tuned-vs-untuned speedup >= TUNE_SPEEDUP_FLOOR per cell (a tuned
    pick must never lose to its own untuned baseline),
  * BENCH_graph.json — schema v4: fused-vs-unfused HBM ratio >= the
    modeled floor recorded in the document
    (``benchmarks.graph_fusion.HBM_RATIO_FLOOR``), *measured*
    merged-vs-sequential wall-clock speedup >= the document's
    ``measured_floor`` (``MEASURED_SPEEDUP_FLOOR``, >= 1.2), relative
    error <= ``parity_rtol`` (<= 1e-5, see
    ``graph_fusion.PARITY_RTOL``) against both the fp32
    explicit-schedule oracle and sequential dispatch, AND a
    ``model_layer`` entry: the whole dense-family layer graph must keep
    >= 1 merged group with its residual tap exported, the same parity
    bound vs ``models.transformer.dense_layer_forward``, and measured
    layer-forward speedup >= ``model_floor`` (>= 1.2).

The emitting benchmarks enforce their own gates too; this checker is
the belt to their suspenders — it catches a stale or hand-edited
artifact and gives CI one uniform failure surface to report.
"""
from __future__ import annotations

import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).parent.parent

TUNE_SPEEDUP_FLOOR = 1.0


def _load(name: str, problems: list) -> dict | None:
    path = ROOT / name
    if not path.exists():
        problems.append(f"{name}: missing (did benchmarks/run.py run?)")
        return None
    try:
        return json.loads(path.read_text())
    except ValueError as e:
        problems.append(f"{name}: unparseable ({e})")
        return None


def check(problems: list) -> None:
    from repro.tune.report import validate_bench

    tune = _load("BENCH_tune.json", problems)
    if tune is not None:
        problems += [f"BENCH_tune.json: {p}" for p in validate_bench(tune)]
        for cell in tune.get("cells", []):
            sp = cell.get("speedup")
            if isinstance(sp, (int, float)) and sp < TUNE_SPEEDUP_FLOOR:
                problems.append(
                    f"BENCH_tune.json: {cell.get('cell')} tuned/untuned "
                    f"speedup {sp:.2f} < floor {TUNE_SPEEDUP_FLOOR}")

    graph = _load("BENCH_graph.json", problems)
    if graph is not None:
        floor = graph.get("floor")
        mfloor = graph.get("measured_floor")
        lfloor = graph.get("model_floor")
        chains = graph.get("chains")
        model = graph.get("model_layer")
        rtol = graph.get("parity_rtol")
        if graph.get("version") != 4:
            problems.append(f"BENCH_graph.json: schema version "
                            f"{graph.get('version')!r} != 4 (stale "
                            f"artifact? re-run benchmarks.graph_fusion)")
        elif (not isinstance(floor, (int, float))
                or not isinstance(mfloor, (int, float))
                or not isinstance(lfloor, (int, float))
                or not isinstance(rtol, (int, float))
                or not isinstance(chains, list) or not chains
                or not isinstance(model, dict)):
            problems.append("BENCH_graph.json: needs numeric 'floor', "
                            "'measured_floor', 'model_floor' and "
                            "'parity_rtol', "
                            "non-empty 'chains' and a 'model_layer' "
                            "object")
        elif mfloor < 1.2 or lfloor < 1.2 or rtol > 1e-5:
            problems.append(f"BENCH_graph.json: measured_floor {mfloor} "
                            f"/ model_floor {lfloor} < 1.2 or "
                            f"parity_rtol {rtol} > 1e-5 (the gates "
                            f"must not be weakened)")
        else:
            for row in chains:
                ratio = row.get("hbm_ratio")
                if not isinstance(ratio, (int, float)) or ratio < floor:
                    problems.append(
                        f"BENCH_graph.json: {row.get('shape')} hbm_ratio "
                        f"{ratio} < floor {floor}")
                speedup = row.get("measured_speedup")
                if (not isinstance(speedup, (int, float))
                        or speedup < mfloor):
                    problems.append(
                        f"BENCH_graph.json: {row.get('shape')} "
                        f"measured_speedup {speedup} < floor {mfloor}")
                if not row.get("merged_groups"):
                    problems.append(
                        f"BENCH_graph.json: {row.get('shape')} has no "
                        f"merged group (megakernel path not exercised)")
                for key, what in (("oracle_rel_err",
                                   "the explicit-schedule oracle"),
                                  ("sequential_rel_err",
                                   "sequential dispatch")):
                    err = row.get(key)
                    if not isinstance(err, (int, float)) or err > rtol:
                        problems.append(
                            f"BENCH_graph.json: {row.get('shape')} "
                            f"{key} {err} vs {what} > {rtol}")
            speedup = model.get("measured_speedup")
            if not isinstance(speedup, (int, float)) or speedup < lfloor:
                problems.append(
                    f"BENCH_graph.json: model_layer measured_speedup "
                    f"{speedup} < floor {lfloor}")
            if not model.get("merged_groups"):
                problems.append(
                    "BENCH_graph.json: model_layer has no merged group "
                    "(whole-layer fusion regressed)")
            if not model.get("tapped_edges"):
                problems.append(
                    "BENCH_graph.json: model_layer exports no residual "
                    "tap")
            for key, what in (("oracle_rel_err",
                               "models.transformer.dense_layer_forward"),
                              ("sequential_rel_err", "sequential dispatch")):
                err = model.get(key)
                if not isinstance(err, (int, float)) or err > rtol:
                    problems.append(
                        f"BENCH_graph.json: model_layer {key} {err} vs "
                        f"{what} > {rtol}")


def main() -> None:
    problems: list = []
    check(problems)
    if problems:
        print("bench-regress gates FAILED:", file=sys.stderr)
        for p in problems:
            print(f"  {p}", file=sys.stderr)
        raise SystemExit(1)
    print("bench-regress gates passed (tune schema+floor, graph "
          "ratio+parity)")


if __name__ == "__main__":
    main()
