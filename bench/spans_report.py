#!/usr/bin/env python3
"""One traced run of a cell, as ``bench/run.py --trace 1`` makes it,
with the program's spans and named scopes read from its trace.

    python3 bench/spans_report.py --workload danube.chat --seed 7 \\
        --seconds 51 [--out report.json]

Standard output ends with the same result line as ``bench/run.py
--trace 1``.  Before it, standard error gives (``benchlib/spans.py``):

* ``idle by span``: the first chip's idle time in the traced window by
  the innermost program span covering it, and ``no span``;
* for a serve cell, ``loop split``: mean ms per server loop under each
  ``serve.*`` span, ``host ms per loop``, and each span's self time;
* for a serve cell, ``step split``: ms per ``jit_step`` run by named
  scope of the compiled step (``SlotEngine.step_hlo_text()``, read after
  the window), ``other`` being the run's device time less the scopes,
  and the ops under no scope that take most of it;
* the spans' arguments: prefills per request id, live slots per step.

``--out`` writes the same as one JSON object.  Like ``bench/run.py`` it
exits 2 unless JAX finds a TPU with the chips the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def traced_run(root, name, seed, seconds, t_start=None):
    """(result line, report): ``harness.run_cell`` traced, keeping the
    trace with its span arguments and the decode step's HLO, which the
    result line does not carry."""
    from benchlib import harness, serve, spans
    from benchlib import trace as trace_mod

    kept = {}
    free, load = serve.free, harness.Tracer.load

    def free_after_hlo(engine, server):
        kept["serve"] = True
        if hasattr(engine, "step_hlo_text"):
            compiles = harness.CompileCounter()
            compiles.on, t0 = True, time.perf_counter()
            kept["hlo"] = engine.step_hlo_text()
            compiles.on = False
            print(f"step_hlo_text: {time.perf_counter() - t0:.3f} s, "
                  f"compiles or cache loads {compiles.count}",
                  file=sys.stderr)
        free(engine, server)

    def load_with_args(tracer):
        kept["trace"] = load(tracer)
        kept["spans"] = spans.load(trace_mod.find_xplane(tracer.directory))
        return kept["trace"]

    serve.free, harness.Tracer.load = free_after_hlo, load_with_args
    try:
        line = harness.run_cell(root, name, seed, seconds, True,
                                t_start=t_start)
    finally:
        serve.free, harness.Tracer.load = free, load
    return line, report(kept)


def report(kept):
    """The spans and scopes of one traced run, as plain numbers."""
    from benchlib import decode_work, spans
    from benchlib import trace as trace_mod

    trace = kept.get("trace")
    if trace is None or not trace.devices:
        return {}
    lo, hi = trace.window
    window = [s for s in kept["spans"] if lo <= s.start < hi]
    idle = trace_mod.gaps(trace.devices[0], trace.window)
    out = {"idle_by_span": spans.idle_by_span(trace),
           "idle_s": sum(b - a for a, b in idle) / 1e9,
           "window_s": trace.window_ns / 1e9,
           "span_counts": _counts(window)}
    if "serve" not in kept:
        calls = [s for s in window if s.name == "graph.call"]
        out["graph_call_ms"] = spans.mean_ms(trace, "graph.call")
        out["graph_calls"] = len(calls)
        return out
    out["loop_split_ms"] = spans.loop_split(trace)
    out["host_ms_per_loop"] = spans.host_ms_per_loop(trace)
    loops = out["span_counts"].get("serve.loop", 0)
    out["self_ms_per_loop"] = {
        k: v / loops / 1e6 for k, v in spans.self_ns(trace, "serve.").items()
        if loops}
    split = spans.program_split(trace, decode_work.DECODE_PROGRAM,
                                spans.hlo_scopes(kept.get("hlo", "")))
    if split is not None:
        per_run = split.total_ns / split.runs / 1e6
        scopes = {s: spans.scope_ms(split, s) for s in spans.DECODE_SCOPES}
        unscoped = sum(split.other_ops.values()) / split.runs / 1e6
        out["step_split_ms"] = {**scopes,
                                "other": per_run - sum(scopes.values())}
        out["other_ms"] = {"ops under no scope": unscoped,
                           "no op running": (per_run - unscoped
                                             - sum(scopes.values()))}
        out["decode_step_ms"] = per_run
        what = _instructions(kept.get("hlo", ""))
        out["other_ops_ms"] = [
            (op, ns / split.runs / 1e6, what.get(op, "?")) for op, ns in
            sorted(split.other_ops.items(), key=lambda kv: -kv[1])[:12]]
        out["step_runs"] = split.runs
    prefills = {}
    for s in window:
        if s.name == "serve.prefill":
            rid = s.args.get("rid")
            prefills[rid] = prefills.get(rid, 0) + 1
    steps = [s for s in window if s.name == "serve.step"]
    out["prefills"] = len(prefills)
    out["rids_with_more_than_one_prefill"] = sum(
        1 for n in prefills.values() if n > 1)
    out["mean_live_slots"] = (sum(s.args.get("live", 0) for s in steps)
                              / len(steps) if steps else None)
    return out


def _instructions(hlo):
    """Op name -> its result shape and opcode, from the HLO text."""
    found = re.findall(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (\([^=]*?\)|\S+) "
                       r"([\w-]+)\(", hlo, flags=re.M)
    return {name: f"{shape} {opcode}" for name, shape, opcode in found}


def _counts(window):
    out = {}
    for s in window:
        out[s.name] = out.get(s.name, 0) + 1
    return out


def print_report(rep) -> None:
    idle = rep.get("idle_s") or 0.0
    for name, s in rep.get("idle_by_span", []):
        share = 100.0 * s / idle if idle else 0.0
        print(f"idle by span: {name} {s * 1e3:.3f} ms ({share:.1f}% of "
              f"idle)", file=sys.stderr)
    if "loop_split_ms" in rep:
        parts = ", ".join(f"{k} {v:.3f}" for k, v in
                          rep["loop_split_ms"].items())
        print(f"loop split, mean ms per loop: {parts}; host ms per loop "
              f"{rep['host_ms_per_loop']:.3f}", file=sys.stderr)
        parts = ", ".join(f"{k} {v:.3f}" for k, v in
                          rep["self_ms_per_loop"].items())
        print(f"span self time, ms per loop: {parts}", file=sys.stderr)
    if "step_split_ms" in rep:
        parts = ", ".join(f"{k} {v:.3f}" for k, v in
                          rep["step_split_ms"].items())
        print(f"step split, ms per jit_step run ({rep['step_runs']} runs, "
              f"{rep['decode_step_ms']:.3f} ms each): {parts}",
              file=sys.stderr)
        parts = ", ".join(f"{k} {v:.3f}" for k, v in rep["other_ms"].items())
        print(f"step split, other: {parts}", file=sys.stderr)
        ops = ", ".join(f"{op} {ms:.3f} [{what}]"
                        for op, ms, what in rep["other_ops_ms"])
        print(f"step ops under no scope, ms per run: {ops}", file=sys.stderr)
    if "graph_call_ms" in rep:
        print(f"graph calls in the window {rep['graph_calls']}, "
              f"{rep['graph_call_ms']} ms each (host)", file=sys.stderr)
    counts = rep.get("span_counts", {})
    per = counts.get("serve.step") or counts.get("graph.call")
    if per:
        print(f"traced window {rep['window_s']:.3f} s: "
              f"{rep['window_s'] / per * 1e3:.3f} ms per decode step or "
              f"graph call", file=sys.stderr)
    print(f"spans in the window: {counts}", file=sys.stderr)
    if "prefills" in rep:
        print(f"requests prefilled {rep['prefills']} (more than once: "
              f"{rep['rids_with_more_than_one_prefill']}); mean live slots "
              f"per step {rep['mean_live_slots']}", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from benchlib import harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"spans_report: {args.workload} needs {cell.chips} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s). Nothing was run.", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    line, rep = traced_run(ROOT, args.workload, args.seed, args.seconds,
                           t_start=T_START)
    print_report(rep)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "seed": args.seed,
                       "line": line, "spans": rep}, f, indent=1)
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
