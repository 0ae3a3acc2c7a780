#!/usr/bin/env python3
"""Readings a cell's definition rests on, made once on the chip; the
benchmark's own runs never make them.

    python3 bench/calibrate.py sweep --workload danube.chat \\
        --rates 1,1.5,2 --seconds 30 --seed 1
    python3 bench/calibrate.py limits --workload danube.chat \\
        --seeds 11,12,13 --seconds 12

``sweep`` serves the cell's mix at each offered rate in turn from one
engine and prints, per rate, what was sent and served, the tokens per
second and the TTFT and TPOT percentiles: the highest rate the system
sustains is read from it, and the mix's ``rate_rps`` set below it.

``limits`` runs, for each seed, the cell's set-up, a short window at
the cell's own load and the check, and prints the compared number for
the program and for the control: the plain reference computed in the
precision one step below the configuration's (float8 for bfloat16) and
put in the program's place.  A check's limit lies between the largest
program reading and the smallest control reading.  One JSON line per
rate or seed on standard output.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _pct(values, q):
    from benchlib.stats import percentile

    p = percentile(values, q)
    return None if p is None or math.isinf(p) else p * 1e3


def sweep(cell, rates, seconds, seed):
    from benchlib import serve, traffic

    engine, server, params, _ = serve.setup(cell, seed, seconds)
    mixes = [dict(cell.traffic, rate_rps=r) for r in rates]
    sets = [traffic.serve_requests(m, seconds, seed, engine.cfg.vocab)
            for m in mixes]
    serve.warm_inserts(engine, cell.traffic,
                       sorted({n for m, s in zip(mixes, sets)
                               for n in traffic.page_counts(m, s)}))
    for rate, reqs in zip(rates, sets):
        records, t0, t1, s0, s1 = serve.window(server, reqs, seconds)
        records = serve.collect(records, t1, backlog=False)
        done = [r for r in records if r.served]
        ttft = [(r.first - r.due) if r.served else math.inf for r in records]
        tpot = [(r.finished - r.first) / (len(r.tokens) - 1)
                if r.served else math.inf for r in records]
        steps = s1["steps"] - s0["steps"]
        print(json.dumps({
            "rate_rps": rate, "sent": len(records), "served": len(done),
            "tokens_per_s": (s1["tokens"] - s0["tokens"]) / (t1 - t0),
            "occupancy": (s1["occupancy_sum"] - s0["occupancy_sum"])
            / max(steps, 1),
            "ttft_p50_ms": _pct(ttft, 50), "ttft_p90_ms": _pct(ttft, 90),
            "ttft_p95_ms": _pct(ttft, 95), "tpot_p50_ms": _pct(tpot, 50),
            "tpot_p95_ms": _pct(tpot, 95),
            "last_finish_after_close_s": max(
                (r.finished for r in done), default=t1) - t1}), flush=True)
    serve.free(engine, server)


def limits(cell, seeds, seconds):
    from benchlib import graph, serve

    for seed in seeds:
        if cell.traffic["kind"] == "graph":
            acc, weights, xs, tune_dir = graph.setup(cell, seed)
            calls, _, _, kept, _ = graph.window(acc, weights, xs, seconds,
                                                seed)
            del acc
            tune_dir.cleanup()
            prog, ctl = graph.rel_errors(cell, weights, xs, kept,
                                         control=True)
            out = {"seed": seed, "number": "layer_rel_err", "program": prog,
                   "control": ctl, "calls": calls, "checked": len(kept)}
            del weights, xs, kept
        else:
            engine, server, params, reqs = serve.setup(cell, seed, seconds)
            records, _, t1, _, _ = serve.window(server, reqs, seconds)
            records = serve.collect(
                records, t1, cell.traffic["arrival"] == "backlog")
            serve.free(engine, server)
            del engine, server
            gc.collect()
            checked = serve.sample(records, int(cell.traffic[
                "check_requests"]), seed)
            prog, ctl, tokens = serve.logit_gaps(cell, params, checked,
                                                 control=True)
            out = {"seed": seed, "number": "logit_gap", "program": prog,
                   "control": ctl, "requests": len(records),
                   "unserved": sum(1 for r in records if not r.served),
                   "checked_tokens": tokens}
            del params, records
        gc.collect()
        print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("what", choices=("sweep", "limits"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--rates", default="")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from benchlib import spec

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU found; nothing was run", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    cell = spec.load_cell(ROOT, args.workload)
    if args.what == "sweep":
        sweep(cell, [float(r) for r in args.rates.split(",")], args.seconds,
              args.seed)
    else:
        limits(cell, [int(s) for s in args.seeds.split(",")], args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
