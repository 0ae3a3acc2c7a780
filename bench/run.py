#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result.

    python3 bench/run.py --workload danube.chat --seed 7 --seconds 10 \\
        --trace 0

The cell, its configuration, traffic mix and metrics are found by name
from ``BENCHMARK.json`` at the root of the checkout (``bench/benchlib/
spec.py``).  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last the ``checks``:
each compared number beside its limit, which also end standard error.

Exits 2, printing no result, unless JAX finds a TPU with as many chips
as the cell asks for: there is no CPU fallback.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from benchlib import harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s). "
              f"Nothing was run.", file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    line = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                            bool(args.trace), t_start=T_START)
    harness.print_result(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
