"""Mesh scaling: per-device executed MACs and comm bytes vs device count.

For dense / block-sparse / batched GEMM-class algebras under their own
classification, sweeps mesh shapes (1 -> 8 devices) and reports, per
point,

  * the solved partition (strategy, batch axis, compressed sides),
  * per-device executed MACs (the batch-shard / spatial speedup),
  * per-device stored operand bytes and collective bytes received — the
    compressed path vs the masked-dense baseline, the batch-sharded path
    vs the replicating baseline,

everything priced from the same ``PartitionSolution`` the interpreter
executes (``repro.core.plan.solve_partition``).

Asserts the acceptance properties: per-device MACs and operand bytes
shrink monotonically with device count (~1/P for the sharded dims), the
compressed payload is the density-scaled fraction of the dense shard,
and — in ``--smoke`` on 8 fake CPU devices — every swept configuration
executes with parity against the loop-nest oracle.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.mesh_scaling [--smoke]

(The CI multidevice job runs ``--smoke`` on every push.)
"""
from __future__ import annotations

import argparse
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np

import repro
from repro.core import algebra
from repro.core.algebra import Sparsity
from repro.compile.lowering import lower_form
from repro.core.plan import comm_plan_for, solve_partition
from repro.core import stt

MESH_SHAPES = ((1, 1), (1, 2), (2, 2), (2, 4))

#: model-sweep bounds (solver accounting only — nothing executes here)
MODEL_BOUNDS = dict(m=256, n=256, k=256)
#: executed bounds for --smoke parity (loop-nest oracle stays fast)
SMOKE_BOUNDS = dict(m=16, n=16, k=16)
SPARSE_DENSITY = 0.25
SPARSE_BLOCK = 4


def cases(bounds):
    """(label, algebra, dataflow name) for dense / sparse / batched."""
    m, n, k = bounds["m"], bounds["n"], bounds["k"]
    g = algebra.gemm(m, n, k)
    sp = Sparsity.random((m, k), (SPARSE_BLOCK, SPARSE_BLOCK),
                         SPARSE_DENSITY, seed=7)
    bg = algebra.get_algebra("batched_gemv", m=m // 2, k=k, n=n)
    return (("dense-gemm", g, "output_stationary"),
            ("sparse-gemm", g.with_sparsity(A=sp), "output_stationary"),
            ("batched-gemv", bg, "output_stationary"))


def solve(alg, dfname, shape, **kw):
    df = stt.apply_stt(alg, alg.loops[:3], stt.stt_from_name(dfname))
    comm = comm_plan_for(df, densities={name: alg.density_of(name)
                                        for name, _ in alg.sparsity})
    return solve_partition(comm, lower_form(alg), shape=shape, **kw)


def rows_for(label, alg, dfname):
    form = lower_form(alg)
    rows = []
    for shape in MESH_SHAPES:
        sol = solve(alg, dfname, shape)
        devices = shape[0] * shape[1]
        stored = sol.per_device_bytes(form)
        moved = sol.comm_bytes(form)
        rows.append({
            "label": label, "shape": shape, "devices": devices,
            "strategy": sol.strategy, "batch_axis": sol.batch_axis,
            "compressed": sol.lhs.compressed or sol.rhs.compressed,
            "per_dev_macs": sol.per_device_macs(form),
            "operand_bytes": stored["lhs"] + stored["rhs"],
            "out_bytes": stored["out"],
            "comm_bytes": sum(moved.values()),
            "solution": sol,
        })
    return rows


def print_rows(rows):
    print(f"\n{rows[0]['label']}")
    print(f"{'mesh':>7s} {'devs':>4s} {'strategy':<17s} {'batch':>5s} "
          f"{'bsr':>3s} {'MACs/dev':>10s} {'opB/dev':>9s} {'commB/dev':>9s}")
    for r in rows:
        print(f"{str(r['shape']):>7s} {r['devices']:>4d} "
              f"{r['strategy']:<17s} {str(r['batch_axis'] or '-'):>5s} "
              f"{'y' if r['compressed'] else 'n':>3s} "
              f"{r['per_dev_macs']:>10d} {r['operand_bytes']:>9.0f} "
              f"{r['comm_bytes']:>9.0f}")


def assert_scaling(rows):
    """Per-device MACs and operand bytes shrink monotonically with device
    count; the 8-device point does ~1/P of the single-device work."""
    macs = [r["per_dev_macs"] for r in rows]
    opb = [r["operand_bytes"] for r in rows]
    assert all(a >= b for a, b in zip(macs, macs[1:])), macs
    assert all(a >= b for a, b in zip(opb, opb[1:])), opb
    # ~1/P on the executed work (padding on skewed dims allows slack 2x)
    p = rows[-1]["devices"]
    assert macs[-1] <= 2 * macs[0] / p, (macs, p)


def assert_baselines(label, alg, dfname, form):
    """The sharded/compressed footprints beat the replicating baselines
    the solver can still produce on request."""
    shape = MESH_SHAPES[-1]
    sol = solve(alg, dfname, shape)
    if form.batch:
        repl = solve(alg, dfname, shape, shard_batch=False)
        f_b = sol.sizes[sol.batch_axis]
        a = sol.per_device_bytes(form)
        b = repl.per_device_bytes(form)
        for side in ("lhs", "rhs", "out"):
            assert a[side] <= b[side] / f_b + 1e-9, (label, side)
        print(f"  {label}: batch shard stores 1/{f_b} of the replicating "
              f"baseline per device")
    if form.sparse is not None:
        dense = solve(alg, dfname, shape, compressed=False)
        side = form.sparse.side
        a = sol.per_device_bytes(form)[side]
        b = dense.per_device_bytes(form)[side]
        assert a < b, (label, a, b)
        print(f"  {label}: compressed payload {a:.0f}B/dev vs masked "
              f"dense {b:.0f}B/dev (density {form.sparse.density:.2f})")


def smoke_parity(label, alg, dfname):
    """Execute every swept mesh shape on fake devices: parity against
    the loop-nest oracle, compressed/batch-sharded paths included; wall
    time per shape via the shared harness (``repro.tune.measure``)."""
    import jax
    from jax.sharding import Mesh

    from repro.tune.measure import measure

    operands = alg.random_operands(seed=3)
    want = alg.reference(operands)
    acc = repro.generate(alg, dfname, interpret=True, validate=False)
    times = []
    for shape in MESH_SHAPES:
        n_dev = shape[0] * shape[1]
        if n_dev > len(jax.devices()):
            continue
        mesh = Mesh(np.asarray(jax.devices()[:n_dev]).reshape(shape),
                    ("x", "y"))
        sh = acc.sharded(mesh)
        got = np.asarray(sh(operands)).round().astype(np.int64)
        np.testing.assert_array_equal(got, want, err_msg=f"{label} {shape}")
        ms = measure(sh, operands, warmup=1, repeats=3).median_s * 1e3
        times.append(f"{shape}={ms:.1f}ms")
    print(f"  {label}: parity on {len(MESH_SHAPES)} mesh shapes "
          f"({' '.join(times)})")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small bounds + executed parity (CI)")
    args = ap.parse_args()
    bounds = SMOKE_BOUNDS if args.smoke else MODEL_BOUNDS

    for label, alg, dfname in cases(bounds):
        rows = rows_for(label, alg, dfname)
        print_rows(rows)
        assert_scaling(rows)
        assert_baselines(label, alg, dfname, lower_form(alg))
    if args.smoke:
        print("\nexecuted parity (fake devices):")
        for label, alg, dfname in cases(SMOKE_BOUNDS):
            smoke_parity(label, alg, dfname)
    print("\nMESH SCALING OK: per-device MACs and operand bytes shrink "
          "with device count")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
