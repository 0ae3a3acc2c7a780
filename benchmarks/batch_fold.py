"""Grid-folded vs block-diagonal batch execution (ISSUE 4 benchmark).

Sweeps batch (batched_gemv) and channel (depthwise_conv) counts and
reports, per size,

  * the executed-MAC ratio of each realization: the grid-folded path
    executes exactly the algebra's MACs (ratio 1.0, read off the
    generated accelerator's ``CostReport.executed_macs``), while the
    retired block-diagonal GEMM-ization executed batch x them,
  * wall time of both realizations on the XLA backend (jit'd, real
    compute — the asymptotic win is visible on CPU; Mosaic timings on a
    real TPU are hardware-pending, see ROADMAP),
  * interpret-mode parity of the grid-folded Pallas kernel against the
    block-diagonal oracle at the smallest size (bit-exact on integer
    operands).

Asserts the acceptance properties: the grid-folded ratio is 1.0 at every
size, the block-diagonal ratio equals the batch count, and the parity
check matches bitwise.

    PYTHONPATH=src python -m benchmarks.batch_fold [--smoke]

``--smoke`` runs two batch sizes with fewer timing repeats (< ~30 s; the
CI benchmark step runs it on every push).
"""
from __future__ import annotations

import argparse

import numpy as np
import jax
import jax.numpy as jnp

import repro
from repro.core import algebra
from repro.kernels import ref
from repro.tune.measure import measure

BATCHES = (4, 16, 64, 128)
SMOKE_BATCHES = (4, 16)
#: per-slice problem so the block-diagonal operand (b, b*k) stays
#: buildable at the largest batch
GEMV_K, GEMV_N = 64, 64
DW = dict(y=14, x=14, p=3, q=3)


def _time(fn, *args, repeats: int = 5) -> float:
    """Best-of-``repeats`` in ms, via the shared measurement harness
    (repro.tune.measure) — the one timing loop the whole repo uses."""
    return measure(fn, *args, warmup=1, repeats=repeats).best_s * 1e3


def gemv_rows(batches, repeats: int) -> list:
    rows = []

    @jax.jit
    def folded(a, b):
        return ref.matmul_ref(b.reshape(b.shape[0], 1, -1), a)

    @jax.jit
    def blockdiag(a, b):
        return ref.batched_gemv_blockdiag_ref(a, b)

    for bsz in batches:
        alg = algebra.batched_gemv(m=bsz, k=GEMV_K, n=GEMV_N)
        acc = repro.generate(alg, interpret=True, validate=False)
        rep = acc.cost_report()
        a = jnp.asarray(np.random.default_rng(0).standard_normal(
            (bsz, GEMV_K, GEMV_N)), jnp.float32)
        b = jnp.asarray(np.random.default_rng(1).standard_normal(
            (bsz, GEMV_K)), jnp.float32)
        rows.append({
            "algebra": "batched_gemv", "batch": bsz,
            "alg_macs": alg.total_macs(),
            "folded_ratio": rep.executed_mac_ratio,
            "blockdiag_ratio": (bsz * GEMV_N * bsz * GEMV_K)
            / alg.total_macs(),
            "folded_ms": _time(folded, a, b, repeats=repeats),
            "blockdiag_ms": _time(blockdiag, a, b, repeats=repeats),
        })
    return rows


def depthwise_rows(batches, repeats: int) -> list:
    rows = []
    y, x, p, q = DW["y"], DW["x"], DW["p"], DW["q"]

    @jax.jit
    def folded(a, b):
        from repro.compile.lowering import _im2col_batched
        return ref.matmul_ref(b.reshape(b.shape[0], 1, p * q),
                              _im2col_batched(a, y, x, p, q))

    @jax.jit
    def blockdiag(a, b):
        return ref.depthwise_blockdiag_ref(a, b, y=y, x=x)

    for ch in batches:
        alg = algebra.depthwise_conv(k=ch, **DW)
        acc = repro.generate(alg, interpret=True, validate=False)
        rep = acc.cost_report()
        a = jnp.asarray(np.random.default_rng(0).standard_normal(
            (ch, y + p - 1, x + q - 1)), jnp.float32)
        b = jnp.asarray(np.random.default_rng(1).standard_normal(
            (ch, p, q)), jnp.float32)
        rows.append({
            "algebra": "depthwise_conv", "batch": ch,
            "alg_macs": alg.total_macs(),
            "folded_ratio": rep.executed_mac_ratio,
            "blockdiag_ratio": (ch * y * x * ch * p * q) / alg.total_macs(),
            "folded_ms": _time(folded, a, b, repeats=repeats),
            "blockdiag_ms": _time(blockdiag, a, b, repeats=repeats),
        })
    return rows


def parity_check() -> None:
    """Grid-folded Pallas kernel (interpret mode) vs block-diagonal
    oracle: bit-exact on integer operands."""
    bg = algebra.batched_gemv(m=4, k=8, n=8)
    acc = repro.generate(bg, interpret=True)
    operands = bg.random_operands(seed=7)
    got = np.asarray(acc(operands))
    want = np.asarray(ref.batched_gemv_blockdiag_ref(
        jnp.asarray(operands["A"], jnp.float32),
        jnp.asarray(operands["B"], jnp.float32)))
    assert (got == want).all(), "batched_gemv parity failed"

    dw = algebra.depthwise_conv(k=8, y=6, x=6, p=3, q=3)
    acc = repro.generate(dw, interpret=True)
    operands = dw.random_operands(seed=7)
    got = np.asarray(acc(operands))
    want = np.asarray(ref.depthwise_blockdiag_ref(
        jnp.asarray(operands["A"], jnp.float32),
        jnp.asarray(operands["B"], jnp.float32), y=6, x=6))
    assert (got == want).all(), "depthwise parity failed"
    print("parity: grid-folded Pallas == block-diagonal oracle "
          "(bit-exact, interpret mode)\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="two batch sizes, fewer repeats (CI step)")
    args = ap.parse_args()
    batches = SMOKE_BATCHES if args.smoke else BATCHES
    repeats = 3 if args.smoke else 7

    parity_check()
    print("algebra,batch,alg_macs,folded_ratio,blockdiag_ratio,"
          "folded_ms,blockdiag_ms,speedup")
    worst_win_at_16 = None
    for row in gemv_rows(batches, repeats) + depthwise_rows(batches,
                                                            repeats):
        assert row["folded_ratio"] == 1.0, row
        assert row["blockdiag_ratio"] == row["batch"], row
        speedup = row["blockdiag_ms"] / row["folded_ms"]
        if row["batch"] >= 16:
            worst_win_at_16 = (speedup if worst_win_at_16 is None
                else min(worst_win_at_16, speedup))
        print(f"{row['algebra']},{row['batch']},{row['alg_macs']},"
              f"{row['folded_ratio']:.2f},{row['blockdiag_ratio']:.0f},"
              f"{row['folded_ms']:.3f},{row['blockdiag_ms']:.3f},"
              f"{speedup:.1f}x")
    print("\nbatch_fold: executed-MAC ratio drops from batch x to 1.0 at "
          "every size; all parity checks passed")
    if worst_win_at_16 is not None and worst_win_at_16 <= 1.0:
        # the win must hold for every row, so report the minimum; XLA
        # timing on shared CI machines can be noisy, so report rather
        # than fail (Mosaic wall time is hardware-pending anyway)
        print(f"note: wall-time win at batch >= 16 not observed on this "
              f"host for every case (worst {worst_win_at_16:.2f}x, "
              f"hardware-pending)")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
