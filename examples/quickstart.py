"""Quickstart: the paper's pipeline through the one front door.

1. Describe a tensor algebra (GEMM) as a loop nest.
2. Pick a Space-Time Transformation matrix -> TensorLib classifies each
   tensor's dataflow (paper Table I).
3. ``repro.generate`` turns the classification into a complete
   accelerator: the Pallas kernel template on a chip *and* the collective
   schedule between chips, both selected by the same plan.
4. With a device mesh, the same handle executes multi-chip: the generated
   CommPlan compiles to a shard_map program (SUMMA / Cannon / ring-reduce
   fall out as special cases — nothing is hand-picked).

    PYTHONPATH=src python examples/quickstart.py
    # multi-chip on fake devices:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.core import algebra
from repro.launch.cache import enable_compile_cache

enable_compile_cache()
#: Pallas kernels compile with Mosaic on a TPU and run in the Pallas
#: interpreter anywhere else (what ``repro.generate`` picks by default)
INTERPRET = jax.default_backend() != "tpu"
#: max |error| / max |oracle| allowed between two fp32 paths: they sum
#: the same products in different orders (about k * 2^-24 ~ 4e-6 of the
#: output scale here); a bf16 stage would sit near 2^-8 ~ 4e-3
RTOL = 1e-5


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / np.abs(want).max())


# 1. the computation: C[m,n] += A[m,k] * B[n,k]
gemm = algebra.gemm(m=256, n=256, k=256)

# 2+3. one call per dataflow: classification -> plan -> executable
for kind in ("identity", "output_stationary", "weight_stationary"):
    acc = repro.generate(gemm, kind, validate=False)
    df = acc.dataflow
    print(f"\nSTT {kind!r} -> dataflow {df.name}")
    for t in df.tensors:
        print(f"  {t.tensor}: {t.cls.value:12s} dp={t.dp} dt={t.dt}")
    print(f"  PE modules: {acc.plan.pe_modules}")
    print(f"  kernel template: {acc.template} "
          f"(VMEM-resident: {acc.plan.kernel.resident_tensor})")
    print(f"  mesh schedule: "
          f"{ {t.tensor: t.kind for t in acc.plan.comm.tensors} }")

# 4. run the generated accelerator (interpret mode on CPU; Mosaic on TPU).
#    Blocks come from the same tile chooser the cost model prices with.
acc = repro.generate(gemm, "output_stationary")
print(f"\ncompiled: template={acc.template} blocks={acc.kernel.blocks} "
      f"stationary={acc.kernel.stationary}")
rng = np.random.default_rng(0)
a = jnp.array(rng.standard_normal((256, 256)), jnp.float32)
b = jnp.array(rng.standard_normal((256, 256)), jnp.float32)
c = acc({"A": a, "B": b})
err = float(jnp.abs(c - a @ b.T).max())
print(f"generated kernel vs oracle: max err {err:.2e}")
assert err < 1e-3

# repeat generation is free: the (bounded, thread-safe) compile cache
# returns the same kernel object
again = repro.generate(gemm, "output_stationary")
info = repro.compile.cache_info()
assert again.kernel is acc.kernel and info["hits"] >= 1
print(f"compile cache: {info}")

# 5. algebra graphs: chain accelerators without HBM round trips.  The
#    gelu epilogue folds into the first GEMM's kernel and the "h" edge is
#    consumed fused, so only x / the weights / the output touch HBM.
graph = repro.AlgebraGraph(
    nodes=(
        repro.GraphNode("up", algebra=algebra.gemm(m=64, n=64, k=64),
                        inputs=("x", "w1"), output="h"),
        repro.GraphNode("act", op="gelu", inputs=("h",), output="ha"),
        repro.GraphNode("down", algebra=algebra.gemm(m=64, n=32, k=64),
                        inputs=("ha", "w2"), output="y"),
    ),
    inputs=("x", "w1", "w2"),
    output="y",
)
gacc = repro.generate(graph, search=3)
grep = gacc.plan.cost_report()
x = jnp.array(rng.standard_normal((64, 64)), jnp.float32)
w1 = jnp.array(rng.standard_normal((64, 64)), jnp.float32)
w2 = jnp.array(rng.standard_normal((32, 64)), jnp.float32)
y = gacc({"x": x, "w1": w1, "w2": w2})
# the fp32 oracle; "highest" keeps XLA on a TPU from rounding fp32 matmul
# inputs to bf16
with jax.default_matmul_precision("highest"):
    want = jax.jit(lambda x, w1, w2:
                   jax.nn.gelu(x @ w1.T, approximate=True) @ w2.T)(x, w1, w2)
err = rel_err(y, want)
print(f"\nfused gemm-gelu-gemm: fused edges {grep.fused_edges}, "
      f"HBM bytes {grep.hbm_bytes:.0f} vs {grep.hbm_bytes_unfused:.0f} "
      f"unfused ({grep.hbm_ratio:.2f}x), relative err {err:.2e}")
assert err <= RTOL and grep.hbm_ratio > 1.0

# the fused chain is not just an accounting story: the whole group runs
# as ONE Pallas megakernel with the intermediate in VMEM scratch.
# Compare the modeled HBM saving with the measured wall clock against
# sequential per-node dispatch (build(merge=False)).
from repro.graph import executor as graph_executor
from repro.tune.measure import measure

assert gacc.group_kernels, "the gemm-gelu-gemm chain should merge"
seq = graph_executor.build(graph, interpret=INTERPRET, merge=False)
ops = {"x": x, "w1": w1, "w2": w2}
assert rel_err(gacc(ops), seq(ops)) <= RTOL
t_merged = measure(gacc, ops, warmup=1, repeats=5).median_s
t_seq = measure(seq, ops, warmup=1, repeats=5).median_s
print(f"merged megakernel {list(gacc.group_kernels)}: "
      f"modeled HBM saving {grep.hbm_ratio:.2f}x, measured "
      f"{t_merged * 1e3:.2f}ms vs sequential {t_seq * 1e3:.2f}ms "
      f"({t_seq / t_merged:.2f}x wall clock)")

# 6. a whole transformer layer as ONE graph: qkv projections, scaled
#    softmax attention, output projection + residual, gelu MLP — eight
#    gemms merging into a single megakernel.  The k/vt edges fuse on
#    consumer *rhs* sides (no materialized transpose), the first
#    residual stream r1 is exported as a *tap* so the closing add reads
#    it from HBM without re-running attention.
from repro.graph import from_model

layer = from_model.transformer_layer_graph(l=64, d=64, dv=64, f=128)
lacc = repro.generate(layer)
lrep = lacc.cost_report()
lops = layer.random_operands(seed=0)
lout = lacc(lops)
with jax.default_matmul_precision("highest"):
    assert rel_err(lout, from_model.layer_oracle(lops)) <= RTOL
lseq = graph_executor.build(layer, interpret=INTERPRET, merge=False)
assert rel_err(lout, lseq(lops)) <= RTOL
t_layer = measure(lacc, lops, warmup=1, repeats=5).median_s
t_layer_seq = measure(lseq, lops, warmup=1, repeats=5).median_s
print(f"\ntransformer layer graph: merged {list(lacc.group_kernels)}, "
      f"taps {list(lrep.tapped_edges)}")
print(f"  modeled HBM saving {lrep.hbm_ratio:.2f}x, measured layer "
      f"forward {t_layer * 1e3:.2f}ms vs sequential "
      f"{t_layer_seq * 1e3:.2f}ms ({t_layer_seq / t_layer:.2f}x)")

# multi-chip: the same plan drives the chip mesh when devices allow.  The
# SST dataflow's two ppermute rings + sharded output compile to a Cannon
# schedule — derived from the CommPlan, not picked by name.
if len(jax.devices()) >= 4:
    from repro.dist.engine import square_submesh
    multi = acc.sharded(square_submesh(2))
    c2 = multi({"A": a, "B": b})
    err = float(jnp.abs(c2 - a @ b.T).max())
    print(f"multi-chip (2x2 mesh, strategy="
          f"{multi._program().strategy}): max err {err:.2e}")
    assert err < 1e-2
else:
    print("single device only: skipping the mesh demo "
          "(rerun with XLA_FLAGS=--xla_force_host_platform_device_count=8)")
print("quickstart OK")
