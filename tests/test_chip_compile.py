"""Compile the main path's kernels and the serving step for a TPU v5e.

Nothing runs: each program is lowered for a *described* ``v5e:2x2``
topology and compiled by the chip's compiler (Mosaic for the Pallas
kernels), which refuses what interpret mode accepts — blocks that break
the (8, 128) tiling rule, kernels over the scoped VMEM limit, programs
larger than HBM.  The topology is described inside a module fixture,
never at import, so every test worker collects the same tests and only
the worker given this file loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import repro
from repro.core.algebra import get_algebra
from repro.core.tpu import V5E
from repro.kernels import ops, stt_gemm

GEN_DATAFLOWS = ("output_stationary", "weight_stationary", "input_stationary")
#: (template, stationary operand) of every stt_gemm realization
TEMPLATES = (("output_stationary", "B"), ("operand_stationary", "B"),
             ("operand_stationary", "A"), ("reduction_tree", "B"))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip cannot be read back from the
    # persistent cache here: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_algebra(alg, df, dtype, one_chip):
    acc = repro.generate(alg, df, dtype=dtype, interpret=False,
                         validate=False)
    shapes = {t.name: _spec(alg.tensor_shape(t), dtype, one_chip)
              for t in alg.inputs}
    return acc, jax.jit(lambda o: acc(o)).lower(shapes).compile()


@pytest.mark.parametrize("df", GEN_DATAFLOWS)
def test_generated_gemm_compiles(df, one_chip):
    # h2o-danube-1.8b's MLP up-projection over a 512-token prompt
    acc, compiled = _compile_algebra(get_algebra("gemm", m=512, n=6912,
                                                 k=2560),
                                     df, jnp.bfloat16, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    bm, bn, bk = acc.kernel.blocks
    assert bm % 16 == 0 and bn % 128 == 0 and bk % 128 == 0


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("template,stationary", TEMPLATES)
def test_gemm_template_compiles(template, stationary, dtype, one_chip):
    # each template at 512x6912x2560 with the blocks lower() would fit
    # from a wide request: the VMEM estimate must keep them under the
    # scoped limit the compiler enforces
    m, n, k = 512, 6912, 2560
    bm, bn, bk = stt_gemm.fit_blocks(
        template, (m, n, k), (256, 512, 512), dtype,
        stt_gemm.DEFAULT_VMEM_BUDGET, stationary=stationary)
    fn = jax.jit(functools.partial(
        ops.stt_matmul, template=template, stationary=stationary,
        bm=bm, bn=bn, bk=bk, interpret=False))
    compiled = fn.lower(_spec((m, k), dtype, one_chip),
                        _spec((k, n), dtype, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("df", GEN_DATAFLOWS)
def test_batched_gemv_decode_shape_compiles(df, one_chip):
    # GQA decode attention: 256 (slot x kv-head) rows against head_dim 80
    # keys over a 4096-token window
    _, compiled = _compile_algebra(get_algebra("batched_gemv", m=256, k=80,
                                               n=4096),
                                   df, jnp.bfloat16, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_merged_layer_graph_compiles(one_chip):
    from repro.graph import from_model

    g = from_model.transformer_layer_graph(l=256, d=256, f=512)
    acc = repro.generate(g, interpret=False, validate=False)
    assert len(acc.group_kernels) == 1
    shapes = {e: _spec(g.edge_shape(e), jnp.float32, one_chip)
              for e in g.inputs}
    compiled = jax.jit(lambda o: acc(o)).lower(shapes).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1


def test_full_width_decode_step_fits(one_chip):
    # h2o-danube-1.8b at its published widths, weights in bf16, the
    # serving batch (4 slots x 1024 positions of per-slot context)
    from repro.configs.registry import get_config
    from repro.models import decode as dec
    from repro.models import init_params, split

    cfg = get_config("h2o-danube-1.8b")
    capacity, context = 4, 1024

    def bf16_params(key):
        params, _ = split(init_params(key, cfg))
        return jax.tree.map(lambda a: a.astype(cfg.dtype), params)

    params = jax.eval_shape(bf16_params, jax.random.PRNGKey(0))
    cache = jax.eval_shape(
        lambda p: dec.init_cache(p, cfg, capacity, context), params)
    cache["pos"] = jax.ShapeDtypeStruct((capacity,), jnp.int32)
    as_spec = functools.partial(jax.tree.map,
                                lambda a: _spec(a.shape, a.dtype, one_chip))
    compiled = jax.jit(functools.partial(dec.decode_step, cfg=cfg)).lower(
        as_spec(params), _spec((capacity, 1), jnp.int32, one_chip),
        as_spec(cache)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes > 3.4e9      # the bf16 weights
    assert total < V5E.hbm_bytes, total


def test_merged_chain_at_the_vmem_gate_compiles(one_chip):
    # a bf16 gemm-gelu-gemm chain whose residency estimate sits just
    # under the 16 MiB budget: the planner merges it, and Mosaic must
    # accept what the planner admitted
    from repro.models import chains

    g = chains.mlp_graph(l=512, d=1024, f=1024)
    acc = repro.generate(g, interpret=False, validate=False,
                         dtype=jnp.bfloat16)
    (grp,) = acc.plan.groups
    assert grp.eligible and 12 * 2 ** 20 < grp.vmem_bytes <= 16 * 2 ** 20
    assert len(acc.group_kernels) == 1
    shapes = {e: _spec(g.edge_shape(e),
                       jnp.float32 if e == "b1" else jnp.bfloat16, one_chip)
              for e in g.inputs}
    compiled = jax.jit(lambda o: acc(o)).lower(shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
