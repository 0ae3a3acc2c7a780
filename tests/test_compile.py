"""End-to-end compile pipeline: every registry algebra x named STTs must
lower to an executable kernel matching the loop-nest oracle (ISSUE 1)."""
import numpy as np
import pytest
import jax.numpy as jnp

from repro import compile as rcompile
from repro.core import algebra, plan, stt, tiling
from repro.kernels import ops, stt_gemm


#: small bounds so alg.reference (python loop oracle) stays fast and the
#: fp32 path is exact on integer operands
SMALL_BOUNDS = {
    "gemm": dict(m=8, n=8, k=8),
    "batched_gemv": dict(m=4, k=8, n=8),
    "conv2d": dict(k=8, c=4, y=6, x=6, p=3, q=3),
    "depthwise_conv": dict(k=8, y=6, x=6, p=3, q=3),
    "mttkrp": dict(i=8, j=8, k=4, l=4),
    "ttmc": dict(i=4, j=4, k=4, l=4, m=4),
}

NAMED_STTS = ("identity", "output_stationary", "weight_stationary",
              "input_stationary")


def small(name):
    return algebra.get_algebra(name, **SMALL_BOUNDS[name])


# ---------------------------------------------------------------------------
# The acceptance matrix: registry x named STTs, interpret mode vs oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", NAMED_STTS)
@pytest.mark.parametrize("name", sorted(algebra.PAPER_ALGEBRAS))
def test_every_algebra_executes_through_pipeline(name, kind):
    alg = small(name)
    df = stt.apply_stt(alg, alg.loops[:3], stt.stt_from_name(kind))
    kern = rcompile.lower(alg, df, interpret=True)
    assert kern.validated          # small problem -> auto-validated
    operands = alg.random_operands(seed=7)
    got = np.asarray(kern(operands)).round().astype(np.int64)
    want = alg.reference(operands)
    np.testing.assert_array_equal(got, want)
    # the template really is the plan's selection for this dataflow
    assert kern.template == plan.kernel_plan_for(df).template


def test_lowering_covers_whole_registry():
    for name in algebra.PAPER_ALGEBRAS:
        form = rcompile.gemmize(small(name))
        alg = small(name)
        assert form.m * form.n * form.k > 0
        # every loop iterator is folded into exactly the dims it claims
        folded = [l for dim in ("m", "n", "k") for l in form.dim_loops[dim]]
        assert set(folded) <= set(alg.loops)


def test_gemmize_unknown_algebra_raises():
    bogus = algebra.gemm(4, 4, 4)
    bogus = bogus.__class__(name="winograd", loops=bogus.loops,
                            bounds=bogus.bounds, tensors=bogus.tensors)
    with pytest.raises(NotImplementedError):
        rcompile.gemmize(bogus)


# ---------------------------------------------------------------------------
# Compile cache
# ---------------------------------------------------------------------------

def test_cache_hits_on_repeat_lowering():
    rcompile.cache_clear()
    alg = small("gemm")
    df = stt.apply_stt(alg, alg.loops, stt.stt_from_name("identity"))
    k1 = rcompile.lower(alg, df, interpret=True)
    before = rcompile.cache_info()
    k2 = rcompile.lower(alg, df, interpret=True)
    after = rcompile.cache_info()
    assert k1 is k2
    assert after["hits"] == before["hits"] + 1
    assert after["misses"] == before["misses"]


def test_cache_hit_honours_late_validate_request():
    rcompile.cache_clear()
    alg = small("gemm")
    df = stt.apply_stt(alg, alg.loops, stt.stt_from_name("identity"))
    k1 = rcompile.lower(alg, df, interpret=True, validate=False)
    assert not k1.validated
    k2 = rcompile.lower(alg, df, interpret=True, validate=True)
    assert k2 is k1 and k2.validated


def test_cache_distinguishes_shapes_dtype_interpret():
    rcompile.cache_clear()
    a1 = small("gemm")
    a2 = a1.with_bounds(m=16)
    df1 = stt.apply_stt(a1, a1.loops, stt.stt_from_name("identity"))
    df2 = stt.apply_stt(a2, a2.loops, stt.stt_from_name("identity"))
    k1 = rcompile.lower(a1, df1, interpret=True)
    k2 = rcompile.lower(a2, df2, interpret=True)            # shapes differ
    k3 = rcompile.lower(a1, df1, interpret=True, dtype=jnp.bfloat16,
                        validate=False)                     # dtype differs
    k4 = rcompile.lower(a1, df1, interpret=True, backend="xla")
    assert len({id(k) for k in (k1, k2, k3, k4)}) == 4
    assert rcompile.cache_info()["misses"] == 4


def _lower_gemm_m(m, **kw):
    alg = small("gemm").with_bounds(m=m)
    df = stt.apply_stt(alg, alg.loops, stt.stt_from_name("identity"))
    return rcompile.lower(alg, df, interpret=True, validate=False, **kw)


def test_cache_eviction_follows_recency_not_insertion():
    """A cache hit must refresh recency: with capacity 2, touching the
    older entry before inserting a third evicts the *other* one."""
    rcompile.cache_clear()
    old_cap = rcompile.cache_info()["capacity"]
    try:
        rcompile.cache_resize(2)
        _lower_gemm_m(8)
        _lower_gemm_m(16)
        _lower_gemm_m(8)            # hit: m=8 becomes most-recently-used
        _lower_gemm_m(24)           # evicts m=16, not m=8
        before = rcompile.cache_info()
        _lower_gemm_m(8)
        after = rcompile.cache_info()
        assert after["hits"] == before["hits"] + 1       # m=8 survived
        _lower_gemm_m(16)
        assert rcompile.cache_info()["misses"] == after["misses"] + 1
    finally:
        rcompile.cache_resize(old_cap)
        rcompile.cache_clear()


def test_cache_resize_below_occupancy_evicts_lru_first():
    rcompile.cache_clear()
    old_cap = rcompile.cache_info()["capacity"]
    try:
        kernels = {m: _lower_gemm_m(m) for m in (8, 16, 24)}
        assert rcompile.cache_info()["size"] == 3
        rcompile.cache_resize(1)
        info = rcompile.cache_info()
        assert info["size"] == 1 and info["capacity"] == 1
        assert info["evictions"] == 2
        # the survivor is the most recently used entry (m=24)
        assert _lower_gemm_m(24) is kernels[24]
        assert rcompile.cache_info()["hits"] == info["hits"] + 1
    finally:
        rcompile.cache_resize(old_cap)
        rcompile.cache_clear()


def test_cache_hit_auto_validates_small_problems():
    """An entry cached via lower(validate=False) must be validated on a
    later hit when the default auto-validate policy applies (small MACs),
    not only on an explicit validate=True request."""
    rcompile.cache_clear()
    alg = small("gemm")
    df = stt.apply_stt(alg, alg.loops, stt.stt_from_name("identity"))
    k1 = rcompile.lower(alg, df, interpret=True, validate=False)
    assert not k1.validated
    assert alg.total_macs() <= rcompile.pipeline.VALIDATE_MACS_LIMIT
    k2 = rcompile.lower(alg, df, interpret=True)         # validate=None
    assert k2 is k1 and k2.validated
    rcompile.cache_clear()


def test_lower_rejects_foreign_dataflow():
    g = small("gemm")
    mt = small("mttkrp")
    df = stt.apply_stt(mt, mt.loops[:3], stt.stt_from_name("identity"))
    with pytest.raises(ValueError):
        rcompile.lower(g, df, interpret=True)


# ---------------------------------------------------------------------------
# Tile chooser is shared between cost model and compiler
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_blocks_come_from_shared_tile_chooser(dtype):
    alg = algebra.gemm(256, 256, 256)
    df = stt.apply_stt(alg, alg.loops, stt.stt_from_name("output_stationary"))
    kern = rcompile.lower(alg, df, interpret=True, validate=False,
                          dtype=dtype)
    tile, _, util = tiling.choose_tile(alg, df, kern.cfg.pe_dims)
    per_loop = dict(zip(df.selected, tile))
    # the blocks are the chip-legal mapping of the shared tile: m rounds
    # up to the dtype's sublanes, n and k to 128 lanes (or the full dim)
    sub = stt_gemm.sublanes(dtype)
    up = lambda t, u: -(-t // u) * u
    assert kern.blocks == (up(per_loop["m"], sub), up(per_loop["n"], 128),
                           up(per_loop["k"], 128))
    assert kern.blocks == stt_gemm.legal_blocks(
        (per_loop["m"], per_loop["n"], per_loop["k"]), (256, 256, 256),
        dtype)
    # the cost model prices the STT tile itself, not the padded blocks
    rep = kern.cost_report()
    assert rep.dataflow_name == df.name
    assert rep.utilization == pytest.approx(util)


# ---------------------------------------------------------------------------
# VMEM bound on the operand-stationary strip (satellite 2)
# ---------------------------------------------------------------------------

def test_operand_stationary_vmem_check_raises():
    a = jnp.zeros((256, 32), jnp.float32)
    b = jnp.zeros((32, 32), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        stt_gemm.matmul_operand_stationary(
            a, b, bm=32, bn=32, bk=32, interpret=True,
            vmem_budget=256 * 32 * 4 - 1)


def test_stt_matmul_falls_back_to_output_stationary():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
    # budget below the (64, 32) fp32 strip -> silently uses the
    # output-stationary template; result must still be correct
    got = ops.stt_matmul(a, b, template="operand_stationary",
                         bm=32, bn=32, bk=32, interpret=True,
                         vmem_budget=64 * 32 * 4 - 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a) @ np.asarray(b),
                               rtol=1e-4, atol=1e-3)


def test_stt_matmul_within_budget_unchanged():
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
    got = ops.stt_matmul(a, b, template="operand_stationary",
                         bm=32, bn=32, bk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(a) @ np.asarray(b),
                               rtol=1e-4, atol=1e-3)


# ---------------------------------------------------------------------------
# Chip-legal blocks (the rule Mosaic enforces; tests/test_chip_compile.py
# checks it against the compiler itself)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,sub", [(jnp.float32, 8), (jnp.bfloat16, 16)])
def test_legal_blocks_round_up_to_tiles_or_full_extent(dtype, sub):
    assert stt_gemm.sublanes(dtype) == sub
    # a PE-array tile rounds up: m to sublanes, n and k to 128 lanes
    assert stt_gemm.legal_blocks((16, 16, 16), (512, 6912, 2560),
                                 dtype) == (16, 128, 128)
    assert stt_gemm.legal_blocks((3, 200, 129), (512, 6912, 2560),
                                 dtype) == (sub, 256, 256)
    # a dim the rounded block would cover is taken whole
    assert stt_gemm.legal_blocks((16, 16, 16), (1, 80, 9), dtype) == (1, 80, 9)
    # the input-stationary realization puts m on the lane axis
    assert stt_gemm.legal_blocks((16, 16, 16), (512, 512, 512), dtype,
                                 lane_m=True)[0] == 128


@pytest.mark.parametrize("template,stationary", [
    ("output_stationary", "B"), ("operand_stationary", "B"),
    ("operand_stationary", "A"), ("reduction_tree", "B")])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fit_blocks_legal_and_within_budget(template, stationary, dtype):
    dims = (512, 6912, 2560)
    budget = stt_gemm.DEFAULT_VMEM_BUDGET
    blocks = stt_gemm.fit_blocks(template, dims, (1024, 1024, 1024), dtype,
                                 budget, stationary=stationary)
    lane_m = template == "operand_stationary" and stationary == "A"
    assert stt_gemm.legal_blocks(blocks, dims, dtype,
                                 lane_m=lane_m) == blocks
    assert stt_gemm.vmem_bytes(template, dims, blocks,
                               jnp.dtype(dtype).itemsize,
                               stationary=stationary) <= budget


def test_fp32_blocks_count_the_precision_split():
    # fp32 operands contract at full precision, which Mosaic runs on
    # bf16 parts of both blocks: the estimate grows by twice the operand
    # blocks (the compiler refused a 256x256x2560 fp32 block at 20.6 MiB)
    dims, blocks = (512, 6912, 2560), (256, 256, 2560)
    f32 = stt_gemm.vmem_bytes("reduction_tree", dims, blocks, 4)
    bf16 = stt_gemm.vmem_bytes("reduction_tree", dims, blocks, 2)
    assert f32 > 20 * 2 ** 20 > stt_gemm.DEFAULT_VMEM_BUDGET > bf16
