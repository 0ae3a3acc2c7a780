"""Grid-folded batch execution (ISSUE 4): the batched templates execute
exactly the algebra's MACs and are bit-exact against the retired
block-diagonal GEMM-ization, kept as a test-only oracle in kernels/ref.py.

Integer-valued operands make every fp path exact (products and fp32
accumulations are integers far below 2^24), so "bit-exact" is meaningful
across dtypes: both paths compute the same integers and round identically
on the final cast.
"""
import numpy as np
import pytest
import jax.numpy as jnp

from repro import compile as rcompile
from repro.core import algebra, stt, tiling
from repro.core.algebra import Sparsity
from repro.core.costmodel import PaperCycleModel
from repro.kernels import ops, ref

NAMED_STTS = ("identity", "output_stationary", "weight_stationary",
              "input_stationary")

#: default (divisible) and deliberately awkward (non-divisible) bounds
GEMV_BOUNDS = dict(m=4, k=8, n=8)
GEMV_RAGGED = dict(m=5, k=7, n=6)
DW_BOUNDS = dict(k=8, y=6, x=6, p=3, q=3)
DW_RAGGED = dict(k=5, y=5, x=5, p=2, q=2)


def _bitwise_equal(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype, (got.dtype, want.dtype)
    assert got.shape == want.shape, (got.shape, want.shape)
    np.testing.assert_array_equal(
        got.astype(np.float64), want.astype(np.float64))


# ---------------------------------------------------------------------------
# Bit-exactness vs the retired block-diagonal oracle, across dtypes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", NAMED_STTS)
def test_batched_gemv_bit_exact_vs_blockdiag(kind, dtype):
    alg = algebra.batched_gemv(**GEMV_BOUNDS)
    df = stt.apply_stt(alg, alg.loops[:3], stt.stt_from_name(kind))
    kern = rcompile.lower(alg, df, interpret=True, dtype=dtype,
                          validate=False)
    operands = alg.random_operands(seed=11)
    got = kern(operands)
    want = ref.batched_gemv_blockdiag_ref(
        jnp.asarray(operands["A"]).astype(dtype),
        jnp.asarray(operands["B"]).astype(dtype))
    _bitwise_equal(got, want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("kind", NAMED_STTS)
def test_depthwise_bit_exact_vs_blockdiag(kind, dtype):
    alg = algebra.depthwise_conv(**DW_BOUNDS)
    df = stt.apply_stt(alg, alg.loops[:3], stt.stt_from_name(kind))
    kern = rcompile.lower(alg, df, interpret=True, dtype=dtype,
                          validate=False)
    operands = alg.random_operands(seed=13)
    got = kern(operands)
    want = ref.depthwise_blockdiag_ref(
        jnp.asarray(operands["A"]).astype(dtype),
        jnp.asarray(operands["B"]).astype(dtype),
        y=DW_BOUNDS["y"], x=DW_BOUNDS["x"])
    _bitwise_equal(got, want)


def test_blockdiag_oracle_matches_loop_nest():
    """The oracle itself must reproduce alg.reference — otherwise the
    bit-exactness tests above would prove nothing."""
    bg = algebra.batched_gemv(**GEMV_BOUNDS)
    ops_bg = bg.random_operands(seed=2)
    want = bg.reference(ops_bg)
    got = ref.batched_gemv_blockdiag_ref(
        jnp.asarray(ops_bg["A"], jnp.float32),
        jnp.asarray(ops_bg["B"], jnp.float32))
    np.testing.assert_array_equal(np.asarray(got).astype(np.int64), want)

    dw = algebra.depthwise_conv(**DW_BOUNDS)
    ops_dw = dw.random_operands(seed=2)
    got = ref.depthwise_blockdiag_ref(
        jnp.asarray(ops_dw["A"], jnp.float32),
        jnp.asarray(ops_dw["B"], jnp.float32),
        y=DW_BOUNDS["y"], x=DW_BOUNDS["x"])
    np.testing.assert_array_equal(np.asarray(got).astype(np.int64),
                                  dw.reference(ops_dw))


# ---------------------------------------------------------------------------
# _block_diag_rows is gone from the execution path
# ---------------------------------------------------------------------------

def test_block_diag_retired_from_lowering():
    from repro.compile import lowering
    assert not hasattr(lowering, "_block_diag_rows")
    for name in ("batched_gemv", "depthwise_conv"):
        form = rcompile.lower_form(algebra.get_algebra(name))
        assert form.batch, name           # batch grid dim, not zero padding
        assert form.lhs_batched and form.rhs_batched


# ---------------------------------------------------------------------------
# Executed MACs == algebra MACs across the whole registry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", NAMED_STTS)
@pytest.mark.parametrize("name", sorted(algebra.PAPER_ALGEBRAS))
def test_registry_executed_mac_ratio_is_one(name, kind):
    alg = algebra.get_algebra(name)
    df = stt.apply_stt(alg, alg.loops[:3], stt.stt_from_name(kind))
    rep = PaperCycleModel().evaluate(alg, df)
    assert rep.executed_macs == alg.total_macs()
    assert rep.executed_mac_ratio == 1.0


def test_lowered_form_executed_macs_matches_algebra():
    for name in sorted(algebra.PAPER_ALGEBRAS):
        alg = algebra.get_algebra(name)
        form = rcompile.lower_form(alg)
        assert form.executed_macs == alg.total_macs(), name


def test_masked_dense_sparse_reports_honest_ratio():
    """A sparse pattern with no structured 2-D image runs masked-dense
    *within the kept batch slices*: in-slice zero blocks still execute,
    and the ratio must report that gap, not hide it.  (All-zero slices
    themselves are skipped since the per-slice mapping — ISSUE 5.)"""
    dw = algebra.depthwise_conv(**DW_BOUNDS)
    # every channel keeps only the q=0 column of its window: no slice is
    # all-zero (nothing to skip), but 2/3 of each slice's MACs are masked
    sp = Sparsity((4, 3, 1), ((0, 0, 0), (1, 0, 0)))
    dws = dw.with_sparsity(B=sp)
    form = rcompile.lower_form(dws)
    assert form.sparse is None and form.masked_sparse == ("B",)
    assert form.batch_keep is None
    rep = PaperCycleModel().evaluate(dws, rcompile.default_dataflow(dws))
    assert rep.executed_mac_ratio > 1.0


def test_batched_sparse_slice_skip_closes_ratio():
    """A pattern whose zero blocks cover whole batch slices is captured
    completely by the per-slice mapping: the kernel skips those slices
    and the ratio returns to 1.0 (previously batch/kept x too high)."""
    dw = algebra.depthwise_conv(**DW_BOUNDS)
    sp = Sparsity.random((8, 3, 3), (4, 3, 3), density=0.5, seed=0)
    dws = dw.with_sparsity(B=sp)
    form = rcompile.lower_form(dws)
    assert form.batch_keep is not None and form.batch == (4,)
    rep = PaperCycleModel().evaluate(dws, rcompile.default_dataflow(dws))
    assert rep.executed_mac_ratio == pytest.approx(1.0)
    kern = rcompile.lower(dws, interpret=True)
    assert kern.validated


# ---------------------------------------------------------------------------
# Non-divisible batch/channel and per-slice shapes pad correctly
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", NAMED_STTS)
def test_batched_gemv_ragged_bounds(kind):
    alg = algebra.batched_gemv(**GEMV_RAGGED)
    df = stt.apply_stt(alg, alg.loops[:3], stt.stt_from_name(kind))
    kern = rcompile.lower(alg, df, interpret=True)
    assert kern.validated
    operands = alg.random_operands(seed=3)
    got = np.asarray(kern(operands)).round().astype(np.int64)
    np.testing.assert_array_equal(got, alg.reference(operands))


@pytest.mark.parametrize("kind", NAMED_STTS)
def test_depthwise_ragged_bounds(kind):
    alg = algebra.depthwise_conv(**DW_RAGGED)
    df = stt.apply_stt(alg, alg.loops[:3], stt.stt_from_name(kind))
    kern = rcompile.lower(alg, df, interpret=True)
    assert kern.validated
    operands = alg.random_operands(seed=4)
    got = np.asarray(kern(operands)).round().astype(np.int64)
    np.testing.assert_array_equal(got, alg.reference(operands))


@pytest.mark.parametrize("template", ["output_stationary",
                                      "operand_stationary",
                                      "reduction_tree"])
def test_stt_matmul_batched_ragged_blocks(template):
    """Per-slice dims that don't divide the blocks pad through
    ops.stt_matmul; the batch dim itself never needs padding."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((5, 13, 11)).astype(np.float32)
    b = rng.standard_normal((5, 11, 9)).astype(np.float32)
    got = ops.stt_matmul(jnp.asarray(a), jnp.asarray(b), template=template,
                         bm=4, bn=4, bk=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.einsum("bmk,bkn->bmn", a, b),
                               rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize("template", ["output_stationary",
                                      "operand_stationary",
                                      "reduction_tree"])
def test_stt_matmul_broadcasts_unbatched_operand(template):
    """A rank-2 operand broadcasts across the batch grid axis via its
    index map — one template instance serves batched x shared shapes."""
    rng = np.random.default_rng(1)
    a = rng.standard_normal((3, 16, 8)).astype(np.float32)
    b = rng.standard_normal((8, 12)).astype(np.float32)
    got = ops.stt_matmul(jnp.asarray(a), jnp.asarray(b), template=template,
                         bm=8, bn=4, bk=4, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.einsum("bmk,kn->bmn", a, b),
                               rtol=1e-4, atol=1e-3)


def test_batched_operand_stationary_vmem_check_is_per_slice():
    """The strip accumulator budget sees the per-slice m extent, not
    batch x it: a batch of strips each within budget must not trip the
    fallback-triggering check in the template itself."""
    from repro.kernels import stt_gemm
    a = jnp.zeros((8, 32, 16), jnp.float32)
    b = jnp.zeros((8, 16, 16), jnp.float32)
    # budget exactly one slice's kernel ((32, 16) fp32 strip + blocks):
    # per-slice fits, batch x would not — must succeed
    one_slice = stt_gemm.vmem_bytes("operand_stationary", (32, 16, 16),
                                    (16, 16, 16), 4)
    assert one_slice < stt_gemm.vmem_bytes(
        "operand_stationary", (8 * 32, 16, 16), (16, 16, 16), 4)
    out = stt_gemm.matmul_operand_stationary(
        a, b, bm=16, bn=16, bk=16, interpret=True, vmem_budget=one_slice)
    assert out.shape == (8, 32, 16)
    with pytest.raises(ValueError, match="VMEM"):
        stt_gemm.matmul_operand_stationary(
            a, b, bm=16, bn=16, bk=16, interpret=True,
            vmem_budget=one_slice - 1)


# ---------------------------------------------------------------------------
# Batch never inflates the contraction in the shared tile chooser
# ---------------------------------------------------------------------------

def test_form_blocks_exclude_batch_loops():
    alg = algebra.batched_gemv(m=64, k=32, n=32)
    df = rcompile.default_dataflow(alg)
    form = rcompile.lower_form(alg)
    bm, bn, bk = tiling.form_blocks(alg, df, form)
    assert bm == 1                       # per-slice gemv row
    assert bk <= form.k                  # contraction ends at k, not m*k
    assert form.k == 32


# ---------------------------------------------------------------------------
# _attach_sparsity tie-break: lowest density wins, name breaks ties
# ---------------------------------------------------------------------------

def test_attach_sparsity_lowest_density_wins():
    g = algebra.gemm(16, 16, 16)
    dense_ish = Sparsity.random((16, 16), (4, 4), density=0.75, seed=0)
    sparse_st = Sparsity.random((16, 16), (4, 4), density=0.25, seed=1)
    form = rcompile.lower_form(g.with_sparsity(A=dense_ish, B=sparse_st))
    assert form.sparse is not None and form.sparse.tensor == "B"
    assert form.masked_sparse == ("A",)


def test_attach_sparsity_tie_breaks_by_tensor_name():
    g = algebra.gemm(16, 16, 16)
    # two distinct patterns with identical density: 4 of 16 blocks each
    sp_a = Sparsity((4, 4), ((0, 0), (1, 1), (2, 2), (3, 3)))
    sp_b = Sparsity((4, 4), ((0, 1), (1, 2), (2, 3), (3, 0)))
    form = rcompile.lower_form(g.with_sparsity(A=sp_a, B=sp_b))
    assert form.sparse is not None
    assert form.sparse.tensor == "A"     # alphabetical on equal density
    assert form.masked_sparse == ("B",)
    # ...and the choice is symmetric in the attachment order
    form2 = rcompile.lower_form(g.with_sparsity(B=sp_b, A=sp_a))
    assert form2.sparse.tensor == "A"
