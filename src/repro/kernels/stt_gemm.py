"""STT-selected Pallas GEMM templates — the paper's PE templates on TPU.

TensorLib's PE-internal modules (paper Fig. 3) map onto VMEM block residency
choices (DESIGN.md §2, level 1).  One template per stationary choice:

* ``output_stationary``  (paper (a)(a)(d), e.g. MNK-SST): the C block is the
  VMEM-resident accumulator across the reduction grid axis; A/B blocks are
  streamed by the Pallas pipeline (the software analogue of systolic
  injection — deviation D1).

* ``operand_stationary`` (paper (a)(c)(b), e.g. MNK-STS / MNK-TSS): the
  chosen operand block stays resident while the *output* streams through,
  read-modify-write accumulated in HBM via input/output aliasing — exactly
  the WS-vs-OS traffic trade the paper's dataflows expose.

* ``reduction_tree``     (paper (f)+tree, e.g. K-spatial dataflows): the
  whole reduction axis is materialized in one block and reduced inside the
  MXU pass — the combinational-adder-tree analogue.  Requires K blocks to
  fit VMEM.

Every template carries a leading **batch grid axis** (parallel, outermost):
operands may be rank 3 — ``(B, m, k) @ (B, k, n)`` — with a rank-2 operand
broadcast across the batch via its BlockSpec index map (the batch
coordinate is pinned to 0).  This is how the grid-folded algebra lowerings
(batched_gemv's batch loop, depthwise_conv's channel loop) execute exactly
the algebra's MACs: the batch iterator is a grid dimension, never
contraction padding.  Rank-2 inputs take the degenerate batch=1 path and
return rank-2 outputs, so plain GEMM call sites are unchanged.

All grids end with the revisited axis innermost, so the Mosaic pipeline
double-buffers streamed operands (compute/DMA overlap).  Block shapes
default to the MXU-aligned 128.  Mosaic accepts a block only when its
last dim is a multiple of 128 lanes and its second-to-last a multiple of
the dtype's sublane count, or when either equals the array extent;
:func:`legal_blocks` maps any requested (bm, bn, bk) onto that rule, and
``tests/test_chip_compile.py`` checks the templates against the chip's
compiler.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import epilogue as _ep


DEFAULT_BLOCK = 128
#: Mosaic's default scoped VMEM limit on TPU v5e (16 MiB of the core's
#: 128 MiB); no kernel here raises it, so every block and scratch buffer
#: of one pallas_call, double-buffered inputs included, must fit in it.
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024
#: lanes of a vreg: the last block dim is a multiple of this (or full)
LANE = 128


def sublanes(dtype) -> int:
    """Rows of ``dtype`` in one (8, 128) x 32-bit vreg tile: the multiple
    Mosaic requires of a block's second-to-last dim (8 for fp32, 16 for
    bf16, 32 for int8)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _align(block: int, full: int, unit: int) -> int:
    """Least multiple of ``unit`` >= ``block``; the full extent when that
    is not smaller (a full-extent block is always legal)."""
    b = -(-max(1, block) // unit) * unit
    return full if b >= full else b


def legal_blocks(blocks: Tuple[int, int, int], dims: Tuple[int, int, int],
                 dtype, *, lane_m: bool = False) -> Tuple[int, int, int]:
    """Map a requested ``(bm, bn, bk)`` onto the least blocks >= it that
    Mosaic accepts for a GEMM of per-slice ``dims = (m, n, k)``.

    ``n`` and ``k`` are the last dim of some operand block (B and C; A)
    in every template, so they round up to lanes; ``m`` is a row dim and
    rounds up to the dtype's sublanes.  ``lane_m`` is for the
    input-stationary template, whose transposed realization makes ``m``
    the last dim of B^T and C^T.  Padding in ``ops.stt_matmul`` covers
    dims the blocks do not divide."""
    (bm, bn, bk), (m, n, k) = blocks, dims
    return (_align(bm, m, LANE if lane_m else sublanes(dtype)),
            _align(bn, n, LANE), _align(bk, k, LANE))


def mxu_dot(a: jax.Array, b: jax.Array, dims=None) -> jax.Array:
    """The templates' contraction, accumulated in fp32.  fp32 operands
    contract at fp32 precision: Mosaic's default would round them to
    bf16 on the MXU, and a kernel asked for fp32 must not compute in
    bf16.  Narrower operands keep the default single pass.  ``dims`` are
    ``lax.dot_general`` dimension numbers (None = a plain 2-D dot)."""
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else None)
    if dims is None:
        dims = (((a.ndim - 1,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, dims, precision=precision,
                               preferred_element_type=jnp.float32)


def _validate(m, n, k, bm, bn, bk):
    if m % bm or n % bn or k % bk:
        raise ValueError(f"shape ({m},{n},{k}) not divisible by blocks "
                         f"({bm},{bn},{bk}); ops.stt_matmul pads first")


def _as_batched(a: jax.Array, b: jax.Array
                ) -> Tuple[jax.Array, jax.Array, int, bool]:
    """Lift operands to rank 3 under a shared leading batch extent.

    A rank-2 operand becomes ``(1, m, k)`` and broadcasts across the batch
    grid axis (its index map pins the batch coordinate to 0).  Returns
    ``(a3, b3, nb, squeeze)`` where ``squeeze`` says both inputs were 2-D
    and the caller should return a rank-2 output.
    """
    if a.ndim not in (2, 3) or b.ndim not in (2, 3):
        raise ValueError(f"operands must be rank 2 or 3, got "
                         f"{a.shape} x {b.shape}")
    squeeze = a.ndim == 2 and b.ndim == 2
    a3 = a if a.ndim == 3 else a[None]
    b3 = b if b.ndim == 3 else b[None]
    nb = max(a3.shape[0], b3.shape[0])
    if a3.shape[0] not in (1, nb) or b3.shape[0] not in (1, nb):
        raise ValueError(f"batch dims must match or broadcast, got "
                         f"{a.shape} x {b.shape}")
    return a3, b3, nb, squeeze


def _bspec(block: Tuple[int, int], batched: bool, imap):
    """A rank-3 BlockSpec with batch block 1: ``imap`` gives the 2-D block
    coordinate; un-batched operands pin the batch coordinate to 0."""
    if batched:
        return pl.BlockSpec((1,) + block,
                            lambda bb, *ij: (bb,) + imap(*ij))
    return pl.BlockSpec((1,) + block, lambda bb, *ij: (0,) + imap(*ij))


def _check_epilogue(epilogue: Tuple[str, ...], bias, n: int, bn: int
                    ) -> Tuple[str, ...]:
    """Validate an epilogue spec against the template geometry.  Returns
    the normalized spec; the reshaped rank-2 bias ``(1, n)`` is produced
    by :func:`_bias2d`."""
    epilogue = _ep.validate_spec(epilogue)
    if _ep.needs_bias(epilogue) and bias is None:
        raise ValueError(f"epilogue {epilogue} needs a bias operand")
    if bias is not None and not _ep.needs_bias(epilogue):
        raise ValueError(f"bias operand given but epilogue {epilogue} "
                         f"has no 'bias' op")
    if _ep.has_softmax(epilogue) and bn != n:
        raise ValueError(
            f"softmax epilogue needs one output block spanning the full "
            f"row (bn == n), got bn={bn} n={n}; a partial row cannot be "
            f"normalized block-locally")
    return epilogue


def _bias2d(bias, n: int) -> jax.Array:
    bias = jnp.asarray(bias)
    if bias.shape != (n,):
        raise ValueError(f"bias must be rank-1 of length n={n}, "
                         f"got shape {bias.shape}")
    return bias.astype(jnp.float32).reshape(1, n)


def _flush_block(acc, bias_ref, epilogue: Tuple[str, ...], out_dtype):
    """The shared flush: epilogue on the fp32 block, then cast."""
    if epilogue:
        b = bias_ref[...] if bias_ref is not None else None
        acc = _ep.apply_epilogue(acc, epilogue, bias=b)
    return acc.astype(out_dtype)


def _tile_bytes(rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (rows, cols) buffer: Mosaic lays it out in whole
    (sublanes, 128) tiles, so a narrow dim still takes a full tile."""
    sub = 8 * max(1, 4 // itemsize)
    return -(-rows // sub) * sub * (-(-cols // LANE) * LANE) * itemsize


def vmem_bytes(template: str, dims: Tuple[int, int, int],
               blocks: Tuple[int, int, int], itemsize: int, *,
               stationary: str = "B") -> int:
    """VMEM one pallas_call of ``template`` holds **per batch slice**
    (the batch grid axis is outermost, so slices reuse every buffer), in
    whole tiles: double-buffered operand, output and bias blocks, the
    template's accumulator — the (bm, bn) scratch of output-stationary or
    the (m, bn) strip of operand-stationary, whose streamed-output extent
    ``m`` is the padded per-slice one — and the fp32 values of one
    accumulate step (the dot result, the accumulator read, their sum).
    fp32 operands contract at full precision (:func:`mxu_dot`), for which
    Mosaic splits both operand blocks into bf16 parts: up to as much
    again as the double-buffered operand blocks.
    The input-stationary realization (``stationary='A'``) swaps m and n.
    Checked against the chip's compiler in tests/test_chip_compile.py."""
    (m, n, k), (bm, bn, bk) = dims, blocks
    if template == "operand_stationary" and stationary == "A":
        m, n, bm, bn = n, m, bn, bm
    if template in ("reduction_tree", "streaming"):
        bk = k
    acc = _tile_bytes(bm, bn, 4)
    operands = _tile_bytes(bm, bk, itemsize) + _tile_bytes(bk, bn, itemsize)
    total = 2 * (operands + _tile_bytes(bm, bn, itemsize)
                 + _tile_bytes(1, bn, 4))
    if itemsize == 4:
        total += 2 * operands
    if template == "output_stationary":
        total += 4 * acc
    elif template == "operand_stationary":
        total += 3 * acc + _tile_bytes(-(-m // bm) * bm, bn, 4)
    else:
        total += 2 * acc
    return total


def fit_blocks(template: str, dims: Tuple[int, int, int],
               blocks: Tuple[int, int, int], dtype, budget: int, *,
               stationary: str = "B", keep_n: bool = False
               ) -> Tuple[int, int, int]:
    """Chip-legal blocks >= ``blocks`` (:func:`legal_blocks`), then
    halved — largest first, staying legal — until :func:`vmem_bytes`
    fits ``budget``.  An operand-stationary strip that cannot fit even at
    the smallest blocks is fitted for the output-stationary template,
    which ``ops.stt_matmul`` falls back to.  ``keep_n`` pins bn (a row
    softmax needs the whole row in one block)."""
    lane_m = template == "operand_stationary" and stationary == "A"
    units = (LANE if lane_m else sublanes(dtype), LANE, LANE)
    b = list(legal_blocks(blocks, dims, dtype, lane_m=lane_m))
    free = [i for i in range(3)
            if not (i == 1 and keep_n)
            and not (i == 2 and template in ("reduction_tree",
                                             "streaming"))]
    item = jnp.dtype(dtype).itemsize
    while vmem_bytes(template, dims, tuple(b), item,
                     stationary=stationary) > budget:
        halves = [(b[i], i) for i in free
                  if _align(b[i] // 2, dims[i], units[i]) < b[i]]
        if not halves:
            if template == "operand_stationary":
                return fit_blocks("output_stationary", dims, tuple(b),
                                  dtype, budget, keep_n=keep_n)
            break
        _, i = max(halves)
        b[i] = _align(b[i] // 2, dims[i], units[i])
    return tuple(b)


# ---------------------------------------------------------------------------
# output-stationary (SST-class): C resident, A/B streamed
# ---------------------------------------------------------------------------
# Two tunable knobs (measured autotuning searches over both):
#
# * ``grid_order`` — the contraction grid order.  "mnk" (default) and
#   "nmk" keep the reduction innermost so the scratch accumulator stays
#   live across k-steps and Mosaic double-buffers the streamed A/B blocks
#   (the double-buffered operand-streaming variants differ in which
#   operand's blocks get the streaming reuse).  "kmn"/"knm" hoist the
#   reduction outermost — the output block is revisited and accumulated
#   in place instead, which trades accumulator residency for streaming
#   the full C through VMEM once per k-step.
#
# * ``accum`` — "scratch" accumulates in an fp32 VMEM scratch buffer and
#   casts once at the final k-step (exact for bf16 inputs); "inplace"
#   accumulates directly in the output block *in the output dtype* — the
#   bf16-direct accumulation strategy (cheaper residency, lossier sums).
#   k-outer grid orders require "inplace" (one scratch block cannot
#   survive a full sweep of the other axes between k-steps).

#: valid output-stationary grid orders (batch axis is always outermost)
OS_GRID_ORDERS = ("mnk", "nmk", "kmn", "knm")
ACCUM_MODES = ("scratch", "inplace")


def _os_kernel_scratch(a_ref, b_ref, *rest, n_k: int, k_axis: int,
                       out_dtype, epilogue: Tuple[str, ...] = ()):
    bias_ref = rest[0] if len(rest) == 3 else None
    o_ref, acc_ref = rest[-2], rest[-1]
    @pl.when(pl.program_id(k_axis) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
    acc_ref[...] += mxu_dot(a_ref[0], b_ref[0])
    @pl.when(pl.program_id(k_axis) == n_k - 1)
    def _flush():
        o_ref[0] = _flush_block(acc_ref[...], bias_ref, epilogue, out_dtype)


def _os_kernel_inplace(a_ref, b_ref, *rest, n_k: int, k_axis: int,
                       out_dtype, epilogue: Tuple[str, ...] = ()):
    bias_ref = rest[0] if len(rest) == 2 else None
    o_ref = rest[-1]
    @pl.when(pl.program_id(k_axis) == 0)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])
    o_ref[0] += mxu_dot(a_ref[0], b_ref[0]).astype(out_dtype)
    if epilogue:
        # the accumulated block is final at the last k-step; the epilogue
        # reads it back at fp32 (the in-place strategy's usual precision
        # trade applies to the pre-epilogue sums)
        @pl.when(pl.program_id(k_axis) == n_k - 1)
        def _epi():
            o_ref[0] = _flush_block(o_ref[0].astype(jnp.float32), bias_ref,
                                    epilogue, out_dtype)


def matmul_output_stationary(a: jax.Array, b: jax.Array, *,
                             bm: int = DEFAULT_BLOCK, bn: int = DEFAULT_BLOCK,
                             bk: int = DEFAULT_BLOCK,
                             grid_order: str = "mnk",
                             accum: str = "scratch",
                             out_dtype=None, interpret: bool = False,
                             epilogue: Tuple[str, ...] = (),
                             bias: Optional[jax.Array] = None
                             ) -> jax.Array:
    if grid_order == "default":
        grid_order = "mnk"
    elif grid_order in ("mn", "nm"):    # reduction-tree spelling: k innermost
        grid_order += "k"
    if grid_order not in OS_GRID_ORDERS:
        raise ValueError(f"grid_order must be one of {OS_GRID_ORDERS}, "
                         f"got {grid_order!r}")
    if accum not in ACCUM_MODES:
        raise ValueError(f"accum must be one of {ACCUM_MODES}, "
                         f"got {accum!r}")
    if accum == "scratch" and grid_order[-1] != "k":
        raise ValueError(
            f"grid_order {grid_order!r} revisits the output block between "
            f"k-steps, which a single scratch accumulator cannot survive; "
            f"use accum='inplace' for k-outer orders")
    a3, b3, nb, squeeze = _as_batched(a, b)
    (m, k), n = a3.shape[1:], b3.shape[2]
    _validate(m, n, k, bm, bn, bk)
    epilogue = _check_epilogue(epilogue, bias, n, bn)
    out_dtype = out_dtype or a.dtype
    n_k = k // bk
    counts = {"m": m // bm, "n": n // bn, "k": n_k}
    ix = {c: i for i, c in enumerate(grid_order)}   # imap arg position
    k_axis = 1 + ix["k"]                            # grid axis incl. batch
    if accum == "scratch":
        kernel = functools.partial(_os_kernel_scratch, n_k=n_k,
                                   k_axis=k_axis, out_dtype=out_dtype,
                                   epilogue=epilogue)
        scratch = [pltpu.VMEM((bm, bn), jnp.float32)]
    else:
        kernel = functools.partial(_os_kernel_inplace, n_k=n_k,
                                   k_axis=k_axis, out_dtype=out_dtype,
                                   epilogue=epilogue)
        scratch = []
    semantics = ("parallel",) + tuple(
        "arbitrary" if c == "k" else "parallel" for c in grid_order)
    in_specs = [_bspec((bm, bk), a3.shape[0] > 1,
                       lambda *ids: (ids[ix["m"]], ids[ix["k"]])),
                _bspec((bk, bn), b3.shape[0] > 1,
                       lambda *ids: (ids[ix["k"]], ids[ix["n"]]))]
    inputs = [a3, b3]
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            (1, bn), lambda bb, *ids: (0, ids[ix["n"]])))
        inputs.append(_bias2d(bias, n))
    out = pl.pallas_call(
        kernel,
        grid=(nb,) + tuple(counts[c] for c in grid_order),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, bm, bn),
            lambda bb, *ids: (bb, ids[ix["m"]], ids[ix["n"]])),
        out_shape=jax.ShapeDtypeStruct((nb, m, n), out_dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=interpret,
    )(*inputs)
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# operand-stationary (STS/TSS-class): operand resident, C strip accumulator
# ---------------------------------------------------------------------------
# On TPU there is no inter-PE wire to stream partial sums through (deviation
# D1), so the streamed-output systolic module (b) becomes a VMEM *strip*
# accumulator: while the stationary operand block is pinned, the entire
# output strip it contributes to lives in VMEM and the other operand streams
# past it.  VMEM bound: strip_len * block * 4B per batch slice (checked).

def _ws_kernel(a_ref, b_ref, *rest, n_k: int, bm: int, out_dtype,
               epilogue: Tuple[str, ...] = ()):
    bias_ref = rest[0] if len(rest) == 3 else None
    o_ref, acc_ref = rest[-2], rest[-1]
    kk, i = pl.program_id(2), pl.program_id(3)
    sl = pl.ds(i * bm, bm)
    @pl.when(kk == 0)
    def _init():
        acc_ref[sl, :] = jnp.zeros_like(acc_ref[sl, :])
    acc_ref[sl, :] += mxu_dot(a_ref[0], b_ref[0])
    @pl.when(kk == n_k - 1)
    def _flush():
        o_ref[0] = _flush_block(acc_ref[sl, :], bias_ref, epilogue,
                                out_dtype)


def matmul_operand_stationary(a: jax.Array, b: jax.Array, *,
                              stationary: str = "B",
                              bm: int = DEFAULT_BLOCK, bn: int = DEFAULT_BLOCK,
                              bk: int = DEFAULT_BLOCK,
                              out_dtype=None, interpret: bool = False,
                              vmem_budget: Optional[int] = DEFAULT_VMEM_BUDGET,
                              epilogue: Tuple[str, ...] = (),
                              bias: Optional[jax.Array] = None
                              ) -> jax.Array:
    """``stationary='B'``: grid (batch, n, k, m) keeps the B block pinned
    while A streams (weight-stationary);  ``stationary='A'`` is the
    symmetric input-stationary template (implemented by transposition
    symmetry: C^T = B^T A^T with B^T stationary, batch dims untouched).

    The strip accumulator scratch is (m, bn) fp32 per batch slice — a VMEM
    residency that grows with the *full* per-slice M extent, not a block
    (the batch grid axis is outermost, so slices reuse one strip).
    ``vmem_budget`` bounds it with the blocks (:func:`vmem_bytes`; pass
    None to skip the check);
    ``ops.stt_matmul`` auto-falls-back to the output-stationary template
    instead of tripping this error.
    """
    if stationary == "A":
        if epilogue:
            # the transposition realization swaps the m/n axes, so a
            # last-axis epilogue would act on the wrong dimension;
            # ops.stt_matmul reroutes epilogue'd calls to the
            # output-stationary template before reaching here
            raise ValueError("epilogue fusion is not supported on the "
                             "input-stationary (stationary='A') "
                             "transposition path")
        out = matmul_operand_stationary(
            jnp.swapaxes(b, -1, -2), jnp.swapaxes(a, -1, -2),
            stationary="B", bm=bn, bn=bm, bk=bk,
            out_dtype=out_dtype, interpret=interpret,
            vmem_budget=vmem_budget)
        return jnp.swapaxes(out, -1, -2)
    if stationary != "B":
        raise ValueError(stationary)
    a3, b3, nb, squeeze = _as_batched(a, b)
    (m, k), n = a3.shape[1:], b3.shape[2]
    _validate(m, n, k, bm, bn, bk)
    epilogue = _check_epilogue(epilogue, bias, n, bn)
    need = vmem_bytes("operand_stationary", (m, n, k), (bm, bn, bk),
                      a3.dtype.itemsize)
    if vmem_budget is not None and need > vmem_budget:
        raise ValueError(
            f"operand-stationary kernel needs {need} bytes of VMEM per "
            f"batch slice (strip (m={m}) x (bn={bn}) x 4B plus blocks) "
            f"but the budget is {vmem_budget}; shrink bn, tile m outside "
            f"the kernel, or use the output_stationary template "
            f"(ops.stt_matmul falls back automatically)")
    out_dtype = out_dtype or a.dtype
    n_k = k // bk
    kernel = functools.partial(_ws_kernel, n_k=n_k, bm=bm,
                               out_dtype=out_dtype, epilogue=epilogue)
    in_specs = [_bspec((bm, bk), a3.shape[0] > 1,
                       lambda j, kk, i: (i, kk)),
                # B block constant along the inner m axis -> VMEM-resident
                _bspec((bk, bn), b3.shape[0] > 1,
                       lambda j, kk, i: (kk, j))]
    inputs = [a3, b3]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn),
                                     lambda bb, j, kk, i: (0, j)))
        inputs.append(_bias2d(bias, n))
    out = pl.pallas_call(
        kernel,
        grid=(nb, n // bn, n_k, m // bm),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn),
                               lambda bb, j, kk, i: (bb, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
    )(*inputs)
    return out[0] if squeeze else out


# ---------------------------------------------------------------------------
# reduction-tree (K-spatial class): full-K blocks, single MXU reduction
# ---------------------------------------------------------------------------

def _rt_kernel(a_ref, b_ref, *rest, out_dtype,
               epilogue: Tuple[str, ...] = ()):
    bias_ref = rest[0] if len(rest) == 2 else None
    o_ref = rest[-1]
    acc = mxu_dot(a_ref[0], b_ref[0])
    o_ref[0] = _flush_block(acc, bias_ref, epilogue, out_dtype)


#: valid reduction-tree grid orders (no k axis: the whole reduction runs
#: inside one MXU pass)
RT_GRID_ORDERS = ("mn", "nm")


def matmul_reduction_tree(a: jax.Array, b: jax.Array, *,
                          bm: int = DEFAULT_BLOCK, bn: int = DEFAULT_BLOCK,
                          grid_order: str = "mn",
                          out_dtype=None, interpret: bool = False,
                          epilogue: Tuple[str, ...] = (),
                          bias: Optional[jax.Array] = None
                          ) -> jax.Array:
    if grid_order == "default":
        grid_order = "mn"
    if grid_order not in RT_GRID_ORDERS:
        raise ValueError(f"grid_order must be one of {RT_GRID_ORDERS}, "
                         f"got {grid_order!r}")
    a3, b3, nb, squeeze = _as_batched(a, b)
    (m, k), n = a3.shape[1:], b3.shape[2]
    _validate(m, n, k, bm, bn, k)
    epilogue = _check_epilogue(epilogue, bias, n, bn)
    out_dtype = out_dtype or a.dtype
    counts = {"m": m // bm, "n": n // bn}
    ix = {c: i for i, c in enumerate(grid_order)}
    kernel = functools.partial(_rt_kernel, out_dtype=out_dtype,
                               epilogue=epilogue)
    in_specs = [_bspec((bm, k), a3.shape[0] > 1,
                       lambda *ids: (ids[ix["m"]], 0)),
                _bspec((k, bn), b3.shape[0] > 1,
                       lambda *ids: (0, ids[ix["n"]]))]
    inputs = [a3, b3]
    if bias is not None:
        in_specs.append(pl.BlockSpec(
            (1, bn), lambda bb, *ids: (0, ids[ix["n"]])))
        inputs.append(_bias2d(bias, n))
    out = pl.pallas_call(
        kernel,
        grid=(nb,) + tuple(counts[c] for c in grid_order),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, bm, bn), lambda bb, *ids: (bb, ids[ix["m"]], ids[ix["n"]])),
        out_shape=jax.ShapeDtypeStruct((nb, m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(*inputs)
    return out[0] if squeeze else out


TEMPLATES = {
    "output_stationary": matmul_output_stationary,
    "operand_stationary": matmul_operand_stationary,
    "reduction_tree": matmul_reduction_tree,
    # 'streaming' (all-unicast) has no reuse to exploit: realize as
    # reduction-tree (single pass, no residency) — documented equivalence.
    "streaming": matmul_reduction_tree,
}
