"""Block-sparse (block-COO) GEMM Pallas kernel.

The dense STT templates (``stt_gemm.py``) iterate a *box* grid; this
kernel's grid iterates **only the nonzero blocks** of a block-sparse
operand: grid = (n-blocks, nnz), with a scalar-prefetched coordinate list
feeding the BlockSpec index maps (``pltpu.PrefetchScalarGridSpec``), so a
zero block costs neither a DMA nor an MXU pass.

Accumulation reuses the output-stationary discipline: ``coords`` is sorted
row-major, so all nonzero blocks of one output block-row are consecutive
grid steps — the fp32 scratch accumulator is initialized on a block-row
change and flushed on the last block of the row, and the k-blocks of each
output block are added in the *same ascending order* as the dense
output-stationary template.  At density 1.0 the coordinate list is the
full grid and the kernel reproduces the dense path bit-exactly (tested).

Block-rows with no nonzero block never appear in the grid; the wrapper
zeroes them from the (static) coordinate list.
"""
from __future__ import annotations

import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


#: static block-COO coordinate list: ((block_row, block_col), ...) sorted
Coords = Tuple[Tuple[int, int], ...]


def sort_coords(coords: Sequence[Sequence[int]]) -> Coords:
    """Canonical row-major, duplicate-free coordinate tuple."""
    return tuple(sorted(set(tuple(int(i) for i in c) for c in coords)))


def gather_blocks(x: jax.Array, coords: Coords, bm: int, bk: int
                  ) -> jax.Array:
    """(m, k) -> (nnz, bm, bk): the gather-of-nonzero-blocks step.

    ``coords`` is static, so under jit this is a constant-index gather the
    compiler folds into the operand layout."""
    m, k = x.shape
    g = x.reshape(m // bm, bm, k // bk, bk).transpose(0, 2, 1, 3)
    idx = np.asarray(coords, dtype=np.int32).reshape(-1, 2)
    return g[jnp.asarray(idx[:, 0]), jnp.asarray(idx[:, 1])]


def scatter_blocks(data: jax.Array, coords: Coords, m: int, k: int
                   ) -> jax.Array:
    """Inverse of :func:`gather_blocks`: reconstruct the masked dense
    operand (reference path / introspection)."""
    nnz, bm, bk = data.shape
    g = jnp.zeros((m // bm, k // bk, bm, bk), data.dtype)
    if nnz:
        idx = np.asarray(coords, dtype=np.int32).reshape(-1, 2)
        g = g.at[jnp.asarray(idx[:, 0]), jnp.asarray(idx[:, 1])].set(data)
    return g.transpose(0, 2, 1, 3).reshape(m, k)


def _row_presence(coords: Coords, n_rows: int) -> np.ndarray:
    present = np.zeros(n_rows, dtype=bool)
    for r, _ in coords:
        present[r] = True
    return present


def _bsr_kernel(coords_ref, a_ref, b_ref, o_ref, acc_ref, *, nnz: int,
                out_dtype):
    s = pl.program_id(1)
    row = coords_ref[s, 0]
    prev = jnp.where(s == 0, -1, coords_ref[jnp.maximum(s - 1, 0), 0])

    @pl.when(row != prev)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[0], b_ref[...],
                            preferred_element_type=jnp.float32)
    nxt = jnp.where(s == nnz - 1, -1,
                    coords_ref[jnp.minimum(s + 1, nnz - 1), 0])

    @pl.when(nxt != row)
    def _flush():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def bsr_matmul(sparse: jax.Array, dense: jax.Array, *, coords: Coords,
               bm: int, bk: int, bn: int, out_dtype=None,
               interpret: bool = False) -> jax.Array:
    """``C = sparse @ dense`` with ``sparse`` (m, k) block-sparse.

    ``sparse`` is passed dense-but-masked (zeros outside the pattern);
    the nonzero blocks are gathered here and the Pallas grid runs one
    (block, n-block) step per *nonzero* block only.  ``coords`` must be
    the static, row-major-sorted block-COO list with (bm, bk) blocks;
    n is padded to a ``bn`` multiple.
    """
    (m, k), n = sparse.shape, dense.shape[1]
    if m % bm or k % bk:
        raise ValueError(f"sparse operand ({m},{k}) not tiled by blocks "
                         f"({bm},{bk})")
    out_dtype = out_dtype or sparse.dtype
    coords = sort_coords(coords)
    nnz = len(coords)
    if nnz == 0:
        return jnp.zeros((m, n), out_dtype)
    bn = min(bn, n)
    pad_n = (-n) % bn
    if pad_n:
        dense = jnp.pad(dense, ((0, 0), (0, pad_n)))
    data = gather_blocks(sparse, coords, bm, bk)
    coord_arr = jnp.asarray(np.asarray(coords, np.int32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=((n + pad_n) // bn, nnz),
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda j, s, c: (s, 0, 0)),
            pl.BlockSpec((bk, bn), lambda j, s, c: (c[s, 1], j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, s, c: (c[s, 0], j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    kernel = functools.partial(_bsr_kernel, nnz=nnz, out_dtype=out_dtype)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n + pad_n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(coord_arr, data, dense)

    present = _row_presence(coords, m // bm)
    if not present.all():
        # block-rows with no nonzero block were never visited by the grid,
        # so their output memory is uninitialized — select, don't multiply
        # (0 * garbage can be nan)
        row_mask = jnp.asarray(np.repeat(present, bm))
        out = jnp.where(row_mask[:, None], out, jnp.zeros((), out_dtype))
    return out[:, :n]


def bsr_matmul_ref(sparse: jax.Array, dense: jax.Array, *, coords: Coords,
                   bm: int, bk: int) -> jax.Array:
    """jnp oracle: gather -> scatter -> dense matmul.  The gather/scatter
    round-trip asserts the pattern really covers the operand's support."""
    m, k = sparse.shape
    data = gather_blocks(sparse, sort_coords(coords), bm, bk)
    return scatter_blocks(data, sort_coords(coords), m, k) @ dense


def transpose_coords(coords: Coords) -> Coords:
    """Swap block coordinates (for the rhs-sparse transposition trick) and
    restore row-major order."""
    return sort_coords((c, r) for r, c in coords)
