"""Paged-cache gather: page-table indirection as a Pallas kernel.

The serving page pool (``repro.serve.pages``) stores every resident
sequence's K/V as fixed-size pages in one shared pool ``(P, page, F)``;
a per-slot page table maps slot ``c``'s logical page ``j`` to a physical
page id.  Assembling the contiguous per-slot decode view is a gather —
and a gather driven by a runtime index list is exactly the
scalar-prefetch + BlockSpec-index-map machinery the BSR kernel uses
(``pltpu.PrefetchScalarGridSpec``): the grid iterates (slot, logical
page) and the *input* index map dereferences the page table, so each
grid step DMAs one physical page straight into its view position.

``paged_gather`` is the jnp twin (a constant-free ``take`` the compiler
fuses); ``paged_gather_pallas`` is the kernel, bit-identical because both
are pure copies (tested).  CPU serving uses the jnp twin — interpret-mode
Pallas would dominate the step time — while the kernel is the TPU path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def paged_gather(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """pool (P, page, F) x page_table (C, n) int32 -> view (C, n*page, F).

    Unmapped table entries must already be clamped to a valid physical
    page (the pool reserves a scratch page); validity masking is the
    caller's job — attention masks by absolute position, so garbage rows
    contribute exactly zero.
    """
    p, page, f = pool.shape
    c, n = page_table.shape
    return jnp.take(pool, page_table.reshape(-1), axis=0).reshape(
        c, n * page, f)


def _gather_kernel(table_ref, pool_ref, out_ref):
    del table_ref  # dereferenced by the BlockSpec index maps
    out_ref[0, 0] = pool_ref[0]


def paged_gather_pallas(pool: jax.Array, page_table: jax.Array, *,
                        interpret: bool = False) -> jax.Array:
    """The Pallas twin of :func:`paged_gather`: grid (C, n), one page DMA
    per step, page table scalar-prefetched into the index maps."""

    p, page, f = pool.shape
    c, n = page_table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(c, n),
        in_specs=[
            pl.BlockSpec((1, page, f), lambda i, j, t: (t[i, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, page, f), lambda i, j, t: (i, j, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((c, n, page, f), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table.astype(jnp.int32), pool)
    return out.reshape(c, n * page, f)


def paged_scatter_token(pool: jax.Array, page_id: jax.Array,
                        offset: jax.Array, values: jax.Array) -> jax.Array:
    """Write one token row per slot back into the pool.

    pool (P, page, F); page_id / offset (C,) int32 — the physical page and
    in-page offset each slot's write position resolves to; values (C, F).
    Slots that must not write are pointed at the pool's scratch page by
    the caller (exact no-op for live data).  Returns the updated pool.
    """
    return pool.at[page_id, offset].set(values.astype(pool.dtype))
