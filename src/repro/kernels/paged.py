"""Paged-cache reads and row writes: the page-table indirection.

The serving page pool (``repro.serve.pages``) stores every resident
sequence's K/V as fixed-size pages in one shared pool per leaf,
``(P, L, page, F)``: a physical page holds ``page`` positions of every
layer, each layer's rows a contiguous block.  A per-slot page table maps
slot ``c``'s logical page ``j`` to a physical page id.  The decode step
reads one layer's contiguous per-slot view (``paged_gather``: one XLA
gather whose slices are whole pages of one layer, indexed on the major
axes only) and writes back one row per slot and layer
(``paged_scatter_token``).  A Pallas twin of the gather (one page DMA
per grid step through a scalar-prefetched table) read a layer of
danube's pool in 8.2 ms a step against the XLA gather's 6.0 (TPU v5e),
and was dropped.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_gather(pool: jax.Array, page_table: jax.Array,
                 layer) -> jax.Array:
    """pool (P, L, page, F) x page_table (C, n) int32 x layer (may be
    traced) -> that layer's view (C, n*page, F).

    Unmapped table entries must already be clamped to a valid physical
    page (the pool reserves a scratch page); validity masking is the
    caller's job — attention masks by absolute position, so garbage rows
    contribute exactly zero.
    """
    page, f = pool.shape[-2:]
    c, n = page_table.shape
    return pool[page_table.reshape(-1), layer].reshape(c, n * page, f)


def paged_scatter_token(pool: jax.Array, page_id: jax.Array,
                        offset: jax.Array, values: jax.Array) -> jax.Array:
    """Write one token row per slot back into the pool.

    pool (P, page, F), or (P, L, page, F); page_id / offset (C,) int32 —
    the physical page and in-page offset each slot's write position
    resolves to; values (C, F), or (C, L, F) with every layer's row.
    Slots that must not write are pointed at the pool's scratch page by
    the caller (exact no-op for live data).  Returns the updated pool (in
    place when the caller donated it).

    A ``(P, L, page, F)`` pool is written as ``(P * L, page, F)``, a row
    per (slot, layer) indexed on the leading axis: a scatter whose window
    spans the layer axis makes XLA relay the whole pool out and back.
    """
    p, *lead, page, f = pool.shape
    layers = math.prod(lead)
    rows = (page_id.astype(jnp.int32)[:, None] * layers
            + jnp.arange(layers, dtype=jnp.int32)).reshape(-1)
    offs = jnp.repeat(offset, layers)
    flat = pool.reshape(p * layers, page, f).at[rows, offs].set(
        values.reshape(-1, f).astype(pool.dtype))
    return flat.reshape(pool.shape)
