"""Paged decode cache: fixed-size pages + slot→page-table indirection.

The per-call decode cache (``models/decode.py``) allocates one dense
``(L, B, S, kv)`` block per batch.  For a serving slot engine that is the
wrong shape twice over: every slot pays for the longest context whether
it uses it or not, and insert/evict would reallocate the batch.  This
module restructures the sequence-axis caches into **pages**:

* one shared pool per K/V leaf, ``(total_pages + 1, L, page, kv)`` — a
  page holds ``page_size`` token positions across *all* layers, each
  layer's rows one contiguous block, and the last physical page is a
  scratch page that absorbs writes from inactive slots and backs
  unmapped table entries;
* a host-managed page table ``(capacity, pages_per_slot)`` with a free
  list — long and short sequences draw from the same pool, so a slot
  only reserves ``ceil((prompt + max_new) / page)`` pages;
* the decode step works on the pools in place: its layer loop reads one
  layer's K/V for every slot straight from the pool through the page
  table (``kernels/paged.paged_gather``, a
  ``(capacity, seq_len, kv)`` block), and only the row each slot writes
  leaves the loop; ``scatter_written`` puts those rows into the donated
  pools.

Cache leaves without a sequence axis (SSM conv/state, static cross K/V)
are **lane pools**: the slot index is their batch axis directly.

Bit-exactness contract: a layer's read of a slot's pages yields exactly
that layer's slice of the dense cache the per-call path would hold
(unmapped positions read the scratch page, whose garbage is masked to an
exact zero contribution by the position-validity masks in
``_decode_attn``), and the step writes the same row into it before
attending, so continuous decode reproduces sequential decode
token-for-token.
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import paged as paged_kernels
from ..models.decode import LayerKV

#: decode-cache paths whose leaves carry a sequence axis (axis 2 of an
#: ``(Lx, B, S, kv)`` leaf) and are therefore paged; everything else
#: (minus "pos", which the slot engine owns) becomes a lane pool.
PAGED_PATHS = (("self", "k"), ("self", "v"), ("shared", "k"), ("shared", "v"))


def _flatten_cache(cache: Dict[str, Any]) -> Dict[Tuple[str, ...], Any]:
    flat = {}
    for k, v in cache.items():
        if k == "pos":
            continue
        if isinstance(v, dict):
            for k2, v2 in v.items():
                flat[(k, k2)] = v2
        else:
            flat[(k,)] = v
    return flat


def _nest(flat: Dict[Tuple[str, ...], Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for path, v in flat.items():
        d = out
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = v
    return out


@dataclasses.dataclass(frozen=True)
class PageLayout:
    """Static geometry of one paged cache (hashable; closed over by the
    jitted decode step, so it must not hold arrays)."""

    capacity: int
    page_size: int
    pages_per_slot: int            # logical pages in every slot's view
    total_pages: int               # physical pages (excluding scratch)
    seq_len: int                   # cache positions per slot
    #: paged leaves: path -> (stack, feat, dtype name); pool is
    #: (total_pages + 1, stack, page, feat)
    paged: Tuple[Tuple[Tuple[str, ...], Tuple[int, int, str]], ...]
    #: lane leaves: path -> (shape, dtype name); slot index is axis 1
    lanes: Tuple[Tuple[Tuple[str, ...], Tuple[Tuple[int, ...], str]], ...]

    @property
    def scratch_page(self) -> int:
        return self.total_pages

    # -- pure device-side ops (used inside the jitted decode step) -------
    def layer_inputs(self) -> Dict[Tuple[str, ...], np.ndarray]:
        """What the decode step's layer loop slices for each paged leaf:
        the layer indices ``0 .. stack - 1`` (``layer_kv`` reads them)."""
        return {path: np.arange(stack, dtype=np.int32)
                for path, (stack, _, _) in self.paged}

    def layer_kv(self, pools: Dict[Tuple[str, ...], jax.Array],
                 table: jax.Array) -> LayerKV:
        """decode_step's per-layer K/V over the pools: ``read`` gathers
        layer ``l``'s ``(capacity, seq_len, feat)`` block of every slot
        from the pool through the page table; ``keep`` returns only the
        row each slot wrote, ``(capacity, feat)``."""
        def read(group, layer):
            with jax.named_scope("cache_gather"):
                return {leaf: paged_kernels.paged_gather(
                            pools[(group, leaf)], table, layer[leaf])
                        for leaf in layer}

        def keep(group, kv, pos):
            slot_pos = pos.astype(jnp.int32) % self.seq_len
            rows = jnp.arange(self.capacity)
            return {leaf: a[rows, slot_pos] for leaf, a in kv.items()}

        return LayerKV(read=read, keep=keep)

    def scatter_written(self, pools: Dict[Tuple[str, ...], jax.Array],
                        table: jax.Array, written: Dict[Tuple[str, ...],
                                                        jax.Array],
                        pos: jax.Array, active: jax.Array
                        ) -> Dict[Tuple[str, ...], jax.Array]:
        """Write back the single token position each slot just produced.

        ``written`` are the rows decode_step kept, ``(stack, capacity,
        feat)`` per paged leaf (``layer_kv``'s ``keep``); each slot's row
        lands at ``pos % seq_len`` of its pages — inactive slots are
        pointed at the scratch page so the write is an exact no-op for
        live data.  With the pools donated the write is in place."""
        slot_pos = pos.astype(jnp.int32) % self.seq_len
        lpage = slot_pos // self.page_size
        off = slot_pos % self.page_size
        rows = jnp.arange(self.capacity)
        pid = table[rows, lpage]
        pid = jnp.where(active, pid, self.scratch_page)
        out = dict(pools)
        for path, _ in self.paged:
            out[path] = paged_kernels.paged_scatter_token(
                pools[path], pid, off, written[path].transpose(1, 0, 2))
        return out

    def freeze_inactive(self, lanes: Dict[Tuple[str, ...], jax.Array],
                        new_lanes: Dict[Tuple[str, ...], jax.Array],
                        active: jax.Array) -> Dict[Tuple[str, ...],
                                                   jax.Array]:
        """Keep inactive slots' lane state (SSM conv/state, cross K/V)
        frozen: decode ran on garbage lanes for those slots and its
        updates must not stick."""
        out = {}
        for path, old in lanes.items():
            new = new_lanes.get(path, old)
            mask = active.reshape((1, self.capacity)
                                  + (1,) * (old.ndim - 2))
            out[path] = jnp.where(mask, new.astype(old.dtype), old)
        return out


class PagedKVCache:
    """Device pools + host page table / free list for one slot engine.

    Built from the *exact* leaf shapes and dtypes the real prefill path
    produces (``jax.eval_shape`` over ``models.decode.prefill``), so
    inserting a prefilled sequence is a pure copy — no casts, no parity
    drift.  Thread-safe: alloc/free/insert take the host lock.
    """

    def __init__(self, template_cache: Dict[str, Any], *, capacity: int,
                 page_size: int, total_pages: Optional[int] = None):
        flat = _flatten_cache(template_cache)
        paged_meta, lane_meta = [], []
        seq_len = None
        for path, leaf in sorted(flat.items()):
            if path in PAGED_PATHS:
                stack, b, s, feat = leaf.shape
                assert b == capacity, (path, leaf.shape, capacity)
                if seq_len is None:
                    seq_len = s
                assert s == seq_len, (
                    f"paged leaves disagree on seq len: {path} {s} != {seq_len}")
                paged_meta.append((path, (stack, feat,
                                          jnp.dtype(leaf.dtype).name)))
            else:
                assert leaf.shape[1] == capacity, (path, leaf.shape)
                lane_meta.append((path, (tuple(leaf.shape),
                                         jnp.dtype(leaf.dtype).name)))
        if seq_len is None:
            # pure-SSM family: no sequence-axis cache at all; keep a
            # 1-page geometry so the table/step machinery stays uniform
            seq_len = page_size
        if seq_len % page_size:
            raise ValueError(f"page_size {page_size} must divide the cache "
                             f"sequence length {seq_len}")
        pages_per_slot = seq_len // page_size
        if total_pages is None:
            total_pages = capacity * pages_per_slot
        self.layout = PageLayout(
            capacity=capacity, page_size=page_size,
            pages_per_slot=pages_per_slot, total_pages=total_pages,
            seq_len=seq_len, paged=tuple(paged_meta), lanes=tuple(lane_meta))
        lay = self.layout
        self.pools = {
            path: jnp.zeros((total_pages + 1, stack, page_size, feat), dt)
            for path, (stack, feat, dt) in lay.paged}
        self.lanes = {path: jnp.zeros(shape, dt)
                      for path, (shape, dt) in lay.lanes}
        self._lock = threading.Lock()
        self._free: List[int] = list(range(total_pages))
        self._slot_pages: Dict[int, List[int]] = {}
        self.table = np.full((capacity, pages_per_slot), lay.scratch_page,
                             np.int32)
        # one fused dispatch per insert (retraced per distinct page count,
        # bounded by pages_per_slot) — the unjitted per-leaf chain costs
        # milliseconds of dispatch on every admission otherwise
        self._insert_fn = jax.jit(self._build_insert())

    # -- host-side accounting --------------------------------------------
    def pages_needed(self, context_len: int) -> int:
        """Physical pages a request spanning ``context_len`` positions
        needs; a rolling (SWA) view cycles through every logical page."""
        lay = self.layout
        n = math.ceil(min(context_len, lay.seq_len) / lay.page_size)
        return lay.pages_per_slot if context_len > lay.seq_len else n

    def can_alloc(self, context_len: int) -> bool:
        with self._lock:
            return len(self._free) >= self.pages_needed(context_len)

    def alloc(self, slot: int, context_len: int) -> bool:
        """Reserve pages for one slot; False when the pool is exhausted
        (the scheduler keeps the request queued)."""
        n = self.pages_needed(context_len)
        with self._lock:
            if slot in self._slot_pages or len(self._free) < n:
                return False
            ids = [self._free.pop() for _ in range(n)]
            self._slot_pages[slot] = ids
            self.table[slot] = self.layout.scratch_page
            self.table[slot, :n] = ids
        return True

    def free(self, slot: int) -> None:
        with self._lock:
            ids = self._slot_pages.pop(slot, [])
            self._free.extend(ids)
            self.table[slot] = self.layout.scratch_page

    @property
    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def occupancy(self) -> float:
        lay = self.layout
        with self._lock:
            return 1.0 - len(self._free) / max(lay.total_pages, 1)

    # -- insert (device) --------------------------------------------------
    def _build_insert(self):
        lay = self.layout

        def insert_fn(pools, lanes, flat, idx, slot):
            n = idx.shape[0]                        # static per trace
            out_pools = dict(pools)
            for path, (stack, feat, _) in lay.paged:
                leaf = flat[path]                   # (stack, 1, S, feat)
                pages = leaf[:, 0].reshape(
                    stack, lay.pages_per_slot, lay.page_size, feat)
                out_pools[path] = pools[path].at[idx].set(
                    pages[:, :n].transpose(1, 0, 2, 3).astype(
                        pools[path].dtype))
            out_lanes = dict(lanes)
            for path, _ in lay.lanes:
                out_lanes[path] = lanes[path].at[:, slot].set(
                    flat[path][:, 0].astype(lanes[path].dtype))
            return out_pools, out_lanes

        return insert_fn

    def insert(self, slot: int, cache: Dict[str, Any]) -> None:
        """Scatter one freshly-prefilled sequence (batch==1 cache pytree)
        into the slot's reserved pages + lane rows.  Pure copies, fused
        into one jitted dispatch; the jit cache is keyed on the page
        count (bounded by pages_per_slot), never on occupancy — the
        decode step's cache stays untouched.  The pools are not donated
        here, so each insert writes whole new pools."""
        flat = _flatten_cache(cache)
        with self._lock:
            ids = list(self._slot_pages.get(slot, ()))
        assert ids, f"slot {slot} has no pages allocated"
        idx = jnp.asarray(np.asarray(ids, np.int32))
        self.pools, self.lanes = self._insert_fn(
            self.pools, self.lanes, flat, idx, jnp.int32(slot))

    def device_table(self) -> jnp.ndarray:
        with self._lock:
            return jnp.asarray(self.table)


# ---------------------------------------------------------------------------
# mesh placement: pages through the partition solver
# ---------------------------------------------------------------------------

def solve_page_placement(cfg, layout: PageLayout,
                         axes: Tuple[str, str] = ("x", "y"),
                         shape: Tuple[int, int] = (2, 2)):
    """Solve the mesh partition for the decode-attention algebra and map
    it onto the page pools.

    Decode attention over a paged cache is a ``batched_gemv``:
    ``scores[b, s] = sum_d q[b, d] * K[b, s, d]`` with the slot x kv-head
    product as the batch dim.  The same front door that serves that
    algebra (``repro.generate``) yields the CommPlan whose
    ``plan.solve_partition`` decides which mesh axis shards the batch —
    and pages belong to slots, so the page axis of every pool shards over
    that axis.  Returns ``(PartitionSolution, PartitionSpec)``; the spec
    shards the page axis, the pools' first.
    """
    from jax.sharding import PartitionSpec as P

    from .. import api
    kv_heads = max(getattr(cfg, "n_kv_heads", 1), 1)
    acc = api.generate(
        "batched_gemv",
        bounds={"m": max(layout.capacity * kv_heads, 2),
                "k": max(getattr(cfg, "head_dim", 16), 2),
                "n": max(layout.seq_len, 2)},
        validate=False)
    sol = acc.kernel.partition_for(shape, axes)
    batch_axis = sol.batch_axis or sol.grid.get("m")
    if isinstance(batch_axis, tuple):
        batch_axis = batch_axis[0]
    spec = P(batch_axis, None, None)
    return sol, spec


def place_pools(cache: PagedKVCache, mesh, spec) -> None:
    """Shard every page pool over the mesh with the solved spec (page
    axis split over the batch-carrying mesh axis).  Divisibility caveat:
    the pool keeps its scratch page, so the page axis is padded up to a
    multiple of the axis size before placement.  The pools are placed as
    ``P(axis)``, the spelling the jitted decode step returns them in:
    ``P(axis, None, None, None)`` shards them the same way but keys a
    second compile of the step."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    axis = spec[0]
    n = (dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1)
        if axis else 1)
    for path, pool in cache.pools.items():
        pad = (-pool.shape[0]) % max(n, 1)
        if pad:
            pool = jnp.pad(pool, ((0, pad),) + ((0, 0),) * (pool.ndim - 1))
        cache.pools[path] = jax.device_put(pool,
                                           NamedSharding(mesh, P(axis)))
