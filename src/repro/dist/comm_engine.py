"""Generic CommPlan interpreter: any generated CommPlan -> shard_map.

``compile_comm_plan`` takes the CommPlan that ``plan.comm_plan_for``
generated from the dataflow classification plus the algebra's
:class:`~repro.compile.LoweredForm`, and emits a shard_map program over a
2-D device mesh — the chip-level realization of the paper's claim that one
transformation matrix yields the complete accelerator, module selection
*and connection*.

Since the unified-partition refactor this module contains **no per-strategy
shard/replicate decisions**: every placement, motion and degradation comes
from ``plan.solve_partition`` — the :class:`~repro.core.plan.
PartitionSolution` maps every LoweredForm dim (batch, m, n, k, and sparse
block coordinates) onto mesh axes once, and this module only materializes
it:

    * stored layouts      -> shard_map ``PartitionSpec``s (one per side),
    * ``all_gather`` motion -> ``jax.lax.all_gather(..., tiled=True)``,
    * ``ppermute_ring`` motion -> rotation schedules in ``fori_loop``s,
    * batch grid dims     -> sharded over their mesh axis (replication only
      as the solver's degenerate solution),
    * compressed sides    -> per-device BSR payload + block-COO coordinate
      lists shipped through the same gathers/rings (never densified),
    * input-systolic dt   -> the staggered accumulate-rotate schedule
      (``k_spatial_stagger``): device r adds its partial for output chunk
      ``(r - t) mod S`` at step t, so the mobile tensor stores 1/S per
      device instead of a full replica.

The classic named schedules fall out as special cases (and are kept as
test oracles in ``engine.py``): SUMMA is gemm x the MMT dataflow, Cannon
is gemm x SST, ring-reduce is gemm x a K-spatial STT.

These run on fake CPU devices (``XLA_FLAGS=--xla_force_host_platform_
device_count=N``) in tests and on real slices unchanged; degenerate
meshes (1x1, 1xN, Nx1) and non-divisible shard shapes are handled by the
same padding every strategy applies.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core import plan as plan_mod
from ..core.plan import CommPlan, PartitionSolution, TensorPartition

try:  # LoweredForm only needed for isinstance-free typing
    from ..compile.lowering import LoweredForm
except Exception:  # pragma: no cover - circular-import guard
    LoweredForm = "LoweredForm"  # type: ignore


def _pad_dim(x: jax.Array, axis: int, mult: int) -> jax.Array:
    """Pad ``axis`` (negative axes address from the last dim, so the same
    call works on 2-D operands and batched rank-3 ones) up to ``mult``."""
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _skew(m: jax.Array, s: int, roll_axis: int, block_axis: int) -> jax.Array:
    """Cannon's initial alignment: roll block row/col ``i`` of ``m`` by
    ``i`` k-blocks along ``roll_axis``."""
    kb = m.shape[roll_axis] // s
    blocks = jnp.split(m, s, axis=block_axis)
    rolled = [jnp.roll(blk, -i * kb, axis=roll_axis)
              for i, blk in enumerate(blocks)]
    return jnp.concatenate(rolled, axis=block_axis)


def _contract(l: jax.Array, r: jax.Array) -> jax.Array:
    """out[..., m, n] = l[..., m, k] @ r[..., k, n] in fp32, broadcasting
    a leading batch dim carried by either operand."""
    return jnp.einsum("...mk,...kn->...mn", l, r,
                      preferred_element_type=jnp.float32)


def _acc_init(l: jax.Array, r: jax.Array) -> jax.Array:
    """fp32 accumulator matching ``_contract(l, r)``'s shape."""
    bshape = jnp.broadcast_shapes(l.shape[:-2], r.shape[:-2])
    return jnp.zeros((*bshape, l.shape[-2], r.shape[-1]), jnp.float32)


def _ring_perm(size: int) -> list:
    """Rotate data one hop backwards: position r receives block r+1."""
    return [(j, (j - 1) % size) for j in range(size)]


def _fwd_perm(size: int) -> list:
    """Rotate data one hop forwards: position r sends to r+1 (the
    staggered accumulator schedule's direction)."""
    return [(j, (j + 1) % size) for j in range(size)]


def _spec_of(tp: TensorPartition) -> P:
    """The stored layout of one side, as a shard_map PartitionSpec."""
    return P(*tp.placement)


@dataclasses.dataclass(frozen=True)
class MeshProgram:
    """A compiled CommPlan: the shard_map specs + ring structure chosen
    for one (CommPlan, LoweredForm, mesh) triple.  ``fn`` maps *global*
    (lhs2d, rhs2d) -> global out; ``solution`` is the partition the
    program materializes (introspection for tests, docs and the cost
    model)."""

    strategy: str                       # summa | cannon | ring | k_spatial...
    in_specs: Tuple[P, P]
    out_spec: P
    ring_axes: Tuple[str, ...]
    pads: Tuple[int, int, int]          # padding multiples for (m, n, k)
    solution: PartitionSolution = None
    fn: Callable[[jax.Array, jax.Array], jax.Array] = (
        dataclasses.field(repr=False, default=None))

    def __call__(self, lhs: jax.Array, rhs: jax.Array) -> jax.Array:
        return self.fn(lhs, rhs)

    def footprint(self, form: "LoweredForm", elem_bytes: int = 4
                  ) -> Dict[str, float]:
        """Per-device stored bytes per side (the solver's accounting)."""
        return self.solution.per_device_bytes(form, elem_bytes)


def compile_comm_plan(comm: CommPlan, form: "LoweredForm", mesh: Mesh,
                      dtype=jnp.float32, *, shard_batch: bool = True,
                      sparse: str = "auto") -> MeshProgram:
    """Compile a generated CommPlan into an executable mesh program.

    The returned program computes ``out[b?, m, n] = lhs @ rhs`` (the
    algebra's LoweredForm view) with every inter-chip transfer prescribed
    by the :class:`~repro.core.plan.PartitionSolution` the plan solves to:
    batch grid dims shard a mesh axis, structured block-sparse operands
    ship compressed, and systolic plans run their rotation schedules.

    ``shard_batch=False`` requests the replicating-batch baseline and
    ``sparse="dense"`` the masked-dense shipping baseline (both kept for
    footprint A/B comparisons); ``sparse="auto"``/``"bsr"`` ship the
    structured operand compressed whenever the form has one.
    """
    if len(mesh.axis_names) != 2:
        raise ValueError(f"comm_engine needs a 2-D mesh, got axes "
                         f"{mesh.axis_names}")
    if sparse not in ("auto", "bsr", "dense"):
        raise ValueError(f"sparse must be 'auto', 'bsr' or 'dense', "
                         f"got {sparse!r}")
    compressed = None if sparse == "auto" else (sparse == "bsr")
    sol = plan_mod.solve_partition(
        comm, form, axes=tuple(mesh.axis_names),
        shape=tuple(mesh.devices.shape), shard_batch=shard_batch,
        compressed=compressed)
    if sparse == "bsr" and not (sol.lhs.compressed or sol.rhs.compressed):
        raise ValueError(
            "sparse='bsr' requested but the solved partition ships no "
            "compressed side (no structured 2-D sparse operand); use "
            "sparse='auto' or 'dense'")
    dt = jnp.dtype(dtype)
    if sol.strategy in ("summa", "cannon", "ring_hybrid",
                        "multicast_hybrid", "local"):
        return _build_out_stationary(sol, form, mesh, dt)
    return _build_k_spatial(sol, form, mesh, dt)


# ---------------------------------------------------------------------------
# Compressed-operand shipping: per-device BSR payload + coordinate lists
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Compressed:
    """Trace-time partition of a structured sparse side.

    The dense prepared operand is decomposed into its pattern's blocks and
    each device's nonzero blocks are collected as (payload, stat-coord,
    k-coord) triples — the stationary-dim coordinate is local to the
    device's shard, the contraction-dim coordinate is in ``k_frame``
    ("global": the frame of a full-k dense side at contract time, i.e.
    gathered/resident; "local": the frame of a k-spatial shard).  Payload
    rows are padded per device to the max nnz (``n_max``); padded entries
    are zeroed so they contribute nothing downstream.
    """

    side: str                       # lhs | rhs
    block: Tuple[int, int]
    d0_pad: int                     # padded operand dims
    d1_pad: int
    n_max: int
    flat_ids: np.ndarray            # (s0, s1, n_max) block ids, padded w/ 0
    stat_c: np.ndarray              # (s0, s1, n_max) local stationary coords
    k_c: np.ndarray                 # (s0, s1, n_max) contraction coords
    valid: np.ndarray               # (s0, s1, n_max) bool
    counts: np.ndarray              # (s0, s1) nnz per device

    @property
    def grid_pad(self) -> Tuple[int, int]:
        return (self.d0_pad // self.block[0], self.d1_pad // self.block[1])


def _splits(ax, sizes: Dict[str, int]) -> int:
    return plan_mod._axis_factor(ax, sizes)


def _compress_partition(form: "LoweredForm", sol: PartitionSolution,
                        k_frame: str, k_extra: int = 1) -> _Compressed:
    """Partition the pattern's block-COO list per device (numpy, static).

    ``k_extra`` is the dense side's contraction-dim split factor: the
    padded k extent must be divisible by it too, so the gathered /
    resident dense side and the payload's k-coordinate frame agree."""
    osp = form.sparse
    tp = sol.lhs if osp.side == "lhs" else sol.rhs
    axes, (s0, s1) = sol.axes, sol.shape
    sizes = sol.sizes
    b0, b1 = osp.block
    if osp.side == "lhs":
        stat_dim, k_dim = "m", "k"
        d0_ext, d1_ext = form.m, form.k
        stat_pos = 0                       # rows are the stationary dim
    else:
        stat_dim, k_dim = "n", "k"
        d0_ext, d1_ext = form.k, form.n
        stat_pos = 1                       # cols are the stationary dim
    stat_ax = tp.axis_of.get(stat_dim)
    k_ax = tp.axis_of.get(k_dim)
    f_stat = _splits(stat_ax, sizes)
    f_k = _splits(k_ax, sizes)

    # pad operand dims so every shard is a whole number of blocks (and the
    # contraction dim also divides the dense side's split)
    def padded(ext, blk, splits, extra=1):
        step = math.lcm(blk * splits, extra)
        return step * math.ceil(ext / step)

    if stat_pos == 0:
        d0_pad = padded(d0_ext, b0, f_stat)
        d1_pad = padded(d1_ext, b1, f_k, k_extra)
        g_stat, g_k = d0_pad // b0, d1_pad // b1
    else:
        d0_pad = padded(d0_ext, b0, f_k, k_extra)
        d1_pad = padded(d1_ext, b1, f_stat)
        g_k, g_stat = d0_pad // b0, d1_pad // b1
    g0, g1 = d0_pad // b0, d1_pad // b1
    stat_per, k_per = g_stat // f_stat, g_k // f_k

    def shard_of(ax, i, j):
        if ax is None:
            return 0
        if isinstance(ax, tuple):
            coords = {axes[0]: i, axes[1]: j}
            idx = 0
            for a in ax:
                idx = idx * sizes[a] + coords[a]
            return idx
        return i if ax == axes[0] else j

    per_dev = [[[] for _ in range(s1)] for _ in range(s0)]
    for (r, c) in osp.coords:
        stat_id, k_id = (r, c) if stat_pos == 0 else (c, r)
        si, ki = stat_id // stat_per, k_id // k_per
        for i in range(s0):
            for j in range(s1):
                if shard_of(stat_ax, i, j) != si and stat_ax is not None:
                    continue
                if shard_of(k_ax, i, j) != ki and k_ax is not None:
                    continue
                stat_local = (stat_id - (si if stat_ax is not None else 0)
                    * stat_per)
                k_out = (k_id if k_frame == "global" else
                    k_id - (ki if k_ax is not None else 0) * k_per)
                per_dev[i][j].append((r * g1 + c, stat_local, k_out))

    counts = np.array([[len(per_dev[i][j]) for j in range(s1)]
                       for i in range(s0)], np.int32)
    n_max = max(1, int(counts.max()))
    flat_ids = np.zeros((s0, s1, n_max), np.int32)
    stat_c = np.zeros((s0, s1, n_max), np.int32)
    k_c = np.zeros((s0, s1, n_max), np.int32)
    valid = np.zeros((s0, s1, n_max), bool)
    for i in range(s0):
        for j in range(s1):
            for t, (fid, sc, kc) in enumerate(per_dev[i][j]):
                flat_ids[i, j, t] = fid
                stat_c[i, j, t] = sc
                k_c[i, j, t] = kc
                valid[i, j, t] = True
    return _Compressed(osp.side, (b0, b1), d0_pad, d1_pad, n_max,
                       flat_ids, stat_c, k_c, valid, counts)


def _pack_payload(dense2d: jax.Array, comp: _Compressed) -> jax.Array:
    """Blocks of the padded dense operand, gathered per device and zeroed
    on padded entries: (s0, s1, n_max, b0, b1)."""
    b0, b1 = comp.block
    g0, g1 = comp.grid_pad
    x = _pad_dim(_pad_dim(dense2d, -2, comp.d0_pad), -1, comp.d1_pad)
    x = x[:comp.d0_pad, :comp.d1_pad]
    blocks = x.reshape(g0, b0, g1, b1).transpose(0, 2, 1, 3)
    flat = blocks.reshape(g0 * g1, b0, b1)
    pay = flat[comp.flat_ids]                     # (s0, s1, N, b0, b1)
    mask = jnp.asarray(comp.valid)[..., None, None]
    return jnp.where(mask, pay, jnp.zeros((), pay.dtype))


def _bsr_contract(pay: jax.Array, stat_c: jax.Array, k_c: jax.Array,
                  dense: jax.Array, side: str, stat_blocks: int,
                  b_stat: int, b_k: int) -> jax.Array:
    """One compressed contraction: nonzero blocks against a dense side.

    ``side == 'lhs'``: pay (N, bm, bk) x dense (K, n) -> (stat_blocks*bm, n)
    ``side == 'rhs'``: dense (m, K) x pay (N, bk, bn) -> (m, stat_blocks*bn)

    ``dense``'s contraction extent K must be in the same frame as ``k_c``
    (full-k at contract time for gathered/resident sides, the local shard
    for k-spatial).  Padded payload entries are zero, so their (0, 0)
    coordinates contribute nothing.
    """
    if side == "lhs":
        n = dense.shape[-1]
        rb = dense.reshape(-1, b_k, n)[k_c]               # (N, bk, n)
        parts = jnp.einsum("nab,nbc->nac", pay, rb,
                           preferred_element_type=jnp.float32)
        out = jax.ops.segment_sum(parts, stat_c, num_segments=stat_blocks)
        return out.reshape(stat_blocks * b_stat, n)
    m = dense.shape[-2]
    lb = jnp.take(dense.reshape(m, -1, b_k), k_c, axis=1)  # (m, N, bk)
    parts = jnp.einsum("mnb,nbc->nmc", lb, pay,
                       preferred_element_type=jnp.float32)
    out = jax.ops.segment_sum(parts, stat_c, num_segments=stat_blocks)
    return out.transpose(1, 0, 2).reshape(m, stat_blocks * b_stat)


# ---------------------------------------------------------------------------
# Strategy family 1: output blocks stationary (shard / stream output)
# ---------------------------------------------------------------------------

def _build_out_stationary(sol: PartitionSolution, form, mesh: Mesh,
                          dtype) -> MeshProgram:
    """Output (b?, m, n) blocks resident on their chip; the contraction is
    delivered by the motions the solver assigned: gathers (multicast
    wires), rings (systolic wires), or local full-k residency."""
    ax0, ax1 = sol.axes
    sizes = sol.sizes
    s0, s1 = sol.shape
    lhs_tp, rhs_tp, out_tp = sol.lhs, sol.rhs, sol.out
    double_ring = sol.strategy == "cannon"
    lhs_ring = lhs_tp.motion == "ppermute_ring"
    rhs_ring = rhs_tp.motion == "ppermute_ring"
    S = s1 if lhs_ring else (s0 if rhs_ring else 1)

    comp = None
    if lhs_tp.compressed or rhs_tp.compressed:
        dn_tp = rhs_tp if lhs_tp.compressed else lhs_tp
        comp = _compress_partition(
            form, sol, k_frame="global",
            k_extra=plan_mod._axis_factor(dn_tp.axis_of.get("k"), sizes))

    in_specs = (_spec_of(lhs_tp), _spec_of(rhs_tp))
    out_spec = _spec_of(out_tp)
    kmult = math.lcm(
        s1 if lhs_tp.axis_of.get("k") else 1,
        s0 if rhs_tp.axis_of.get("k") else 1, max(S, 1))
    f_b = plan_mod._axis_factor(sol.batch_axis, sizes)
    f_m = plan_mod._axis_factor(sol.grid.get("m"), sizes)
    f_n = plan_mod._axis_factor(sol.grid.get("n"), sizes)

    if comp is None:
        fn = _dense_out_stationary_fn(
            sol, form, mesh, dtype, in_specs, out_spec, kmult,
            f_b, f_m, f_n, S, double_ring)
    else:
        fn = _compressed_out_stationary_fn(
            sol, form, mesh, dtype, comp, out_spec, kmult, f_m, f_n, S)
    return MeshProgram(sol.strategy, in_specs, out_spec, sol.ring_axes,
                       (f_m, f_n, kmult), sol, fn)


def _dense_out_stationary_fn(sol, form, mesh, dtype, in_specs, out_spec,
                             kmult, f_b, f_m, f_n, S, double_ring):
    ax0, ax1 = sol.axes
    s0, s1 = sol.shape
    lhs_tp, rhs_tp = sol.lhs, sol.rhs
    lhs_ring = lhs_tp.motion == "ppermute_ring"
    rhs_ring = rhs_tp.motion == "ppermute_ring"
    lhs_gather = lhs_tp.motion == "all_gather"
    rhs_gather = rhs_tp.motion == "all_gather"

    def body(l, r):
        if lhs_gather:
            l = jax.lax.all_gather(l, ax1, axis=l.ndim - 1, tiled=True)
        if rhs_gather:
            r = jax.lax.all_gather(r, ax0, axis=r.ndim - 2, tiled=True)
        if not (lhs_ring or rhs_ring):
            return _contract(l, r).astype(dtype)

        if double_ring:
            left = _ring_perm(s1)
            up = _ring_perm(s0)

            def step(t, carry):
                l_c, r_c, acc = carry
                acc = acc + _contract(l_c, r_c)
                l_c = jax.lax.ppermute(l_c, ax1, left)
                r_c = jax.lax.ppermute(r_c, ax0, up)
                return l_c, r_c, acc

            _, _, acc = jax.lax.fori_loop(0, S, step, (l, r, _acc_init(l, r)))
            return acc.astype(dtype)

        # single ring: one side circulates its k-blocks; the other side
        # holds full k (gathered or resident) and slices the block that is
        # currently aligned with the ring position.
        ax_ring = ax1 if lhs_ring else ax0
        perm = _ring_perm(S)
        pos = jax.lax.axis_index(ax_ring)
        mov0 = l if lhs_ring else r
        kb = mov0.shape[-1] if lhs_ring else mov0.shape[-2]

        def step(t, carry):
            mov, acc = carry
            idx = ((pos + t) % S) * kb
            if lhs_ring:
                r_blk = jax.lax.dynamic_slice_in_dim(r, idx, kb,
                                                     axis=r.ndim - 2)
                acc = acc + _contract(mov, r_blk)
            else:
                l_blk = jax.lax.dynamic_slice_in_dim(l, idx, kb,
                                                     axis=l.ndim - 1)
                acc = acc + _contract(l_blk, mov)
            mov = jax.lax.ppermute(mov, ax_ring, perm)
            return mov, acc

        _, acc = jax.lax.fori_loop(0, S, step, (mov0, _acc_init(l, r)))
        return acc.astype(dtype)

    batched = bool(form.batch)

    def run(lhs, rhs):
        b, m, n = form.batch_size, lhs.shape[-2], rhs.shape[-1]
        lhs = _pad_dim(_pad_dim(lhs, -2, f_m), -1, kmult)
        rhs = _pad_dim(_pad_dim(rhs, -1, f_n), -2, kmult)
        if batched:
            if form.lhs_batched:
                lhs = _pad_dim(lhs, -3, f_b)
            if form.rhs_batched:
                rhs = _pad_dim(rhs, -3, f_b)
        if double_ring:
            lhs = _skew(lhs, s0, roll_axis=-1, block_axis=-2)
            rhs = _skew(rhs, s1, roll_axis=-2, block_axis=-1)
        out = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
            check_vma=False)(lhs, rhs)
        out = out[..., :m, :n]
        return out[:b] if batched else out

    return jax.jit(run)


def _compressed_out_stationary_fn(sol, form, mesh, dtype, comp, out_spec,
                                  kmult, f_m, f_n, S):
    """The sparse side ships as (payload, stat-coords, k-coords) through
    the motion the solver assigned (gather or single ring — the solver
    never emits a compressed double ring); the dense side moves exactly as
    in the dense program and is full-k at contract time, so the global
    k-coordinates the payload carries need no realignment."""
    ax0, ax1 = sol.axes
    s0, s1 = sol.shape
    sp_side = comp.side
    sp_tp = sol.lhs if sp_side == "lhs" else sol.rhs
    dn_tp = sol.rhs if sp_side == "lhs" else sol.lhs
    dn_gather = dn_tp.motion == "all_gather"
    sp_gather = sp_tp.motion == "all_gather"
    sp_ring = sp_tp.motion == "ppermute_ring"
    b0, b1 = comp.block
    b_stat, b_k = (b0, b1) if sp_side == "lhs" else (b1, b0)
    stat_ax = sp_tp.axis_of.get("m" if sp_side == "lhs" else "n")
    f_stat = plan_mod._axis_factor(stat_ax, sol.sizes)
    stat_blocks = ((comp.d0_pad if sp_side == "lhs" else comp.d1_pad)
        // (b_stat * f_stat))
    # the sparse side's motion axis (k split) and the dense side's
    dn_ax = ax0 if sp_side == "lhs" else ax1
    sp_ax = ax1 if sp_side == "lhs" else ax0
    triple_specs = (P(ax0, ax1, None, None, None),
                    P(ax0, ax1, None), P(ax0, ax1, None))

    def body(pay, sc, kc, dense):
        pay, sc, kc = pay[0, 0], sc[0, 0], kc[0, 0]
        if dn_gather:
            axis = dense.ndim - 2 if sp_side == "lhs" else dense.ndim - 1
            dense = jax.lax.all_gather(dense, dn_ax, axis=axis, tiled=True)
        if sp_gather:
            pay = jax.lax.all_gather(pay, sp_ax, axis=0, tiled=True)
            sc = jax.lax.all_gather(sc, sp_ax, axis=0, tiled=True)
            kc = jax.lax.all_gather(kc, sp_ax, axis=0, tiled=True)
        if not sp_ring:
            return _bsr_contract(pay, sc, kc, dense, sp_side,
                                 stat_blocks, b_stat, b_k).astype(dtype)

        perm = _ring_perm(S)
        if sp_side == "lhs":
            acc0 = jnp.zeros((stat_blocks * b_stat, dense.shape[-1]),
                             jnp.float32)
        else:
            acc0 = jnp.zeros((dense.shape[-2], stat_blocks * b_stat),
                             jnp.float32)

        def step(t, carry):
            pay_c, sc_c, kc_c, acc = carry
            acc = acc + _bsr_contract(pay_c, sc_c, kc_c, dense, sp_side,
                                      stat_blocks, b_stat, b_k)
            pay_c = jax.lax.ppermute(pay_c, sp_ax, perm)
            sc_c = jax.lax.ppermute(sc_c, sp_ax, perm)
            kc_c = jax.lax.ppermute(kc_c, sp_ax, perm)
            return pay_c, sc_c, kc_c, acc

        _, _, _, acc = jax.lax.fori_loop(0, S, step, (pay, sc, kc, acc0))
        return acc.astype(dtype)

    dense_spec = _spec_of(dn_tp)
    sc = jnp.asarray(comp.stat_c)
    kc = jnp.asarray(comp.k_c)

    def run(lhs, rhs):
        m, n = lhs.shape[-2], rhs.shape[-1]
        sp2d, dn2d = (lhs, rhs) if sp_side == "lhs" else (rhs, lhs)
        pay = _pack_payload(sp2d, comp)
        if sp_side == "lhs":
            dn2d = _pad_dim(_pad_dim(dn2d, -1, f_n), -2, comp.d1_pad)
            dn2d = dn2d[:comp.d1_pad]
            args = (pay, sc, kc, dn2d)
        else:
            dn2d = _pad_dim(_pad_dim(dn2d, -2, f_m), -1, comp.d0_pad)
            dn2d = dn2d[:, :comp.d0_pad]
            args = (pay, sc, kc, dn2d)
        out = jax.shard_map(
            body, mesh=mesh, in_specs=(*triple_specs, dense_spec),
            out_specs=out_spec, check_vma=False)(*args)
        return out[..., :m, :n]

    return jax.jit(run)


# ---------------------------------------------------------------------------
# Strategy family 2: contraction spatial over mesh axes (psum / staggered
# output ring / broadcast-reduction outputs)
# ---------------------------------------------------------------------------

def _build_k_spatial(sol: PartitionSolution, form, mesh: Mesh,
                     dtype) -> MeshProgram:
    """The contraction dim is sharded over ``sol.k_axes``; each chip
    computes a partial product and the reduction runs over those axes —
    one ``psum`` (reduction-class outputs) or the staggered
    accumulate-rotate ppermute schedule (systolic-class outputs, the
    executed dt: the output is the mobile tensor and stores 1/S per
    device)."""
    sizes = sol.sizes
    k_axes = sol.k_axes
    lhs_tp, rhs_tp, out_tp = sol.lhs, sol.rhs, sol.out
    kmult = math.prod(sizes[a] for a in k_axes)
    f_b = plan_mod._axis_factor(sol.batch_axis, sizes)
    f_m = plan_mod._axis_factor(sol.grid.get("m"), sizes)
    f_n = plan_mod._axis_factor(sol.grid.get("n"), sizes)
    S = sizes[k_axes[0]] if sol.stagger else 0

    comp = None
    if lhs_tp.compressed or rhs_tp.compressed:
        comp = _compress_partition(form, sol, k_frame="local",
                                   k_extra=kmult)

    in_specs = (_spec_of(lhs_tp), _spec_of(rhs_tp))
    out_spec = _spec_of(out_tp)
    ring_ax = k_axes[0] if sol.stagger else None

    def reduce_partial(part):
        """Partial (b?, m_pad, n_loc) fp32 -> reduced output block: one
        psum over the k axes, or — for systolic-class outputs — the
        staggered accumulate-rotate schedule (the executed dt): at step t
        device r adds its k-shard's partial for output chunk
        ``(r - t) mod S`` to the chunk passing by and forwards it, so
        after S rotations chunk r has visited every k-shard and lands on
        device r — the mobile tensor stores 1/S per device instead of a
        full replica."""
        if not sol.stagger:
            return jax.lax.psum(part, k_axes if len(k_axes) > 1
                                else k_axes[0])
        pos = jax.lax.axis_index(ring_ax)
        chunk = part.shape[-2] // S
        perm = _fwd_perm(S)

        def step(t, acc):
            c = (pos - t) % S
            pc = jax.lax.dynamic_slice_in_dim(part, c * chunk, chunk,
                                              axis=part.ndim - 2)
            return jax.lax.ppermute(acc + pc, ring_ax, perm)

        acc0 = jnp.zeros((*part.shape[:-2], chunk, part.shape[-1]),
                         jnp.float32)
        return jax.lax.fori_loop(0, S, step, acc0)

    m_mult = S if sol.stagger else f_m
    if comp is not None:
        fn = _compressed_k_spatial_fn(sol, form, mesh, dtype, comp,
                                      out_spec, f_m, f_n, m_mult,
                                      reduce_partial)
    else:
        fn = _dense_k_spatial_fn(sol, form, mesh, dtype, in_specs,
                                 out_spec, kmult, f_b, f_n, m_mult,
                                 reduce_partial)
    return MeshProgram(sol.strategy, in_specs, out_spec,
                       sol.ring_axes, (f_m, f_n, kmult), sol, fn)


def _dense_k_spatial_fn(sol, form, mesh, dtype, in_specs, out_spec, kmult,
                        f_b, f_n, m_mult, reduce_partial):
    batched = bool(form.batch)

    def body(l, r):
        return reduce_partial(_contract(l, r)).astype(dtype)

    def run(lhs, rhs):
        b, m, n = form.batch_size, lhs.shape[-2], rhs.shape[-1]
        lhs = _pad_dim(_pad_dim(lhs, -1, kmult), -2, m_mult)
        rhs = _pad_dim(_pad_dim(rhs, -2, kmult), -1, f_n)
        if batched:
            if form.lhs_batched:
                lhs = _pad_dim(lhs, -3, f_b)
            if form.rhs_batched:
                rhs = _pad_dim(rhs, -3, f_b)
        out = jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
            check_vma=False)(lhs, rhs)
        out = out[..., :m, :n]
        return out[:b] if batched else out

    return jax.jit(run)


def _compressed_k_spatial_fn(sol, form, mesh, dtype, comp, out_spec,
                             f_m, f_n, m_mult, reduce_partial):
    """Compressed operand under a k-spatial plan: every device holds only
    the nonzero blocks of its own (stat-shard, k-shard) tile — local-frame
    k coordinates against the dense side's k-shard — and the reduction
    (psum tree or staggered output ring) runs on the partial products."""
    sp_side = comp.side
    sp_tp = sol.lhs if sp_side == "lhs" else sol.rhs
    b0, b1 = comp.block
    b_stat, b_k = (b0, b1) if sp_side == "lhs" else (b1, b0)
    stat_ax = sp_tp.axis_of.get("m" if sp_side == "lhs" else "n")
    f_stat = plan_mod._axis_factor(stat_ax, sol.sizes)
    stat_blocks = ((comp.d0_pad if sp_side == "lhs" else comp.d1_pad)
        // (b_stat * f_stat))
    dn_tp = sol.rhs if sp_side == "lhs" else sol.lhs
    dense_spec = _spec_of(dn_tp)
    triple_specs = (P(*sol.axes, None, None, None),
                    P(*sol.axes, None), P(*sol.axes, None))
    sc = jnp.asarray(comp.stat_c)
    kc = jnp.asarray(comp.k_c)

    def body(pay, sc_b, kc_b, dense):
        pay, sc_b, kc_b = pay[0, 0], sc_b[0, 0], kc_b[0, 0]
        part = _bsr_contract(pay, sc_b, kc_b, dense, sp_side,
                             stat_blocks, b_stat, b_k)
        if sol.stagger and part.shape[-2] % m_mult:
            part = _pad_dim(part, -2, m_mult)
        return reduce_partial(part).astype(dtype)

    def run(lhs, rhs):
        m, n = lhs.shape[-2], rhs.shape[-1]
        sp2d, dn2d = (lhs, rhs) if sp_side == "lhs" else (rhs, lhs)
        pay = _pack_payload(sp2d, comp)
        if sp_side == "lhs":
            dn2d = _pad_dim(_pad_dim(dn2d, -1, f_n), -2, comp.d1_pad)
            dn2d = dn2d[:comp.d1_pad]
        else:
            dn2d = _pad_dim(_pad_dim(dn2d, -2, max(f_m, m_mult)),
                            -1, comp.d0_pad)
            dn2d = dn2d[:, :comp.d0_pad]
        out = jax.shard_map(
            body, mesh=mesh, in_specs=(*triple_specs, dense_spec),
            out_specs=out_spec, check_vma=False)(pay, sc, kc, dn2d)
        return out[..., :m, :n]

    return jax.jit(run)


# ---------------------------------------------------------------------------
# Introspection: kind -> spec table for one plan (used by docs and tests)
# ---------------------------------------------------------------------------

def describe(comm: CommPlan, form: "LoweredForm", mesh: Mesh
             ) -> Dict[str, str]:
    """Human-readable per-tensor realization of a CommPlan on a mesh."""
    prog = compile_comm_plan(comm, form, mesh)
    lines = {"strategy": prog.strategy,
             "lhs_spec": str(prog.in_specs[0]),
             "rhs_spec": str(prog.in_specs[1]),
             "out_spec": str(prog.out_spec)}
    lines.update(prog.solution.describe())
    for t in comm.tensors:
        ax = ",".join(t.mesh_axes) if t.mesh_axes else "-"
        lines[t.tensor] = f"{t.kind}[{ax}]"
    return lines
