"""Explicit (STT-scheduled) collectives for the transformer hot paths.

GSPMD's auto-partitioner chooses intermediate shardings by local cost
heuristics; inside the chunked-attention scan it ping-pongs between
Lq-sharded and kv-head-sharded layouts (observed 19.2 TB/step of resharding
all-gathers on qwen2.5-32b prefill — EXPERIMENTS.md §Perf).  TensorLib's
thesis applied to the mesh level says: derive the dataflow once and emit the
collectives *explicitly*.  This module provides shard_map realizations of
the three schedules the classification picks for the LM stack:

  * ``gather_seq``       — SP -> TP boundary: bf16 all-gather of sequence
                           shards (multicast dataflow),
  * ``project_scatter``  — TP -> SP boundary: local partial dot + bf16
                           psum_scatter (reduction-tree dataflow, scattered),
  * ``chunked_attn_manual`` — the full attention inner loop under manual
                           sharding: q/output stationary-sharded over Lq,
                           K/V multicast (replicated), zero resharding.

Each helper falls back to the auto path when the mesh/shape doesn't allow
the manual layout (e.g. decode steps with Lq == 1).  Enabled per-config via
``ModelConfig.explicit_collectives``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _mesh_info():
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None, (), 1
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    batch_axes = tuple(a for a in ("pod", "data") if a in sizes)
    return mesh, batch_axes, sizes.get("model", 1)


def _batch_ok(b: int, batch_axes, mesh) -> bool:
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    n = 1
    for a in batch_axes:
        n *= sizes[a]
    return b % n == 0 if n > 1 else True


def gather_seq(x: jax.Array) -> Optional[jax.Array]:
    """(B, S@model, D) -> (B, S, D) via explicit bf16 all-gather; None if
    the manual layout doesn't apply here."""
    mesh, bd, msize = _mesh_info()
    if mesh is None or msize <= 1 or x.ndim != 3:
        return None
    b, s, d = x.shape
    if s % msize or not _batch_ok(b, bd, mesh):
        return None
    bspec = bd if len(bd) > 1 else (bd[0] if bd else None)

    def body(xl):
        return lax.all_gather(xl, "model", axis=1, tiled=True)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=P(bspec, "model", None),
        out_specs=P(bspec, None, None),
        check_vma=False)(x)


def project_scatter(h: jax.Array, w: jax.Array) -> Optional[jax.Array]:
    """(B, S, F@model) @ (F@model, D) -> (B, S@model, D): local partial dot
    + bf16 psum_scatter over the model axis (reduction tree, scattered)."""
    mesh, bd, msize = _mesh_info()
    if mesh is None or msize <= 1 or h.ndim != 3:
        return None
    b, s, f = h.shape
    if s % msize or f % msize or not _batch_ok(b, bd, mesh):
        return None
    bspec = bd if len(bd) > 1 else (bd[0] if bd else None)

    def body(hl, wl):
        part = jnp.dot(hl, wl, preferred_element_type=jnp.float32)
        part = part.astype(h.dtype)        # reduce on the wire in bf16
        return lax.psum_scatter(part, "model", scatter_dimension=1,
                                tiled=True)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, "model"), P("model", None)),
        out_specs=P(bspec, "model", None),
        check_vma=False)(h, w)


def mlp_manual(x: jax.Array, wg: jax.Array, wu: jax.Array, wd: jax.Array,
               compute) -> Optional[jax.Array]:
    """The whole SwiGLU MLP as ONE manual dataflow:
    all-gather(x over seq) -> local wg/wu/silu/wd -> psum_scatter(out).

    Keeping the dots *inside* the shard_map makes the backward fully manual
    too (AG(dout) -> local dots -> RS(dx)); with the dots outside, the
    partitioner finishes the dx partial-sums with full all-reduces
    (observed 900 GiB/step on qwen1.5-110b — EXPERIMENTS.md §Perf)."""
    mesh, bd, msize = _mesh_info()
    if mesh is None or msize <= 1 or x.ndim != 3:
        return None
    b, s_loc_or_full, d = x.shape
    f = wg.shape[1]
    if s_loc_or_full % msize or f % msize or not _batch_ok(b, bd, mesh):
        return None
    bspec = bd if len(bd) > 1 else (bd[0] if bd else None)

    def body(xl, wgl, wul, wdl):
        xf = lax.all_gather(xl.astype(compute), "model", axis=1, tiled=True)
        g = xf @ wgl
        u = xf @ wul
        h = jax.nn.silu(g.astype(jnp.float32)).astype(compute) * u
        part = jnp.dot(h, wdl, preferred_element_type=jnp.float32)
        return lax.psum_scatter(part.astype(compute), "model",
                                scatter_dimension=1, tiled=True)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, "model", None), P(None, "model"),
                  P(None, "model"), P("model", None)),
        out_specs=P(bspec, "model", None),
        check_vma=False)(x, wg.astype(compute), wu.astype(compute),
                         wd.astype(compute))


def qkv_manual(x: jax.Array, wq: jax.Array, wk: jax.Array, wv: jax.Array,
               compute) -> Optional[Tuple[jax.Array, jax.Array, jax.Array]]:
    """Gather(x over seq) + q/k/v projections in one manual dataflow.
    q comes back sharded on its head dim ('model'); k/v are psum-free local
    dots returned sharded the same way (callers re-gather the small kv)."""
    mesh, bd, msize = _mesh_info()
    if mesh is None or msize <= 1 or x.ndim != 3:
        return None
    b, s, d = x.shape
    if (s % msize or wq.shape[1] % msize or wk.shape[1] % msize
            or not _batch_ok(b, bd, mesh)):
        return None
    bspec = bd if len(bd) > 1 else (bd[0] if bd else None)

    def body(xl, wql, wkl, wvl):
        xf = lax.all_gather(xl.astype(compute), "model", axis=1, tiled=True)
        return xf @ wql, xf @ wkl, xf @ wvl

    spec_out = P(bspec, None, "model")
    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, "model", None), P(None, "model"),
                  P(None, "model"), P(None, "model")),
        out_specs=(spec_out, spec_out, spec_out),
        check_vma=False)(x, wq.astype(compute), wk.astype(compute),
                         wv.astype(compute))


def moe_manual(x: jax.Array, p: dict, cfg, compute
               ) -> Optional[Tuple[jax.Array, jax.Array]]:
    """The whole MoE layer as ONE manual dataflow.

    gather(x over seq) -> local router/top-k/dispatch -> expert dots with
    d_ff sharded over 'model' -> local combine -> psum_scatter(out), which
    performs the f-partial reduction AND the TP->SP scatter in a single
    collective.  Auto-partitioning of the gather/scatter dispatch tensors
    was worth 8.6 TB/step of resharding on mixtral (EXPERIMENTS.md §Perf).
    """
    mesh, bd, msize = _mesh_info()
    if mesh is None or msize <= 1 or x.ndim != 3:
        return None
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    f = cfg.d_ff
    if s % msize or f % msize or not _batch_ok(b, bd, mesh):
        return None
    bspec = bd if len(bd) > 1 else (bd[0] if bd else None)
    capacity = int(s * k / e * cfg.capacity_factor + 1)
    from .mlp import _dispatch_indices

    def body(xl, router, wg, wu, wd):
        xf = lax.all_gather(xl.astype(compute), "model", axis=1, tiled=True)
        logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, top_idx = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        me = probs.mean(axis=(0, 1))
        ce = jnp.zeros((e,), jnp.float32).at[top_idx.reshape(-1)].add(
            1.0 / (top_idx.size))
        aux = e * jnp.sum(me * ce) + 1e-3 * jnp.mean(
            jax.nn.logsumexp(logits, -1) ** 2)
        for ax in bd:
            aux = lax.pmean(aux, ax)

        def per_group(xg, idxg, gateg):
            slots, keep = _dispatch_indices(idxg, e, capacity)
            token_of = slots // k
            valid = slots < s * k
            safe_token = jnp.minimum(token_of, s - 1)
            xin = jnp.where(valid[..., None],
                            jnp.take(xg, safe_token, axis=0),
                            0.0).astype(compute)
            h = jax.nn.silu(jnp.einsum(
                "ecd,edf->ecf", xin, wg).astype(jnp.float32)).astype(compute)
            h = h * jnp.einsum("ecd,edf->ecf", xin, wu)
            out_e = jnp.einsum("ecf,efd->ecd", h, wd)      # f-shard partial
            gate_flat = (gateg * keep).reshape(-1)
            w = jnp.where(valid,
                          jnp.take(gate_flat, jnp.minimum(slots, s * k - 1)),
                          0.0)
            contrib = (out_e.astype(jnp.float32) * w[..., None]
                       ).reshape(e * capacity, d)
            scatter_idx = jnp.where(valid, safe_token, s).reshape(-1)
            return jnp.zeros((s, d), jnp.float32).at[scatter_idx].add(
                contrib, mode="drop")

        out = jax.vmap(per_group)(xf, top_idx, gates)      # (B_loc, S, D)
        # one collective: sum f-shard partials AND scatter back to seq shards
        out = lax.psum_scatter(out.astype(compute), "model",
                               scatter_dimension=1, tiled=True)
        return out, aux

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, "model", None), P(None, None),
                  P(None, None, "model"), P(None, None, "model"),
                  P(None, "model", None)),
        out_specs=(P(bspec, "model", None), P()),
        check_vma=False)(
        x, p["router"], p["wg"].astype(compute), p["wu"].astype(compute),
        p["wd"].astype(compute))
    return out, aux


def chunked_attn_manual(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool, window: Optional[int],
                        bkv: int = 1024) -> Optional[jax.Array]:
    """Online-softmax attention with q/out Lq-sharded over 'model' and K/V
    replicated (multicast) — the manual realization of the dataflow the
    classification picks, with zero partitioner resharding."""
    import os
    bkv = int(os.environ.get("REPRO_ATTN_BKV", bkv))
    mesh, bd, msize = _mesh_info()
    if mesh is None or msize <= 1:
        return None
    b, hq, lq, dh = q.shape
    lkv = k.shape[2]
    if lq % msize or lq // msize < 1 or not _batch_ok(b, bd, mesh):
        return None
    if lkv % bkv:
        bkv = next((bb for bb in (512, 256, 128, 64, 1) if lkv % bb == 0), 1)
    bspec = bd if len(bd) > 1 else (bd[0] if bd else None)
    from .attention import _chunked_attn

    def body(ql, kl, vl):
        off = lax.axis_index("model") * (lq // msize)
        return _chunked_attn(ql, kl, vl, causal=causal, window=window,
                             q_offset=off, bkv=bkv)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, "model", None),
                  P(bspec, None, None, None),
                  P(bspec, None, None, None)),
        out_specs=P(bspec, None, "model", None),
        check_vma=False)(q, k, v)
