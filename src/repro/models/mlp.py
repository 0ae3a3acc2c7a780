"""Feed-forward layers: SwiGLU MLP and top-k MoE with capacity dispatch.

The MoE uses gather/scatter dispatch (indices (E, C) per token group)
instead of GShard's dense one-hot dispatch einsum — the (tokens, E, C)
one-hot tensor is the memory hog that caps MoE scale; the index form is
O(E*C) and shards cleanly.  Expert weights carry an 'expert' leading axis
and are TP-sharded on d_ff ('mlp' logical axis) — EP via all_to_all is a
config option exercised on small meshes (tests) where n_experts divides the
axis; at 256 chips with 8 experts, TP-inside-experts is the production
layout (DESIGN.md §4).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .common import Leaf, shard, shard_pinned, stacked_dense_init


# ---------------------------------------------------------------------------
# Dense SwiGLU
# ---------------------------------------------------------------------------

def init_mlp(key, cfg: ModelConfig, n_layers: int) -> Dict:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    return {
        "wg": stacked_dense_init(ks[0], n_layers, d, f, ("embed", "mlp")),
        "wu": stacked_dense_init(ks[1], n_layers, d, f, ("embed", "mlp")),
        "wd": stacked_dense_init(ks[2], n_layers, f, d, ("mlp", "embed")),
    }


@jax.named_scope("mlp")
def apply_mlp(p: Dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    compute = jnp.dtype(cfg.dtype)
    if cfg.explicit_collectives and cfg.sequence_parallel:
        # fully-manual dataflow: gather + dots + reduce-scatter in ONE
        # shard_map (keeps the backward manual as well)
        from .explicit_tp import mlp_manual
        res = mlp_manual(x, p["wg"], p["wu"], p["wd"], compute)
        if res is not None:
            return res.astype(x.dtype)
    # SP -> TP boundary: gather the (bf16) sequence shards here, NOT inside
    # the fp32 norm internals (keeps the all-gather at half width)
    xc = x.astype(compute)
    if cfg.explicit_collectives:
        from .explicit_tp import gather_seq
        xg = gather_seq(xc)
        xc = xg if xg is not None else shard_pinned(
            xc, ("pod", "data"), None, None)
    else:
        xc = shard_pinned(xc, ("pod", "data"), None, None)
    g = xc @ p["wg"].astype(compute)
    u = xc @ p["wu"].astype(compute)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(compute) * u
    h = shard(h, ("pod", "data"), None, "model")
    wd = p["wd"].astype(compute)
    if cfg.explicit_collectives and cfg.sequence_parallel:
        from .explicit_tp import project_scatter
        res = project_scatter(h, wd)
        if res is not None:
            return res.astype(x.dtype)
    out = jnp.dot(h, wd, preferred_element_type=jnp.float32)
    if cfg.sequence_parallel:
        # TP -> SP boundary: constrain the raw dot output (before any
        # convert) so the partitioner emits a reduce-scatter, not
        # all-reduce + slice
        out = shard(out, ("pod", "data"), "model", None)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MoE (top-k, capacity-based, gather/scatter dispatch)
# ---------------------------------------------------------------------------

def init_moe(key, cfg: ModelConfig, n_layers: int) -> Dict:
    ks = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    scale = (1.0 / d) ** 0.5

    def expert_w(k, din, dout, axes):
        w = jax.random.normal(k, (n_layers, e, din, dout), jnp.float32)
        return Leaf(w * (1.0 / din) ** 0.5, ("layers", "expert", *axes))

    return {
        "router": stacked_dense_init(ks[0], n_layers, d, e,
                                     ("embed", None), scale=scale),
        "wg": expert_w(ks[1], d, f, ("embed", "mlp")),
        "wu": expert_w(ks[2], d, f, ("embed", "mlp")),
        "wd": expert_w(ks[3], f, d, ("mlp", "embed")),
    }


def _dispatch_indices(top_idx: jax.Array, n_experts: int, capacity: int
                      ) -> Tuple[jax.Array, jax.Array]:
    """top_idx: (T, K) expert choice per token/slot.

    Returns (token_slot (E, C) int32 index into T*K flat choices — entries
    >= T*K mean empty —, keep_mask (T, K) bool for choices that won the
    capacity race).  Priority: token order, then slot (GShard-style).
    """
    t, k = top_idx.shape
    flat = top_idx.reshape(-1)                                 # (T*K,)
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)  # (T*K, E)
    pos = jnp.cumsum(onehot, axis=0) - 1                       # slot in expert
    my_pos = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    keep = my_pos < capacity
    # scatter flat-choice id into (E, C); dropped entries scatter nowhere
    buf = jnp.full((n_experts, capacity), t * k, jnp.int32)
    e_idx = jnp.where(keep, flat, n_experts)       # out-of-range -> dropped
    c_idx = jnp.where(keep, my_pos, capacity)
    buf = buf.at[e_idx, c_idx].set(jnp.arange(t * k, dtype=jnp.int32),
                                   mode="drop")
    return buf, keep.reshape(t, k)


@jax.named_scope("mlp")
def apply_moe(p: Dict, x: jax.Array, cfg: ModelConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss).  Router in fp32.

    Tokens are grouped by batch row (G = B groups of S tokens) so dispatch
    stays local to the data shard; capacity is per group.
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    compute = jnp.dtype(cfg.dtype)
    if cfg.explicit_collectives and cfg.sequence_parallel:
        from .explicit_tp import moe_manual
        res = moe_manual(x, p, cfg, compute)
        if res is not None:
            return res[0].astype(x.dtype), res[1]
    x = shard(x, ("pod", "data"), None, None)        # SP -> TP gather
    capacity = int(s * k / e * cfg.capacity_factor + 1)

    logits = (x.astype(jnp.float32) @
              p["router"].astype(jnp.float32))                 # (B, S, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, top_idx = jax.lax.top_k(probs, k)                   # (B, S, K)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch-style) + router z-loss
    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros((e,), jnp.float32).at[top_idx.reshape(-1)].add(
        1.0 / (b * s * k))
    aux = e * jnp.sum(me * ce) + 1e-3 * jnp.mean(
        jax.nn.logsumexp(logits, -1) ** 2)

    def per_group(xg, idxg, gateg):
        """xg: (S, D); idxg: (S, K); gateg: (S, K)."""
        slots, keep = _dispatch_indices(idxg, e, capacity)     # (E, C)
        token_of = slots // k                                  # (E, C)
        valid = slots < s * k
        safe_token = jnp.minimum(token_of, s - 1)
        xin = jnp.where(valid[..., None],
                        jnp.take(xg, safe_token, axis=0),
                        0.0).astype(compute)                   # (E, C, D)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xin,
                                   p["wg"].astype(compute)).astype(jnp.float32)
                        ).astype(compute)
        h = h * jnp.einsum("ecd,edf->ecf", xin, p["wu"].astype(compute))
        out_e = jnp.einsum("ecf,efd->ecd", h,
                           p["wd"].astype(compute))            # (E, C, D)
        # combine: scatter expert outputs back to tokens, weighted by gates
        gate_flat = (gateg * keep).reshape(-1)                 # (S*K,)
        w = jnp.where(valid, jnp.take(gate_flat, jnp.minimum(slots, s * k - 1)),
                      0.0)                                     # (E, C)
        contrib = (out_e.astype(jnp.float32) * w[..., None]
                   ).reshape(e * capacity, d)
        scatter_idx = jnp.where(valid, safe_token, s).reshape(-1)
        outg = jnp.zeros((s, d), jnp.float32).at[scatter_idx].add(
            contrib, mode="drop")
        return outg

    out = jax.vmap(per_group)(x, top_idx, gates)
    out = out.astype(x.dtype)
    if cfg.sequence_parallel:
        out = shard(out, ("pod", "data"), "model", None)   # TP -> SP
    return out, aux
