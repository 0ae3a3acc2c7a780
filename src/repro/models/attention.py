"""Attention layers: GQA + RoPE + SWA + cross-attention + KV caches.

Three execution paths, all oracle-checked against each other in tests:

* full-scores XLA path (short sequences),
* chunked online-softmax XLA path (long sequences — same math as the Pallas
  flash kernel, expressed with lax.scan so the 32k prefill does not
  materialize (L, L) score matrices when compiled for the dry-run),
* decode path (single query over a — possibly rolling — KV cache).

The Pallas kernel (kernels/flash_attention.py) is the TPU hot-spot
implementation; models call the XLA paths so CPU dry-runs compile, and the
kernel is validated against the same oracle in interpret mode.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import common
from .common import shard, stacked_dense_init

NEG_INF = float(-1e30)
FULL_SCORES_MAX_LEN = 8_192   # above this, use the chunked path


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, n_layers: int) -> Dict:
    """Stacked (scan-ready) attention params for ``n_layers`` layers."""
    ks = jax.random.split(key, 4)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p = {
        "wq": stacked_dense_init(ks[0], n_layers, d, qd, ("embed", "heads")),
        "wk": stacked_dense_init(ks[1], n_layers, d, kvd, ("embed", "kv")),
        "wv": stacked_dense_init(ks[2], n_layers, d, kvd, ("embed", "kv")),
        "wo": stacked_dense_init(ks[3], n_layers, qd, d, ("heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = common.zeros_init((n_layers, qd), ("layers", "heads"))
        p["bk"] = common.zeros_init((n_layers, kvd), ("layers", "kv"))
        p["bv"] = common.zeros_init((n_layers, kvd), ("layers", "kv"))
    return p


# ---------------------------------------------------------------------------
# Score paths
# ---------------------------------------------------------------------------

def _full_scores_attn(q, k, v, *, causal, window, q_offset=0):
    """(B, H, Lq, dh) x (B, Hkv, Lkv, dh); materializes (Lq, Lkv) scores."""
    from ..kernels import ref
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)


def _chunked_attn(q, k, v, *, causal, window, q_offset=0, bkv: int = 1024):
    """Online-softmax over kv chunks via lax.scan — O(Lq * bkv) memory."""
    b, hq, lq, dh = q.shape
    _, hkv, lkv, _ = k.shape
    group = hq // hkv
    scale = 1.0 / (dh ** 0.5)
    bkv = min(bkv, lkv)
    assert lkv % bkv == 0, (lkv, bkv)
    nkv = lkv // bkv

    kc = k.reshape(b, hkv, nkv, bkv, dh)
    vc = v.reshape(b, hkv, nkv, bkv, dh)
    qf = q.astype(jnp.float32) * scale
    qpos = q_offset + jnp.arange(lq)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, ci = inp                       # (B,Hkv,bkv,dh) x2, scalar
        kb = jnp.repeat(kb.astype(jnp.float32), group, axis=1)
        vb = jnp.repeat(vb.astype(jnp.float32), group, axis=1)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kb)
        kpos = ci * bkv + jnp.arange(bkv)
        mask = jnp.ones((lq, bkv), bool)
        if causal:
            mask &= qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = alpha * l + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, vb)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, lq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, lq), jnp.float32)
    a0 = jnp.zeros((b, hq, lq, dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kc.transpose(2, 0, 1, 3, 4), vc.transpose(2, 0, 1, 3, 4),
         jnp.arange(nkv)))
    safe = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe[..., None]).astype(q.dtype)


def _decode_attn(q, k_cache, v_cache, *, pos, window, cache_len):
    """q: (B, Hq, 1, dh); caches (B, Hkv, S, dh); attend to entries < pos+1.

    With a rolling (SWA) cache the entries are position-tagged modulo the
    cache length, so validity is derived from absolute positions.  ``pos``
    may be a scalar (one shared position, the classic batched decode) or a
    ``(B,)`` vector (per-slot positions, continuous batching): the masks
    vectorize over the batch and each row computes exactly what it would
    with that row's scalar position.
    """
    b, hq, _, dh = q.shape
    hkv, s = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    scale = 1.0 / (dh ** 0.5)
    kf = jnp.repeat(k_cache.astype(jnp.float32), group, axis=1)
    vf = jnp.repeat(v_cache.astype(jnp.float32), group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32) * scale, kf)
    slots = jnp.arange(s)
    pos_a = jnp.asarray(pos)
    if pos_a.ndim:
        pos_b, slots = pos_a[:, None], slots[None, :]      # (B, 1) x (1, S)
    else:
        pos_b = pos_a
    if window is None:
        valid = slots <= pos_b                     # linear cache
    elif cache_len > window:
        valid = (slots <= pos_b) & (slots > pos_b - window)  # linear + SWA
    else:
        # rolling cache: slot holds absolute position p iff p = pos - ((pos -
        # slot) mod S); valid iff within window and <= pos (always true once
        # warm). Entries beyond pos when cold (pos < S) are invalid.
        abs_pos = pos_b - ((pos_b - slots) % s)
        valid = (abs_pos >= 0) & (abs_pos > pos_b - window)
    valid = (valid[:, None, None, :] if pos_a.ndim
        else valid[None, None, None, :])
    scores = jnp.where(valid, scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Block apply
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class KVCache:
    k: jax.Array          # (B, S_cache, kv_dim)
    v: jax.Array
    # absolute write position is carried by the caller (shared across layers)


def make_kv_cache(cfg: ModelConfig, batch: int, seq_len: int,
                  dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Rolling cache for SWA archs (window slots), linear otherwise."""
    s = seq_len if cfg.swa_window is None else min(seq_len, cfg.swa_window)
    shape = (batch, s, cfg.kv_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


@jax.named_scope("attention")
def apply_attention(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                    kv_x: Optional[jax.Array] = None,
                    causal: bool = True,
                    positions: Optional[jax.Array] = None,
                    cache: Optional[Dict[str, jax.Array]] = None,
                    pos=None,
                    collect_kv: bool = False,
                    ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """One attention block on per-layer (already unstacked) params.

    x: (B, Lq, D).  Self-attention when ``kv_x`` is None.  With ``cache``
    (decode): Lq == 1, new K/V are written at ``pos`` and attention runs
    over the cache.  Returns (out, updated_cache_or_None).
    """
    b, lq, d = x.shape
    is_self = kv_x is None
    kv_src = x if is_self else kv_x
    compute = jnp.dtype(cfg.dtype)
    static_cross = (cache is not None) and not is_self

    def heads(t, n):
        return t.reshape(b, -1, n, cfg.head_dim).transpose(0, 2, 1, 3)

    q = k = v = None
    if cfg.explicit_collectives and is_self and not static_cross:
        # fully-manual SP->TP dataflow: gather + q/k/v dots in one shard_map
        from .explicit_tp import qkv_manual
        res = qkv_manual(x, p["wq"].astype(compute), p["wk"].astype(compute),
                         p["wv"].astype(compute), compute)
        if res is not None:
            q, k, v = res

    if q is None:
        # SP -> TP boundary: gather the (bf16) sequence shards explicitly
        xq = x.astype(compute)
        gathered = None
        if cfg.explicit_collectives:
            from .explicit_tp import gather_seq
            gathered = gather_seq(xq)
        xq = gathered if gathered is not None else common.shard_pinned(
            xq, ("pod", "data"), None, None)
        kv_src = xq if is_self else kv_src
        q = xq @ p["wq"].astype(compute)
        if not static_cross:
            xkv = kv_src.astype(compute)
            k = xkv @ p["wk"].astype(compute)
            v = xkv @ p["wv"].astype(compute)

    if cfg.qkv_bias:
        q = q + p["bq"].astype(compute)
    q = shard(q, ("pod", "data"), None, "model")
    qh = heads(q, cfg.n_heads)                    # (B, Hq, Lq, dh)

    if not static_cross:
        if cfg.qkv_bias:
            k = k + p["bk"].astype(compute)
            v = v + p["bv"].astype(compute)
        k = shard(k, ("pod", "data"), None, None)
        v = shard(v, ("pod", "data"), None, None)
        kh = heads(k, cfg.n_kv_heads)
        vh = heads(v, cfg.n_kv_heads)

    if is_self:
        if positions is None:
            if pos is None:
                positions = jnp.arange(lq)
            elif jnp.asarray(pos).ndim:
                # per-slot positions (continuous batching): (B, lq) rope
                positions = jnp.broadcast_to(
                    jnp.asarray(pos, jnp.int32)[:, None], (b, lq))
            else:
                positions = jnp.full((lq,), pos, jnp.int32)
        qh = common.rope(qh, positions, cfg.rope_theta)
        if not static_cross:
            kh = common.rope(kh, positions, cfg.rope_theta)

    def from_cache(c):
        s_cache = c.shape[1]
        return c.reshape(b, s_cache, cfg.n_kv_heads, cfg.head_dim
                         ).transpose(0, 2, 1, 3).astype(compute)

    new_cache = None
    if static_cross:
        # read-only precomputed cross K/V (e.g. whisper encoder output):
        # non-causal attention over the full cache, no update
        s_cache = cache["k"].shape[1]
        out = _decode_attn(qh, from_cache(cache["k"]), from_cache(cache["v"]),
                           pos=s_cache - 1, window=None, cache_len=s_cache)
    elif cache is not None:
        s_cache = cache["k"].shape[1]
        k_flat = kh.transpose(0, 2, 1, 3).reshape(b, lq, cfg.kv_dim)
        v_flat = vh.transpose(0, 2, 1, 3).reshape(b, lq, cfg.kv_dim)
        if jnp.asarray(pos).ndim:
            # per-slot write positions: one scatter row per batch lane
            slot = jnp.asarray(pos, jnp.int32) % s_cache
            rows = jnp.arange(b)
            ck = cache["k"].at[rows, slot].set(
                k_flat[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows, slot].set(
                v_flat[:, 0].astype(cache["v"].dtype))
        else:
            slot = pos % s_cache
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k_flat.astype(cache["k"].dtype), (0, slot, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v_flat.astype(cache["v"].dtype), (0, slot, 0))
        new_cache = {"k": ck, "v": cv}
        # rope for cached keys is applied at write time (above); a rolling
        # cache stores *rotated* keys, which is fine because rope is
        # absolute-position — each key was rotated at its own position.
        out = _decode_attn(qh, from_cache(ck), from_cache(cv), pos=pos,
                           window=cfg.swa_window, cache_len=s_cache)
    else:
        lkv = kh.shape[2]
        window = cfg.swa_window if is_self else None
        use_causal = causal and is_self
        # score tensors shard over heads when the head count divides the
        # model axis; otherwise over query rows (attention rows are
        # independent) — whisper's 12 heads don't divide a 16-way axis and
        # would otherwise replicate (B, H, Lq, Lkv) per device
        mesh = jax.sharding.get_abstract_mesh()
        model_size = dict(zip(mesh.axis_names, mesh.axis_sizes)
                          ).get("model", 1) if mesh.axis_names else 1
        heads_ok = cfg.n_heads % max(model_size, 1) == 0
        if heads_ok:
            qh = shard(qh, ("pod", "data"), "model", None, None)
        else:
            qh = shard(qh, ("pod", "data"), None, "model", None)
        if lkv <= FULL_SCORES_MAX_LEN:
            out = _full_scores_attn(qh, kh, vh, causal=use_causal,
                                    window=window)
        else:
            out = None
            if cfg.explicit_collectives:
                from .explicit_tp import chunked_attn_manual
                out = chunked_attn_manual(qh, kh, vh, causal=use_causal,
                                          window=window)
            if out is None:
                bkv = (1024 if lkv % 1024 == 0 else
                    next(b for b in (512, 256, 128, 64, 1)
                         if lkv % b == 0))
                out = _chunked_attn(qh, kh, vh, causal=use_causal,
                                    window=window, bkv=bkv)
        if collect_kv:
            # prefill: hand rotated K / V back for the decode cache
            new_cache = {
                "k": kh.transpose(0, 2, 1, 3).reshape(b, lkv, cfg.kv_dim),
                "v": vh.transpose(0, 2, 1, 3).reshape(b, lkv, cfg.kv_dim),
            }

    out = out.transpose(0, 2, 1, 3).reshape(b, lq, cfg.q_dim)
    out = shard(out, ("pod", "data"), None, "model")
    wo = p["wo"].astype(compute)
    if cfg.explicit_collectives and cfg.sequence_parallel:
        from .explicit_tp import project_scatter
        res = project_scatter(out, wo)
        if res is not None:
            return res.astype(x.dtype), new_cache
    out = jnp.dot(out, wo, preferred_element_type=jnp.float32)
    if cfg.sequence_parallel:
        # TP -> SP boundary: constrain the raw dot output so the partitioner
        # emits a reduce-scatter, not all-reduce + slice
        out = shard(out, ("pod", "data"), "model", None)
    return out.astype(x.dtype), new_cache


def precompute_cross_cache(p: Dict, enc_out: jax.Array, cfg: ModelConfig,
                           dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Project encoder output to K/V once; decode steps read it statically."""
    compute = jnp.dtype(cfg.dtype)
    xkv = enc_out.astype(compute)
    k = xkv @ p["wk"].astype(compute)
    v = xkv @ p["wv"].astype(compute)
    if cfg.qkv_bias:
        k = k + p["bk"].astype(compute)
        v = v + p["bv"].astype(compute)
    return {"k": k.astype(dtype), "v": v.astype(dtype)}
