"""Blockwise online-softmax attention (flash attention) for TPU.

TPU adaptation of the attention hot-spot: the KV sequence is the Pallas
*time* axis (innermost, "arbitrary" semantics), the running (m, l, acc)
statistics are the *stationary* tensors held in VMEM scratch — i.e. the
attention kernel is itself an output-stationary STT dataflow over the
(q_block, kv_block) loop nest, which is how the paper's technique picks this
template (see core.plan).

Features: GQA (q-head to kv-head mapping in the BlockSpec index_map), causal
masking, sliding-window (SWA), and cross-attention (no mask).  fp32 softmax,
inputs may be bf16/fp32.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = float(-1e30)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: Optional[int],
                 bq: int, bkv: int, n_kv: int, out_dtype):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)               # (bkv, d)
    v = v_ref[0, 0].astype(jnp.float32)               # (bkv, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    if causal or window is not None:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        kpos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = jnp.ones((bq, bkv), dtype=bool)
        if causal:
            mask &= qpos >= kpos
        if window is not None:
            mask &= qpos - kpos < window
        s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, 0]                           # (bq,)
    m_cur = jnp.maximum(m_prev, s.max(axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    # guard fully-masked rows: s == m_cur == NEG_INF must give p = 0, not 1
    p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_cur[:, None]), 0.0)
    l_cur = alpha * l_ref[:, 0] + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_cur[:, None], m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur[:, None], l_ref.shape)

    @pl.when(ki == n_kv - 1)
    def _flush():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)         # fully-masked rows -> 0
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(out_dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    bq: int = 128, bkv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Lq, D);  k, v: (B, Hkv, Lkv, D);  Hq % Hkv == 0.

    Grid: (B, Hq, Lq/bq, Lkv/bkv) — kv innermost so the online-softmax
    statistics stay resident; q/k/v blocks stream through the pipeline.
    """
    b, hq, lq, d = q.shape
    _, hkv, lkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"GQA requires Hq % Hkv == 0, got {hq}, {hkv}")
    group = hq // hkv
    bq = min(bq, lq)
    bkv = min(bkv, lkv)
    if lq % bq or lkv % bkv:
        raise ValueError(f"seq lens ({lq},{lkv}) not divisible by blocks "
                         f"({bq},{bkv}); ops.attention pads first")
    n_kv = lkv // bkv
    kernel = functools.partial(
        _attn_kernel, scale=1.0 / (d ** 0.5), causal=causal, window=window,
        bq=bq, bkv=bkv, n_kv=n_kv, out_dtype=q.dtype)
    grid = (b, hq, lq // bq, n_kv)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bb, h, qi, ki: (bb, h, qi, 0)),
            # GQA: q head h reads kv head h // group
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bb, h, qi, ki: (bb, h // group, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bb, h, qi, ki: (bb, h // group, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bb, h, qi, ki: (bb, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, lq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max m
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom l
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
