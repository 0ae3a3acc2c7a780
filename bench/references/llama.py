"""Plain float32 forward pass of a dense llama-family decoder.

Follows the published architecture (Llama 2 / Mistral, which
h2o-danube-1.8b and Granite Code 8B use): token embedding; per layer an
RMSNorm, grouped-query attention with rotary position embedding over
the two halves of each head (the Hugging Face ``rotate_half`` layout),
a causal mask and, where ``sliding_window`` is set, a window of that
many positions, a residual add, an RMSNorm and a SwiGLU MLP
(``down(silu(gate(x)) * up(x))``) with a residual add; a final RMSNorm
and the output head (the embedding's transpose when tied).  No cache,
no batching, no kernels: one sequence, every position at once, every
product at ``Precision.HIGHEST`` in float32.

Weights are read from the parameter tree the benchmark made, by the
names the served program takes (``embed``, ``unembed``, ``final_norm``,
``layers/{ln1,ln2}``, ``layers/attn/{wq,wk,wv,wo}``,
``layers/ffn/{wg,wu,wd}``), each matrix stored ``(in, out)``.

``control=True`` computes the same in float8 (e4m3, per-tensor scale):
every matrix product's operands, the attention's included, are rounded
to it.  That is the precision one step below the bfloat16 the
configuration serves in, and the check must reject it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
_F8_MAX = 448.0          # largest finite float8_e4m3fn


def _fp8(a):
    scale = _F8_MAX / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def _rmsnorm(x, gamma, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gamma.astype(F32)


def forward(params, tokens, conf, control: bool = False):
    """tokens: (S,) int32 -> logits (S, vocab) in float32."""
    q8 = _fp8 if control else (lambda a: a)
    heads = conf["num_attention_heads"]
    kv_heads = conf["num_key_value_heads"]
    dh = conf.get("head_dim") or conf["hidden_size"] // heads
    eps = conf["rms_norm_eps"]
    window = conf.get("sliding_window")
    s = tokens.shape[0]

    def mm(a, w):
        return jnp.dot(q8(a), q8(w.astype(F32)), precision=HI)

    pos = jnp.arange(s)
    half = dh // 2
    freqs = conf["rope_theta"] ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None].astype(F32) * freqs[None, :]          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(t):                                             # (S, H, dh)
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin],
                               axis=-1)

    allowed = pos[:, None] >= pos[None, :]
    if window:
        allowed &= pos[:, None] - pos[None, :] < window

    def attend(q, k, v):                     # (S, G, dh), (S, dh) x 2
        scores = jnp.einsum("qgd,kd->gqk", q8(q), q8(k),
                            precision=HI) / jnp.sqrt(F32(dh))
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("gqk,kd->qgd", q8(p), q8(v), precision=HI)

    def layer(x, lp):
        a, f = lp["attn"], lp["ffn"]
        h = _rmsnorm(x, lp["ln1"], eps)
        q = rope(mm(h, a["wq"]).reshape(s, heads, dh))
        k = rope(mm(h, a["wk"]).reshape(s, kv_heads, dh))
        v = mm(h, a["wv"]).reshape(s, kv_heads, dh)
        # query head h reads key/value head h // (heads // kv_heads); one
        # such group's scores at a time, so a long sequence fits
        qg = q.reshape(s, kv_heads, heads // kv_heads, dh).swapaxes(0, 1)
        o = jax.lax.map(lambda t: attend(*t),
                        (qg, k.swapaxes(0, 1), v.swapaxes(0, 1)))
        x = x + mm(o.swapaxes(0, 1).reshape(s, heads * dh), a["wo"])
        h = _rmsnorm(x, lp["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(h, f["wg"])) * mm(h, f["wu"]), f["wd"])
        return x, None

    x = params["embed"][tokens].astype(F32)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"], eps)
    head = (params["embed"].T if conf["tie_word_embeddings"]
            else params["unembed"])
    return mm(x, head)
