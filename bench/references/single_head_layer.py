"""Plain float32 forward of the generator's single-head layer graph.

The layer the graph cell generates (one attention head, no RoPE, GQA or
norms, a bias-GELU MLP), weights stored ``(out, in)``:

    q = x wq^T    k = x wk^T    v^T = wv_t x^T
    p = softmax(q k^T / sqrt(d))        a = p v
    r1 = a wo^T + x                     h = gelu_tanh(r1 w1^T + b1)
    out = h w2^T + r1

Every product at ``Precision.HIGHEST`` in float32, nothing rounded in
between.  ``control=True`` rounds every product's operands to float8
(e4m3, per-tensor scale), one step below the bfloat16 the cell runs in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
_F8_MAX = 448.0


def _fp8(a):
    scale = _F8_MAX / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def _gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def forward(ops, control: bool = False):
    """ops: the graph's inputs by edge name -> the layer output (l, d)."""
    q8 = _fp8 if control else (lambda a: a)
    f = {k: jnp.asarray(v, F32) for k, v in ops.items()}
    x = f["x"]

    def mm(a, b_t):                      # a @ b_t.T
        return jnp.dot(q8(a), q8(b_t).T, precision=HI)

    d = x.shape[-1]
    q, k = mm(x, f["wq"]), mm(x, f["wk"])
    vt = mm(f["wv_t"], x)                                    # (dv, l)
    p = jax.nn.softmax(mm(q, k) / math.sqrt(d), axis=-1)
    a = mm(p, vt)
    r1 = mm(a, f["wo"]) + x
    h = _gelu_tanh(mm(r1, f["w1"]) + f["b1"])
    return mm(h, f["w2"]) + r1
