"""Public jit'd wrappers for the Pallas kernels.

These handle padding to block multiples, dtype policy (bf16 in / fp32
accumulate), template dispatch from an STT ``KernelPlan``, and the
CPU fallback (``backend='xla'`` routes to the jnp oracle so the same call
sites work in dry-runs and on real TPUs).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.plan import KernelPlan
from . import bsr_gemm as _bsr
from . import epilogue as _ep
from . import flash_attention as _fa
from . import ref as _ref
from . import ssd_scan as _ssd
from . import stt_gemm as _gemm


def _pad_to(x: jax.Array, mults: tuple) -> jax.Array:
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


# ---------------------------------------------------------------------------
# GEMM
# ---------------------------------------------------------------------------

def resolve_accum(accum: str, out_dtype) -> str:
    """The accumulation-strategy policy (the bf16 knob the tuner selects
    over): ``"auto"`` picks the numerically safe default — fp32 scratch
    accumulation — for *every* dtype, because bf16 inputs lose reduction
    precision when partial sums round to bf16 each k-step.  The tuner may
    explicitly select ``"inplace"`` (direct accumulation in the output
    dtype — for bf16, the bf16-direct strategy) when the variant still
    validates within tolerance; callers can force either mode."""
    if accum == "auto":
        return "scratch"
    if accum not in _gemm.ACCUM_MODES:
        raise ValueError(f"accum must be 'auto' or one of "
                         f"{_gemm.ACCUM_MODES}, got {accum!r}")
    return accum


def _rt_order(grid_order: str) -> str:
    """Project a 3-axis grid order onto the reduction-tree's (m, n) grid
    (its whole reduction runs inside one MXU pass, so 'k' drops out)."""
    if grid_order == "default":
        return "mn"
    order = "".join(c for c in grid_order if c in "mn")
    return order if order in _gemm.RT_GRID_ORDERS else "mn"


@functools.partial(jax.jit, static_argnames=(
    "template", "stationary", "bm", "bn", "bk", "backend", "interpret",
    "vmem_budget", "grid_order", "accum", "epilogue"))
def stt_matmul(a: jax.Array, b: jax.Array, *, template: str = "output_stationary",
               stationary: str = "B", bm: int = 128, bn: int = 128,
               bk: int = 128, backend: str = "pallas",
               interpret: bool = False,
               vmem_budget: Optional[int] = _gemm.DEFAULT_VMEM_BUDGET,
               grid_order: str = "default", accum: str = "auto",
               epilogue: tuple = (),
               bias: Optional[jax.Array] = None
               ) -> jax.Array:
    """C = A @ B with the Pallas template selected by an STT dataflow.

    Operands may carry a leading batch dim (``(B, m, k) @ (B, k, n)``; a
    rank-2 operand broadcasts across the batch) — the templates fold it
    onto a leading parallel grid axis, so a grid-folded algebra lowering
    executes exactly the algebra's MACs.  Per-slice m/n/k are padded to
    block multiples; the batch dim never needs padding (batch block = 1).

    ``vmem_budget`` caps the operand-stationary kernel's VMEM, whose
    strip accumulator is allocated **per batch slice**: when the
    per-slice (m, bn) fp32 strip and its blocks would not fit
    (``stt_gemm.vmem_bytes``), the call falls back to the output-stationary
    template (same math, block-local residency) instead of erroring — the
    compile pipeline relies on this safety net.

    ``grid_order`` and ``accum`` are the measured-autotuning knobs (see
    ``kernels/stt_gemm.py``): contraction grid order for the output-
    stationary / reduction-tree templates, and the accumulation strategy
    (``resolve_accum``).  The operand-stationary template has its own
    fixed streaming order, so the knobs apply to it only after the VMEM
    fallback reroutes to the output-stationary template.

    ``epilogue`` is a static tuple of post-processing ops
    (``kernels/epilogue.py``) fused into the template's output-block
    flush; ``bias`` is the extra rank-1 operand a ``"bias"`` op streams.
    A ``"softmax"`` op needs one block spanning the whole unpadded row
    (``bn >= n``) — a partial or padded row cannot be normalized
    block-locally — so the call raises instead of silently computing a
    wrong softmax; the graph planner treats that as fusion illegality
    and applies the epilogue outside the kernel.
    """
    epilogue = _ep.validate_spec(epilogue)
    if backend == "xla":
        out = _ref.matmul_ref(a, b, out_dtype=jnp.float32)
        if epilogue:
            out = _ep.apply_epilogue(out, epilogue, bias=bias)
        return out.astype(a.dtype)
    m, k = a.shape[-2:]
    n = b.shape[-1]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if _ep.has_softmax(epilogue) and (bn != n or n % bn):
        raise ValueError(
            f"softmax epilogue needs one unpadded output block covering "
            f"the full row: bn >= n and n % bn == 0 (got bn={bn}, n={n})")
    ap = _pad_to(a, (1,) * (a.ndim - 2) + (bm, bk))
    bp = _pad_to(b, (1,) * (b.ndim - 2) + (bk, bn))
    if bias is not None:
        # padded n columns get bias 0 and are sliced off below
        bias = _pad_to(jnp.asarray(bias), (bn,))
    if epilogue and template == "operand_stationary" and stationary == "A":
        # the input-stationary realization transposes m/n (stt_gemm), so
        # a last-axis epilogue cannot ride it; same math, other template
        template = "output_stationary"
    if template == "operand_stationary" and vmem_budget is not None:
        # the strip extent follows the *streamed-output* dimension of one
        # batch slice: M for stationary B, N for stationary A
        # (transposition symmetry, handled inside vmem_bytes)
        dims = (ap.shape[-2], bp.shape[-1], ap.shape[-1])
        if (_gemm.vmem_bytes(template, dims, (bm, bn, bk),
                             a.dtype.itemsize, stationary=stationary)
                > vmem_budget):
            template = "output_stationary"
    kw = dict(bm=bm, bn=bn, bk=bk, interpret=interpret,
              epilogue=epilogue, bias=bias)
    if template == "output_stationary":
        out = _gemm.matmul_output_stationary(
            ap, bp, grid_order=grid_order,
            accum=resolve_accum(accum, a.dtype), **kw)
    elif template == "operand_stationary":
        out = _gemm.matmul_operand_stationary(ap, bp, stationary=stationary,
                                              vmem_budget=vmem_budget, **kw)
    elif template in ("reduction_tree", "streaming"):
        kw.pop("bk")
        out = _gemm.matmul_reduction_tree(ap, bp,
                                          grid_order=_rt_order(grid_order),
                                          **kw)
    else:
        raise ValueError(f"unknown template {template!r}")
    return out[..., :m, :n]


@functools.partial(jax.jit, static_argnames=(
    "coords", "block", "bstream", "side", "backend", "interpret"))
def bsr_matmul(sparse: jax.Array, dense: jax.Array, *,
               coords: _bsr.Coords, block: tuple, bstream: int = 128,
               side: str = "lhs", backend: str = "pallas",
               interpret: bool = False) -> jax.Array:
    """Block-sparse GEMM with one block-COO operand (zeros outside the
    static ``coords`` pattern are skipped by the kernel grid).

    ``side='lhs'``: C = sparse @ dense, ``sparse`` (m, k) with ``block`` =
    (bm, bk) blocks; ``bstream`` tiles the streamed n dimension.
    ``side='rhs'``: C = dense @ sparse, realized by transposition symmetry
    (C^T = sparse^T @ dense^T) so one kernel serves both operand sides.
    ``backend='xla'`` routes to a plain jnp matmul (the operand is already
    masked, so the dense product is the masked oracle).
    """
    if side not in ("lhs", "rhs"):
        raise ValueError(f"side must be 'lhs' or 'rhs', got {side!r}")
    if backend == "xla":
        out = (sparse @ dense) if side == "lhs" else (dense @ sparse)
        return out
    if side == "rhs":
        return bsr_matmul(sparse.T, dense.T,
                          coords=_bsr.transpose_coords(coords),
                          block=(block[1], block[0]), bstream=bstream,
                          side="lhs", backend=backend, interpret=interpret).T
    bm, bk = block
    return _bsr.bsr_matmul(sparse, dense, coords=coords, bm=bm, bk=bk,
                           bn=bstream, interpret=interpret)


def matmul_from_plan(plan: KernelPlan, a: jax.Array, b: jax.Array,
                     **kw) -> jax.Array:
    """Dispatch a GEMM according to a generated KernelPlan — the paper's
    'select modules from the dataflow' step, at call granularity."""
    stationary = "B" if plan.resident_tensor in (None, "B", "C") else "A"
    return stt_matmul(a, b, template=plan.template, stationary=stationary,
                      **kw)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "bq", "bkv", "backend", "interpret"))
def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True, window: Optional[int] = None,
              bq: int = 128, bkv: int = 128, backend: str = "pallas",
              interpret: bool = False) -> jax.Array:
    """GQA attention (B, Hq, Lq, D) x (B, Hkv, Lkv, D) -> (B, Hq, Lq, D)."""
    if backend == "xla":
        return _ref.attention_ref(q, k, v, causal=causal, window=window)
    lq, lkv = q.shape[2], k.shape[2]
    bq, bkv = min(bq, lq), min(bkv, lkv)
    qp = _pad_to(q, (1, 1, bq, 1))
    kp = _pad_to(k, (1, 1, bkv, 1))
    vp = _pad_to(v, (1, 1, bkv, 1))
    # padded kv columns must not contribute: they are masked iff causal;
    # for non-causal padding we mask via window trick — instead just require
    # the caller to pad explicitly for cross-attention.
    if not causal and (kp.shape[2] != lkv):
        raise ValueError("cross-attention requires Lkv % bkv == 0")
    out = _fa.flash_attention(qp, kp, vp, causal=causal, window=window,
                              bq=bq, bkv=bkv, interpret=interpret)
    return out[:, :, :lq]


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk", "backend", "interpret"))
def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
        c: jax.Array, *, chunk: int = 64, backend: str = "pallas",
        interpret: bool = False) -> jax.Array:
    """Mamba-2 SSD:  x (B, L, H, P), dt (B, L, H), a (H,),
    b/c (B, L, G, N) -> y (B, L, H, P)."""
    if backend == "xla":
        return _ref.ssd_chunked_ref(x, dt, a, b, c, chunk=chunk)[0]
    bsz, l, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    rep = h // g
    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    da = dt.astype(jnp.float32) * a.astype(jnp.float32)
    bf = jnp.repeat(b.astype(jnp.float32), rep, axis=2)
    cf = jnp.repeat(c.astype(jnp.float32), rep, axis=2)
    # flatten (B, H) and move L inside: (B*H, L, ...)
    def flat(t):
        return t.transpose(0, 2, 1, *range(3, t.ndim)).reshape(
            bsz * h, l, *t.shape[3:])
    y = _ssd.ssd_scan(flat(xdt), da.transpose(0, 2, 1).reshape(bsz * h, l),
                      flat(bf), flat(cf), chunk=chunk, interpret=interpret)
    return y.reshape(bsz, h, l, p).transpose(0, 2, 1, 3).astype(x.dtype)
