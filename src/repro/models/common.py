"""Shared model machinery: parameters with logical sharding axes, norms,
RoPE, losses.

Parameters are built as ``Leaf(value, logical_axes)`` pytrees; ``split``
separates them into (params, PartitionSpec) trees.  Logical axes map to mesh
axes through ``AxisRules`` (MaxText-style), with a divisibility fallback so
one rule set serves all ten architectures (e.g. whisper's 12 heads can't
shard over a 16-way model axis and silently fall back to replicated).

This is the mesh-level half of the paper's technique applied to the LM
stack: a tensor whose reuse class is *stationary* along an axis gets sharded
there (memory bank assignment, deviation D4), *multicast* tensors are
replicated/all-gathered, *reduction* outputs psum — see dist/schedules.py
for the explicit GEMM schedules and train/loss.py for their use.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


# ---------------------------------------------------------------------------
# Parameter leaves with logical axes
# ---------------------------------------------------------------------------

@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class Logical:
    """Static marker carrying logical axis names for one param."""
    axes: Tuple[Optional[str], ...]


class Leaf(tuple):
    """(value, Logical) pair that tree_map treats as a leaf via is_leaf."""
    def __new__(cls, value, axes):
        return super().__new__(cls, (value, Logical(tuple(axes))))


def is_leaf(x) -> bool:
    return isinstance(x, Leaf)


def split(tree) -> Tuple[Any, Any]:
    """Leaf pytree -> (params pytree, logical-axes pytree)."""
    params = jax.tree.map(lambda l: l[0], tree, is_leaf=is_leaf)
    axes = jax.tree.map(lambda l: l[1], tree, is_leaf=is_leaf)
    return params, axes


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """logical axis -> mesh axis (or tuple of mesh axes)."""
    rules: Dict[str, Union[str, Tuple[str, ...], None]]

    def spec_for(self, axes: Logical, shape: Tuple[int, ...],
                 mesh_shape: Dict[str, int]) -> P:
        out = []
        for dim, name in zip(shape, axes.axes):
            mesh_ax = self.rules.get(name) if name else None
            if mesh_ax is None:
                out.append(None)
                continue
            size = 1
            for ax in ((mesh_ax,) if isinstance(mesh_ax, str) else mesh_ax):
                size *= mesh_shape.get(ax, 1)
            # divisibility fallback: replicate rather than force padding
            out.append(mesh_ax if dim % size == 0 else None)
        return P(*out)

    def specs(self, axes_tree, shapes_tree, mesh_shape) -> Any:
        return jax.tree.map(
            lambda a, s: self.spec_for(a, s.shape, mesh_shape),
            axes_tree, shapes_tree,
            is_leaf=lambda x: isinstance(x, Logical))


#: default rules for the production mesh (pod, data, model):
#:   fsdp  — parameter & optimizer-state sharding over the data axis (ZeRO-3)
#:   tp    — tensor-parallel over the model axis
DEFAULT_RULES = AxisRules({
    "embed": "data",        # d_model dim of weights: FSDP
    "heads": "model",       # attention heads / q projection out-dim
    "kv": "model",          # kv projection out-dim (flattened kv_dim)
    "mlp": "model",         # d_ff
    "vocab": "model",       # embedding table / logits
    "layers": None,         # stacked-scan layer dim stays unsharded
    "expert": None,         # experts replicated; TP inside experts ("mlp")
    "ssm_inner": "model",   # mamba d_inner
    "ssm_state": None,
    "batch": ("pod", "data"),
    "seq": "model",         # sequence parallelism for residual activations
})


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, in_dim: int, out_dim: int,
               axes: Sequence[Optional[str]],
               scale: Optional[float] = None) -> Leaf:
    scale = scale if scale is not None else (1.0 / in_dim) ** 0.5
    w = jax.random.normal(key, (in_dim, out_dim), jnp.float32) * scale
    return Leaf(w, axes)


def stacked_dense_init(key, n: int, in_dim: int, out_dim: int,
                       axes: Sequence[Optional[str]],
                       scale: Optional[float] = None) -> Leaf:
    scale = scale if scale is not None else (1.0 / in_dim) ** 0.5
    w = jax.random.normal(key, (n, in_dim, out_dim), jnp.float32) * scale
    return Leaf(w, ("layers", *axes))


def zeros_init(shape: Tuple[int, ...], axes: Sequence[Optional[str]]) -> Leaf:
    return Leaf(jnp.zeros(shape, jnp.float32), axes)


def ones_init(shape: Tuple[int, ...], axes: Sequence[Optional[str]]) -> Leaf:
    return Leaf(jnp.ones(shape, jnp.float32), axes)


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * gamma).astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array,
         theta: float = 10_000.0) -> jax.Array:
    """Rotary embedding.  x: (..., L, D even), positions: (L,) or (B, L)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs     # (..., L, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast across head dims: x (..., H, L, D) vs ang (L, half)/(B,L,half)
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  mask: Optional[jax.Array] = None) -> jax.Array:
    """Token-mean CE in fp32; logits may stay vocab-sharded (the log-softmax
    reduction is over the last axis, which GSPMD keeps sharded)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    return jnp.mean(nll)


def shard(x: jax.Array, *axes) -> jax.Array:
    """with_sharding_constraint that resolves axis names against the active
    mesh: missing axes (e.g. 'pod' on a single-pod mesh) and non-divisible
    dims fall back to replicated; outside any mesh context it is a no-op.

    This keeps one set of constraints valid across the 1-device test mesh,
    the 16x16 pod and the 2x16x16 multi-pod mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    names = dict(zip(mesh.axis_names, mesh.axis_sizes))
    spec = []
    for dim, a in zip(x.shape, axes):
        cand = (a,) if (a is None or isinstance(a, str)) else tuple(a)
        cand = tuple(c for c in cand if c is not None and c in names)
        size = 1
        for c in cand:
            size *= names[c]
        if not cand or size <= 1 or dim % size != 0:
            spec.append(None)
        elif len(cand) == 1:
            spec.append(cand[0])
        else:
            spec.append(cand)
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, P(*spec))


def shard_pinned(x: jax.Array, *axes) -> jax.Array:
    """``shard`` + optimization barrier: pins the resharding collective to
    THIS value.  Used at SP->TP boundaries so the all-gather runs on the
    bf16 activation instead of being commuted past the f32 upcast that the
    CPU/XLA dot emulation inserts (which would double the wire bytes)."""
    y = shard(x, *axes)
    if y is x:
        return x
    return jax.lax.optimization_barrier(y)
