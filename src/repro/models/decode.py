"""Prefill and single-token decode for every architecture family.

The decode cache layout (one pytree, sharded like activations):

    {"pos":   () or (B,) int32 — absolute position of the NEXT token
              (a (B,) vector gives every sequence its own position, which
              is what lets the serving slot engine mix sequences of
              different lengths in one jitted decode batch),
     "self":  {"k","v"} (L, B, S_c, kv_dim)      attention families
     "ssm":   {"conv","state"} (L, B, ...)       ssm / hybrid
     "shared":{"k","v"} (n_apps, B, S_c, kv_dim) hybrid shared-attn
     "cross": {"k","v"} (L|n_cross, B, F, kv_dim) encdec / vlm (static)}

SWA archs use rolling caches of ``window`` slots; prefill fills them with
the last ``window`` positions (valid because window divides the assigned
sequence lengths).  decode_step lowers the ``serve_step`` of the dry-run's
decode cells: one new token against a seq_len-deep cache.

Where the step's per-layer self-attention K/V come from is the caller's
(:class:`LayerKV`): the per-call path scans the dense leaves above and
keeps every updated slice; the serving slot engine scans layer indices,
reads each layer from its page pool and keeps only the written rows.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import attention as attn
from . import mlp as mlp_mod
from . import ssm as ssm_mod
from .common import rmsnorm, shard
from .transformer import forward, hybrid_groups, scan_layers


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------

def _fit_cache(kv: Dict[str, jax.Array], window: Optional[int],
               max_len: int, s0: int) -> Dict[str, jax.Array]:
    """Resize collected (.., S0, kv_dim) K/V to the decode cache layout.

    Rolling caches (SWA) keep ``min(max_len, window)`` slots with slot
    ``i == abs_pos % s_cache`` (a roll re-aligns when s_cache does not
    divide S0); linear caches pad to ``max_len`` slots."""
    s_cache = max_len if window is None else min(max_len, window)

    def fit(a):
        if s0 >= s_cache:
            a = a[:, :, s0 - s_cache:]
            shift = s0 % s_cache
            if shift:
                a = jnp.roll(a, shift, axis=2)
            return a
        pad = [(0, 0)] * a.ndim
        pad[2] = (0, s_cache - s0)
        return jnp.pad(a, pad)

    return jax.tree.map(fit, kv)


def prefill(params: Dict, tokens: jax.Array, cfg: ModelConfig, *,
            frontend: Optional[jax.Array] = None,
            max_len: Optional[int] = None,
            ) -> Tuple[jax.Array, Dict[str, Any]]:
    """Run the full prompt, return (last-position logits, decode cache).

    ``max_len`` is the total context budget (prompt + generated); the cache
    allocates min(max_len, swa_window) slots."""
    b, s = tokens.shape
    max_len = max_len or s
    logits, _, caches = forward(params, tokens, cfg, frontend=frontend,
                                collect_cache=True)
    cache: Dict[str, Any] = {"pos": jnp.array(s, jnp.int32)}
    caches = caches or {}
    if "self" in caches:
        cache["self"] = _fit_cache(caches["self"], cfg.swa_window, max_len, s)
    if "ssm" in caches:
        cache["ssm"] = caches["ssm"]
    if "shared" in caches:
        cache["shared"] = _fit_cache(caches["shared"], cfg.swa_window,
                                     max_len, s)
    if cfg.family == "encdec":
        enc = caches["enc_out"]

        def cross_kv(pl_):
            return attn.precompute_cross_cache(pl_["cross"], enc, cfg)
        cache["cross"] = jax.vmap(cross_kv)(
            jax.tree.map(lambda a: a, params["layers"]))
    if cfg.family == "vlm":
        img = frontend.astype(jnp.dtype(cfg.dtype))

        def cross_kv(pl_):
            return attn.precompute_cross_cache(pl_, img, cfg)
        cache["cross"] = jax.vmap(cross_kv)(params["cross_layers"]["attn"])
    return logits[:, -1], cache


def init_cache(params: Dict, cfg: ModelConfig, batch: int, seq_len: int, *,
               frontend: Optional[jax.Array] = None,
               dtype=jnp.bfloat16) -> Dict[str, Any]:
    """Empty decode cache for a maximum context of ``seq_len`` (the decode
    dry-run cells build this from ShapeDtypeStructs via eval_shape)."""
    cache: Dict[str, Any] = {"pos": jnp.array(0, jnp.int32)}
    L = cfg.n_layers

    def kv(n, s):
        return {"k": jnp.zeros((n, batch, s, cfg.kv_dim), dtype),
                "v": jnp.zeros((n, batch, s, cfg.kv_dim), dtype)}

    s_c = seq_len if cfg.swa_window is None else min(seq_len, cfg.swa_window)
    if cfg.family in ("dense", "moe", "encdec", "vlm"):
        cache["self"] = kv(L, s_c)
    if cfg.family in ("ssm", "hybrid"):
        cache["ssm"] = {
            "conv": jnp.zeros((L, batch, cfg.conv_kernel - 1,
                               ssm_mod.conv_dim(cfg)), jnp.float32),
            "state": jnp.zeros((L, batch, cfg.ssm_heads, cfg.ssm_state,
                                cfg.ssm_head_dim), jnp.float32),
        }
    if cfg.family == "hybrid":
        n_apps, _, _ = hybrid_groups(cfg)
        cache["shared"] = kv(n_apps, s_c)
    if cfg.family == "encdec":
        enc_fwd, _, caches = forward(params, jnp.zeros((batch, 1), jnp.int32),
                                     cfg, frontend=frontend,
                                     collect_cache=True)
        del enc_fwd
        cache["cross"] = jax.vmap(
            lambda pl_: attn.precompute_cross_cache(pl_["cross"],
                                                    caches["enc_out"], cfg)
        )(params["layers"])
    if cfg.family == "vlm":
        img = frontend.astype(jnp.dtype(cfg.dtype))
        cache["cross"] = jax.vmap(
            lambda pl_: attn.precompute_cross_cache(pl_, img, cfg)
        )(params["cross_layers"]["attn"])
    return cache


# ---------------------------------------------------------------------------
# decode step
# ---------------------------------------------------------------------------

class LayerKV(NamedTuple):
    """How the layer loop gets one layer's self-attention K/V and what it
    keeps of the updated ones.

    ``cache["self"]`` / ``cache["shared"]`` hold what the loop slices per
    layer (a scan input, or a static index for hybrid's shared block);
    ``read(group, x)`` turns one layer's slice ``x`` into ``{"k", "v"}``
    ``(B, S, kv)`` blocks, and ``keep(group, kv, pos)`` picks what the
    loop stacks of the updated blocks — the step returns that stack under
    ``group``."""

    read: Callable[[str, Any], Dict[str, jax.Array]]
    keep: Callable[[str, Dict[str, jax.Array], jax.Array], Any]


#: the per-call cache: dense ``(L, B, S, kv)`` leaves, every updated
#: layer slice kept, so the step returns the whole updated cache
DENSE_KV = LayerKV(read=lambda group, kv: kv,
                   keep=lambda group, kv, pos: kv)


def decode_step(params: Dict, tokens: jax.Array, cache: Dict[str, Any],
                cfg: ModelConfig, kv: LayerKV = DENSE_KV
                ) -> Tuple[jax.Array, Dict[str, Any]]:
    """tokens: (B, 1) int32 — one new token per sequence.

    ``cache["pos"]`` may be a scalar (all sequences at the same position,
    the classic batched decode) or a ``(B,)`` vector (per-sequence
    positions, continuous batching); rope, validity masks and cache writes
    vectorize accordingly and each row computes exactly what it would with
    that row's scalar position.

    Named scopes for the compiled step's op metadata: ``attention`` and
    ``mlp`` (on ``apply_attention`` / ``apply_mlp`` / ``apply_moe``
    themselves) and ``head`` (final norm and logits).

    ``kv`` says where each layer's self-attention K/V come from and what
    the step returns of them (:class:`LayerKV`; the dense cache by
    default).  The attention math is the same either way.

    Returns (logits (B, vocab), updated cache)."""
    compute = jnp.dtype(cfg.dtype)
    pos = cache["pos"]
    x = jnp.take(params["embed"], tokens, axis=0).astype(compute)
    x = shard(x, ("pod", "data"), None, None)
    new_cache: Dict[str, Any] = {"pos": pos + 1}

    def self_attn(p, x, group, kv_l):
        h, kv_new = attn.apply_attention(p, x, cfg,
                                         cache=kv.read(group, kv_l), pos=pos)
        return h, kv.keep(group, kv_new, pos)

    if cfg.family in ("dense", "moe"):
        def body(pl_and_kv, x):
            pl_, kv_l = pl_and_kv
            h, kv_new = self_attn(
                pl_["attn"], rmsnorm(x, pl_["ln1"], cfg.norm_eps), "self",
                kv_l)
            x = x + h
            if "router" in pl_["ffn"]:
                h, _ = mlp_mod.apply_moe(
                    pl_["ffn"], rmsnorm(x, pl_["ln2"], cfg.norm_eps), cfg)
            else:
                h = mlp_mod.apply_mlp(
                    pl_["ffn"], rmsnorm(x, pl_["ln2"], cfg.norm_eps), cfg)
            return x + h, jnp.zeros((), jnp.float32), kv_new
        x, _, kv_out = scan_layers((params["layers"], cache["self"]),
                                   x, lambda inp, x: body(inp, x), cfg)
        new_cache["self"] = kv_out

    elif cfg.family == "ssm":
        def body(pl_and_c, x):
            pl_, conv, state = pl_and_c
            h, c_new = ssm_mod.apply_ssm(
                pl_["ssm"], rmsnorm(x, pl_["ln1"], cfg.norm_eps), cfg,
                cache={"conv": conv, "state": state})
            return x + h, jnp.zeros((), jnp.float32), c_new
        x, _, c = scan_layers(
            (params["layers"], cache["ssm"]["conv"], cache["ssm"]["state"]),
            x, lambda inp, x: body(inp, x), cfg)
        new_cache["ssm"] = c

    elif cfg.family == "hybrid":
        n_apps, gsz, tail = hybrid_groups(cfg)
        lay = params["layers"]
        main = jax.tree.map(
            lambda a: a[:n_apps * gsz].reshape(n_apps, gsz, *a.shape[1:]),
            lay)
        cmain = jax.tree.map(
            lambda a: a[:n_apps * gsz].reshape(n_apps, gsz, *a.shape[1:]),
            cache["ssm"])

        def body(pl_and_c, x):
            pl_, conv, state = pl_and_c
            h, c_new = ssm_mod.apply_ssm(
                pl_["ssm"], rmsnorm(x, pl_["ln1"], cfg.norm_eps), cfg,
                cache={"conv": conv, "state": state})
            return x + h, jnp.zeros((), jnp.float32), c_new

        ssm_new, shared_new = [], []
        for gi in range(n_apps):
            x, _, c = scan_layers(
                (jax.tree.map(lambda a: a[gi], main),
                 cmain["conv"][gi], cmain["state"][gi]),
                x, lambda inp, x: body(inp, x), cfg)
            ssm_new.append(c)
            ps = params["shared"]
            h, kv_new = self_attn(
                ps["attn"], rmsnorm(x, ps["ln1"], cfg.norm_eps), "shared",
                jax.tree.map(lambda a: a[gi], cache["shared"]))
            x = x + h
            h = mlp_mod.apply_mlp(ps["mlp"],
                                  rmsnorm(x, ps["ln2"], cfg.norm_eps), cfg)
            x = x + h
            shared_new.append(kv_new)
        if tail:
            x, _, c = scan_layers(
                (jax.tree.map(lambda a: a[n_apps * gsz:], lay),
                 cache["ssm"]["conv"][n_apps * gsz:],
                 cache["ssm"]["state"][n_apps * gsz:]),
                x, lambda inp, x: body(inp, x), cfg)
            ssm_new.append(c)
        new_cache["ssm"] = _concat_ssm(ssm_new, n_apps, gsz, tail)
        new_cache["shared"] = jax.tree.map(lambda *xs: jnp.stack(xs, 0),
                                           *shared_new)

    elif cfg.family == "encdec":
        def body(inp, x):
            pl_, kv_l, xk, xv = inp
            h, kv_new = self_attn(
                pl_["attn"], rmsnorm(x, pl_["ln1"], cfg.norm_eps), "self",
                kv_l)
            x = x + h
            h, _ = attn.apply_attention(
                pl_["cross"], rmsnorm(x, pl_["ln2"], cfg.norm_eps), cfg,
                kv_x=x,  # marker: non-self; K/V come from the static cache
                cache={"k": xk, "v": xv}, pos=pos)
            x = x + h
            h = mlp_mod.apply_mlp(pl_["ffn"],
                                  rmsnorm(x, pl_["ln3"], cfg.norm_eps), cfg)
            return x + h, jnp.zeros((), jnp.float32), kv_new
        x, _, kv_out = scan_layers(
            (params["layers"], cache["self"], cache["cross"]["k"],
             cache["cross"]["v"]),
            x, lambda inp, x: body(inp, x), cfg)
        new_cache["self"] = kv_out
        new_cache["cross"] = cache["cross"]

    elif cfg.family == "vlm":
        period = cfg.cross_attn_every
        n_groups = cfg.n_layers // period
        grouped = jax.tree.map(
            lambda a: a.reshape(n_groups, period, *a.shape[1:]),
            params["layers"])
        cgrouped = jax.tree.map(
            lambda a: a.reshape(n_groups, period, *a.shape[1:]),
            cache["self"])

        def body(inp, x):
            pl_, kv_l = inp
            h, kv_new = self_attn(
                pl_["attn"], rmsnorm(x, pl_["ln1"], cfg.norm_eps), "self",
                kv_l)
            x = x + h
            h = mlp_mod.apply_mlp(pl_["ffn"],
                                  rmsnorm(x, pl_["ln2"], cfg.norm_eps), cfg)
            return x + h, jnp.zeros((), jnp.float32), kv_new

        kv_groups = []
        for gi in range(n_groups):
            cl = jax.tree.map(lambda a: a[gi], params["cross_layers"])
            h, _ = attn.apply_attention(
                cl["attn"], rmsnorm(x, cl["ln"], cfg.norm_eps), cfg,
                kv_x=x,  # marker: K/V from static image cache
                cache=jax.tree.map(lambda a: a[gi], cache["cross"]), pos=pos)
            x = x + jnp.tanh(cl["gate"]) * h
            x, _, kv_out = scan_layers(
                (jax.tree.map(lambda a: a[gi], grouped),
                 jax.tree.map(lambda a: a[gi], cgrouped)),
                x, lambda inp, x: body(inp, x), cfg)
            kv_groups.append(kv_out)
        new_cache["self"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, 0), *kv_groups)
        new_cache["cross"] = cache["cross"]
    else:
        raise ValueError(cfg.family)

    with jax.named_scope("head"):
        x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
        w_out = (params["embed"].T if cfg.tie_embeddings
                 else params["unembed"])
        logits = jnp.dot(x.astype(compute), w_out.astype(compute),
                         preferred_element_type=jnp.float32)
        logits = shard(logits, ("pod", "data"), None, "model")
    return logits[:, 0], new_cache


def _concat_ssm(ssm_new, n_apps, gsz, tail):
    """Stitch per-group (gsz, B, ...) ssm caches back to (L, B, ...)."""
    parts = ssm_new[:n_apps]
    out = (jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *parts)
        if len(parts) > 1 else parts[0])
    if tail:
        out = jax.tree.map(lambda a, t: jnp.concatenate([a, t], axis=0),
                           out, ssm_new[-1])
    return out
