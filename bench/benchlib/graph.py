"""Generator cells: ``repro.generate`` on a layer graph, called in a
closed loop with one call in flight.

The mix (``"kind": "graph"``) names the sequence length ``l``, the
``dtype``, and how many distinct activations ``x`` the calls cycle
through (``inputs``); the configuration gives ``hidden_size`` and
``intermediate_size``.  Set-up generates the accelerator (with the
tuning cache pointed at an empty directory, so the analytic default
blocks are used) and makes one call.  The window calls it until the time
is up, each call awaited; a sample of the calls, drawn from the seed,
keeps its output for the check.
"""
from __future__ import annotations

import os
import tempfile
import time
from typing import Dict, List, Tuple

import numpy as np

from . import counts
from .serve import jax_seed

LAYER_INPUTS = ("x", "wq", "wk", "wv_t", "wo", "w1", "b1", "w2")


def shapes(l: int, d: int, f: int) -> Dict[str, Tuple[int, ...]]:
    """The layer graph's inputs, weights stored (out, in)."""
    return {"x": (l, d), "wq": (d, d), "wk": (d, d), "wv_t": (d, d),
            "wo": (d, d), "w1": (f, d), "b1": (f,), "w2": (d, f)}


def dims(cell) -> Tuple[int, int, int]:
    return (int(cell.traffic["l"]), int(cell.config["hidden_size"]),
            int(cell.config["intermediate_size"]))


def flops(cell) -> int:
    return counts.layer_graph_flops(*dims(cell))


def make_operands(cell, seed: int):
    """(weights, [x_0, ...]) in the cell's dtype, made by one jitted call:
    x ~ N(0, 1), weights ~ N(0, 1/fan_in), b1 ~ N(0, 0.01)."""
    import jax
    import jax.numpy as jnp

    l, d, f = dims(cell)
    shp = shapes(l, d, f)
    dtype = jnp.dtype(cell.traffic["dtype"])
    n_x = int(cell.traffic["inputs"])

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(LAYER_INPUTS) + n_x)
        w = {}
        for k, name in zip(keys, LAYER_INPUTS[1:]):
            v = jax.random.normal(k, shp[name], jnp.float32)
            v = v * (0.1 if name == "b1" else shp[name][-1] ** -0.5)
            w[name] = v.astype(dtype)
        xs = [jax.random.normal(k, shp["x"], jnp.float32).astype(dtype)
              for k in keys[len(LAYER_INPUTS):]]
        return w, xs

    return make(jax.random.PRNGKey(jax_seed(seed, 1)))


def setup(cell, seed: int):
    """(accelerator, weights, xs, tune directory)."""
    import repro
    from repro.configs.base import ModelConfig
    from repro.graph import from_model

    l, d, f = dims(cell)
    conf = cell.config
    mcfg = ModelConfig(name=conf["name"], family="dense", n_layers=1,
                       d_model=d, n_heads=conf["num_attention_heads"],
                       n_kv_heads=conf["num_key_value_heads"], d_ff=f,
                       vocab=conf["vocab_size"])
    graph = from_model.layer_graph_from_config(mcfg, l=l)
    tune_dir = tempfile.TemporaryDirectory(prefix="bench-tune-")
    os.environ["REPRO_TUNE_CACHE"] = tune_dir.name
    acc = repro.generate(graph, dtype=cell.traffic["dtype"], validate=False)
    weights, xs = make_operands(cell, seed)
    acc({**weights, "x": xs[0]}).block_until_ready()
    return acc, weights, xs, tune_dir


def keep_set(seed: int, n: int = 4096, share: float = 1 / 16):
    rng = np.random.default_rng([seed, 4])
    return set(np.flatnonzero(rng.random(n) < share).tolist())


def window(acc, weights, xs, seconds: float, seed: int, tracer=None,
           trace_at: float = 0.0, trace_s: float = 0.0):
    """Call until ``seconds`` have passed.  Returns (calls, t_open,
    t_close, kept [(call index, x index, output)], traced calls)."""
    keep = keep_set(seed)
    kept: List = []
    calls, traced = 0, 0
    tracing = False
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if tracer is not None and not tracing and now >= trace_at \
                and traced == 0:
            tracer.start()
            tracing = True
        i = calls % len(xs)
        out = acc({**weights, "x": xs[i]})
        out.block_until_ready()
        if calls in keep:
            kept.append((calls, i, out))
        calls += 1
        if tracing:
            traced += 1
            if time.perf_counter() - t0 >= trace_at + trace_s:
                tracer.stop()
                tracing = False
    if tracing:
        tracer.stop()
    t1 = time.perf_counter()
    if not kept:                          # too short a window: check the last
        kept.append((calls - 1, (calls - 1) % len(xs), out))
    return calls, t0, t1, kept, traced


def rel_errors(cell, weights, xs, kept, control: bool = False):
    """Worst max|out - ref| / max|ref| over the kept outputs (and the
    control's, against the same reference)."""
    import jax
    import jax.numpy as jnp

    ref_mod = cell.reference()
    fwd = jax.jit(ref_mod.forward, static_argnames=("control",))
    refs, ctl = {}, {}
    worst = worst_ctl = 0.0
    for _, i, out in kept:
        if i not in refs:
            ops = {**weights, "x": xs[i]}
            refs[i] = fwd(ops)
            if control:
                ctl[i] = fwd(ops, control=True)
        scale = float(jnp.abs(refs[i]).max())
        err = float(jnp.abs(out.astype(jnp.float32) - refs[i]).max())
        worst = max(worst, err / scale)
        if control:
            e = float(jnp.abs(ctl[i] - refs[i]).max())
            worst_ctl = max(worst_ctl, e / scale)
    return worst, worst_ctl


def least_time_s(cell, peaks) -> float:
    """The least time one call can take on the chip: its FLOPs at the
    peak rate, or its least bytes at the HBM rate, whichever is longer."""
    import jax.numpy as jnp

    l, d, f = dims(cell)
    itemsize = jnp.dtype(cell.traffic["dtype"]).itemsize
    return max(flops(cell) / peaks.flops_bf16,
               counts.layer_graph_bytes(l, d, f, itemsize=itemsize)
               / peaks.hbm_bytes_per_s)
