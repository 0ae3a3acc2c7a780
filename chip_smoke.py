#!/usr/bin/env python3
"""Run the system's two main paths once on a TPU and check what they return.

    python chip_smoke.py              # one chip: generator, graph, serve
    python chip_smoke.py --chips 4    # the 2x2-mesh path only

One process; the first failure ends the run with a traceback and a
non-zero exit.  Phases, in order:

  device     exit 1 unless JAX's first device is a TPU.
  generator  ``repro.generate`` for the six registry algebras x three
             named STTs at model-derived bounds, in bf16, compiled by
             Mosaic (``interpret=False``), each against an fp32
             ``jnp.einsum`` / ``lax.conv`` reference on the same
             bf16-rounded inputs.
  graph      a transformer-layer graph that merges into one megakernel,
             then one h2o-danube-1.8b layer at its published widths (the
             merged group declines and dispatches node by node), both
             against the fp32 layer oracle.
  serve      h2o-danube-1.8b at its published widths with random bf16
             weights behind ``SlotEngine`` + ``ContinuousServer``.

``--chips 4`` runs only what exists across chips, on a 2x2 mesh: the six
algebras through ``Accelerator.sharded`` against the same accelerator on
one chip, and the serve phase with its page pools placed over the mesh
against the same requests served on one chip.

Compile and run times are printed per phase.  The compile cache is
JAX's persistent one (``repro.launch.cache``); the run reports its hits,
so a second run in the same checkout shows them.  The last line of
standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

#: generator cells: registry algebra -> loop bounds taken from the
#: models and networks the algebras stand for
GEN_CELLS = {
    # h2o-danube-1.8b MLP up-projection over a 512-token prompt
    "gemm": dict(m=512, n=6912, k=2560),
    # its GQA decode attention: 256 (slot x kv-head) rows, head_dim 80,
    # a 4096-token window
    "batched_gemv": dict(m=256, k=80, n=4096),
    # a ResNet-50 stage-2 3x3 convolution
    "conv2d": dict(k=128, c=128, y=28, x=28),
    # a MobileNet 3x3 depthwise convolution
    "depthwise_conv": dict(k=256, y=28, x=28),
    "mttkrp": dict(i=256, j=256, k=64, l=64),
    "ttmc": dict(i=64, j=64, k=64, l=64, m=64),
}
DATAFLOWS = ("output_stationary", "weight_stationary", "input_stationary")

#: max |out - ref| / max |ref| for a bf16 kernel.  bf16 keeps 8
#: significant bits, so rounding the fp32 accumulator to the bf16 output
#: moves an element by up to 2^-8 of its magnitude; 2^-7 leaves as much
#: again for the summation order and for the bf16-rounded factor
#: products mttkrp and ttmc contract against.  A wrong block or a lost
#: tile shows up as an error of order 1.
GEN_RTOL = 2.0 ** -7
#: max |out - ref| / max |ref| between two fp32 executions of a layer
#: graph (Mosaic and XLA both at fp32 matmul precision).  They sum the
#: same products in different orders and approximate exp/tanh
#: differently: about sqrt(k) * 2^-24 < 1e-5 of the output scale for the
#: widest contraction here (k = 6912).  One bf16-rounded stage would put
#: the error near 2^-9 = 2e-3.
GRAPH_RTOL = 1e-4
#: max |logits - ref| / max |ref| between the served bf16 prefill and an
#: fp32 forward on the same bf16 weights (bf16 activations between the
#: matmuls, fp32 attention and accumulation).  Measured at these widths
#: and prompt length on the CPU: 8.5e-3, 1.07e-2, 1.03e-2, 1.08e-2 and
#: 1.36e-2 at 1, 2, 4, 8 and 12 layers.  It grows slowly with depth;
#: 5e-2 leaves room for the 24 layers served here.  Wrong weights, a
#: wrong cache or a wrong position give errors of order 1.
SERVE_LOGIT_RTOL = 5e-2

SERVE_ARCH = "h2o-danube-1.8b"
SERVE_CAPACITY = 4
SERVE_MAX_CONTEXT = 1024
SERVE_PAGE_SIZE = 16
SERVE_PROMPT_LENS = (32, 100, 300, 600, 32, 100, 300, 600)
SERVE_NEW_TOKENS = 32
#: requests checked against the fp32 forward and compared with
#: sequential decode (same prompt length, so one fp32 compile serves
#: both)
SERVE_CHECKED = (1, 5)

#: XLA/Mosaic compile time, persistent-cache reads included (tracing
#: is counted as run time: nested jits would count it twice)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_s = [0.0]


def _on_duration(event: str, secs: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _compile_s[0] += secs


@contextlib.contextmanager
def phase(name: str):
    """Print a phase's wall time split into backend compile and the
    rest."""
    print(f"== {name}", flush=True)
    c0, t0 = _compile_s[0], time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    comp = _compile_s[0] - c0
    print(f"== {name}: {wall:.2f} s = compile {comp:.2f} s "
          f"+ run {wall - comp:.2f} s", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def rel_err(got, want) -> float:
    """max |got - want| / max |want|, in fp32 on the device."""
    import jax.numpy as jnp

    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


# ---------------------------------------------------------------------------
# generator
# ---------------------------------------------------------------------------

def reference(name: str, ops):
    """The algebra in fp32 at full matmul precision, written from its
    definition in ``core.algebra`` and independent of the lowering."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    f = {k: jnp.asarray(v, jnp.float32) for k, v in ops.items()}
    if name == "gemm":                       # C[m,n] = A[m,k] B[n,k]
        return jnp.einsum("mk,nk->mn", f["A"], f["B"], precision=hi)
    if name == "batched_gemv":               # C[m,n] = A[m,k,n] B[m,k]
        return jnp.einsum("mkn,mk->mn", f["A"], f["B"], precision=hi)
    if name in ("conv2d", "depthwise_conv"):
        # C[k,y,x] = A[c,y+p,x+q] B[k,c,p,q] (depthwise: c = k, B[k,p,q])
        depthwise = name == "depthwise_conv"
        rhs = f["B"][:, None] if depthwise else f["B"]
        return jax.lax.conv_general_dilated(
            f["A"][None], rhs, (1, 1), "VALID",
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=f["A"].shape[0] if depthwise else 1,
            precision=hi)[0]
    if name == "mttkrp":                     # D[i,j] = A[i,k,l] B[k,j] C[l,j]
        return jnp.einsum("ikl,kj,lj->ij", f["A"], f["B"], f["C"],
                          precision=hi)
    if name == "ttmc":                       # D[i,j,k] = A[i,l,m] B[l,j] C[m,k]
        return jnp.einsum("ilm,lj,mk->ijk", f["A"], f["B"], f["C"],
                          precision=hi)
    raise ValueError(name)


def _operands(alg, key, dtype):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(key, len(alg.inputs))
    return {t.name: jax.random.normal(k, alg.tensor_shape(t), jnp.float32
                                      ).astype(dtype)
            for t, k in zip(alg.inputs, keys)}


def generator_phase(cells, *, interpret: bool, seed: int,
                    dtype="bfloat16") -> None:
    """Every cell x named STT through ``repro.generate``."""
    import jax

    import repro
    from repro.core.algebra import get_algebra

    key = jax.random.PRNGKey(seed)
    for i, (name, bounds) in enumerate(cells.items()):
        alg = get_algebra(name, **bounds)
        ops = _operands(alg, jax.random.fold_in(key, i), dtype)
        want = reference(name, ops)
        for df in DATAFLOWS:
            c0, t0 = _compile_s[0], time.perf_counter()
            acc = repro.generate(alg, df, dtype=dtype,
                                 interpret=interpret, validate=False)
            got = acc(ops).block_until_ready()
            wall = time.perf_counter() - t0
            comp = _compile_s[0] - c0
            err = rel_err(got, want)
            k = acc.kernel
            print(f"  {name:15s} {df:18s} {k.template:18s} "
                  f"blocks={k.blocks} rel_err={err:.3e} "
                  f"compile {comp:.2f} s run {wall - comp:.3f} s",
                  flush=True)
            check(got.shape == want.shape and got.dtype == dtype,
                  f"{name} {df}: {got.shape} {got.dtype}, want "
                  f"{want.shape} {dtype}")
            check(err <= GEN_RTOL,
                  f"{name} {df}: rel_err {err:.3e} > {GEN_RTOL:.3e}")


def sharded_generator_phase(cells, mesh, *, interpret: bool, seed: int,
                            dtype="bfloat16") -> None:
    """Each algebra through ``Accelerator.sharded(mesh)`` against the
    same accelerator on one chip."""
    import jax

    import repro
    from repro.core.algebra import get_algebra

    n_dev = mesh.devices.size
    key = jax.random.PRNGKey(seed)
    for i, (name, bounds) in enumerate(cells.items()):
        alg = get_algebra(name, **bounds)
        ops = _operands(alg, jax.random.fold_in(key, i), dtype)
        acc = repro.generate(alg, "output_stationary", dtype=dtype,
                             interpret=interpret, validate=False)
        one = acc(ops).block_until_ready()
        sharded = acc.sharded(mesh)
        got = sharded(ops).block_until_ready()
        devices = got.sharding.device_set
        err_one = rel_err(got, one)
        err_ref = rel_err(got, reference(name, ops))
        print(f"  {name:15s} strategy={sharded.partition.strategy:18s} "
              f"devices={len(devices)} vs one chip {err_one:.3e} "
              f"vs reference {err_ref:.3e}", flush=True)
        check(len(devices) == n_dev,
              f"{name}: sharded output spans {len(devices)} devices, "
              f"not {n_dev}")
        check(got.shape == one.shape,
              f"{name}: sharded {got.shape} vs one chip {one.shape}")
        # both round an fp32 sum of the same products to bf16
        check(err_one <= GEN_RTOL and err_ref <= GEN_RTOL,
              f"{name}: sharded vs one chip {err_one:.3e}, vs reference "
              f"{err_ref:.3e} (bound {GEN_RTOL:.3e})")


# ---------------------------------------------------------------------------
# graph
# ---------------------------------------------------------------------------

def _layer_operands(graph, key):
    """fp32 layer inputs at unit activation scale: x ~ N(0, 1), every
    weight ~ N(0, 1 / fan_in) (fan-in is the last storage dim), so the
    softmax sees moderate scores at any width."""
    import jax
    import jax.numpy as jnp

    ops = {}
    for i, e in enumerate(graph.inputs):
        shape = graph.edge_shape(e)
        v = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if e == "b1":
            v = 0.1 * v
        elif e != "x":
            v = v / np.sqrt(shape[-1])
        ops[e] = v
    return ops


def graph_phase(cases, *, interpret: bool, seed: int) -> None:
    """``cases``: (label, graph, merges) — whether the layer must run as
    one megakernel, or decline and dispatch node by node."""
    import jax

    import repro
    from repro.graph import from_model

    key = jax.random.PRNGKey(seed)
    for label, graph, merges in cases:
        c0, t0 = _compile_s[0], time.perf_counter()
        acc = repro.generate(graph, interpret=interpret, validate=False)
        ops = _layer_operands(graph, key)
        got = acc(ops).block_until_ready()
        wall = time.perf_counter() - t0
        comp = _compile_s[0] - c0
        with jax.default_matmul_precision("highest"):
            want = from_model.layer_oracle(ops)
        err = rel_err(got, want)
        how = [ln.strip() for ln in acc.describe().splitlines()
               if ln.strip().startswith(("merged ", "sequential "))]
        print(f"  {label}: rel_err={err:.3e} compile {comp:.2f} s "
              f"run {wall - comp:.3f} s", flush=True)
        for ln in how:
            print(f"    {ln}", flush=True)
        if merges:
            check(len(acc.group_kernels) == 1,
                  f"{label}: expected one merged megakernel, got "
                  f"{list(acc.group_kernels)}")
        else:
            check(not acc.group_kernels and any(
                ln.startswith("sequential ") for ln in how),
                f"{label}: expected the group to decline, got {how}")
        check(got.shape == want.shape,
              f"{label}: {got.shape} vs oracle {want.shape}")
        check(err <= GRAPH_RTOL,
              f"{label}: rel_err {err:.3e} > {GRAPH_RTOL:.0e}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def random_params(cfg, seed: int):
    """Random weights built inside one jit and returned in ``cfg.dtype``:
    the fp32 values live only as that program's temporaries."""
    import jax

    from repro.models import init_params, split

    @jax.jit
    def init(key):
        params, _ = split(init_params(key, cfg))
        return jax.tree.map(lambda a: a.astype(cfg.dtype), params)

    return init(jax.random.PRNGKey(seed))


def serve(cfg, params, prompts, *, capacity: int, max_context: int,
          page_size: int, new_tokens: int, seed: int, mesh=None,
          timeout: float = 900.0):
    """Serve ``prompts`` through ``ContinuousServer``; with ``mesh``, the
    weights are replicated over it and the page pools placed by the
    partition solver.  Checks every request's token count and that the
    decode step compiled once.  Returns (engine, generated tokens per
    request)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.serve import (ContinuousServer, ServeConfig, SlotEngine,
                             place_pools, solve_page_placement)

    if mesh is not None:
        params = jax.device_put(params, NamedSharding(mesh, P()))
    engine = SlotEngine(params, cfg, capacity=capacity,
                        max_context=max_context, page_size=page_size,
                        serve_cfg=ServeConfig(max_new_tokens=new_tokens,
                                              seed=seed))
    if mesh is not None:
        _, spec = solve_page_placement(cfg, engine.cache.layout,
                                       axes=mesh.axis_names,
                                       shape=mesh.devices.shape)
        place_pools(engine.cache, mesh, spec)
        print(f"  page pools placed with {spec}", flush=True)
    with ContinuousServer(engine) as server:
        futures = [server.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        outs = [f.result(timeout=timeout) for f in futures]
        stats = dict(server.stats)
        occupancy = server.mean_occupancy()
    print(f"  served {len(outs)} requests: {stats['steps']} decode steps, "
          f"{stats['tokens']} tokens, mean occupancy {occupancy:.2f}, "
          f"decode compiles {engine.decode_compiles}, prefill compiles "
          f"{engine.prefill_compiles}", flush=True)
    for i, out in enumerate(outs):
        check(out.shape == (new_tokens,),
              f"request {i}: {out.shape[0]} tokens, want {new_tokens}")
    check(engine.decode_compiles == 1,
          f"decode compiled {engine.decode_compiles} times, want 1")
    return engine, outs


def agreement(a, b) -> float:
    return float(np.mean(np.asarray(a) == np.asarray(b)))


def check_against_fp32(cfg, params, prompts, outs, checked, *,
                       max_context: int, label: str) -> None:
    """For each checked request, one fp32 forward over the prompt and the
    served tokens (teacher forcing) is the reference for both the served
    prefill logits and every served decode token.

    A greedy bf16 decoder picks the argmax of logits within e of the fp32
    ones, so the fp32 logit of the token it picks is within 2e of the
    fp32 maximum.  Near-ties among 32000 random-weight logits make the
    tokens themselves differ from an fp32 or a sequential decode after a
    few steps; a wrong cache page or position puts the served token far
    from the maximum at once."""
    import jax
    import jax.numpy as jnp

    from repro.models import decode as dec
    from repro.models.transformer import forward

    prefill = jax.jit(functools.partial(dec.prefill, cfg=cfg),
                      static_argnames=("max_len",))
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    fwd32 = jax.jit(lambda p, t: forward(p, t, cfg32)[0][0])
    for i in checked:
        prompt, out = prompts[i], np.asarray(outs[i])
        logits, _ = prefill(params, jnp.asarray(prompt)[None],
                            max_len=max_context)
        seq = jnp.asarray(np.concatenate([prompt, out[:-1]]))[None]
        with jax.default_matmul_precision("highest"):
            ref = fwd32(params, seq)[len(prompt) - 1:]      # (tokens, V)
        err = rel_err(logits[0], ref[0])
        picked = jnp.take_along_axis(ref, jnp.asarray(out)[:, None], 1)[:, 0]
        gap = float(((ref.max(-1) - picked) / jnp.abs(ref).max()).max())
        top1 = agreement(ref.argmax(-1), out)
        print(f"  request {i} (prompt {len(prompt)}), {label}: prefill "
              f"logits vs fp32 forward rel_err={err:.3e}; tokens' fp32 "
              f"logit gap to the maximum <= {gap:.3e} of max|logit| (fp32 "
              f"top-1 agreement {top1:.3f})", flush=True)
        check(bool(np.isfinite(np.asarray(logits)).all()),
              f"request {i}: non-finite prefill logits")
        check(err <= SERVE_LOGIT_RTOL,
              f"request {i}: prefill logits rel_err {err:.3e} > "
              f"{SERVE_LOGIT_RTOL:.0e}")
        check(gap <= 2 * SERVE_LOGIT_RTOL,
              f"request {i}, {label}: a token's fp32 logit is {gap:.3e} of "
              f"max|logit| below the maximum (> {2 * SERVE_LOGIT_RTOL:.0e})")


def serve_phase(cfg, *, capacity: int, max_context: int, page_size: int,
                prompt_lens, new_tokens: int, checked, seed: int) -> None:
    """Serve on one chip; check the checked requests against an fp32
    forward, and report the top-1 agreement with sequential decode."""
    import jax

    from repro.serve import DecodeEngine, ServeConfig

    params = random_params(cfg, seed)
    n_params = sum(int(a.size) for a in jax.tree.leaves(params))
    print(f"  {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads of {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab}; {n_params / 1e9:.3f} B params "
          f"in {cfg.dtype}", flush=True)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, (s,), dtype=np.int32)
               for s in prompt_lens]
    _, outs = serve(cfg, params, prompts, capacity=capacity,
                    max_context=max_context, page_size=page_size,
                    new_tokens=new_tokens, seed=seed)
    seq = DecodeEngine(params, cfg, ServeConfig(max_new_tokens=new_tokens,
                                                seed=seed))
    seq_outs = {}
    for i in checked:
        gen, _ = seq.generate(prompts[i][None], cache_len=max_context)
        seq_outs[i] = gen[0]
        print(f"  request {i}: top-1 agreement with sequential decode "
              f"{agreement(gen[0], outs[i]):.3f}", flush=True)
    check_against_fp32(cfg, params, prompts, outs, checked,
                       max_context=max_context, label="served")
    check_against_fp32(cfg, params, prompts, seq_outs, checked,
                       max_context=max_context, label="sequential")


def sharded_serve_phase(cfg, mesh, *, capacity: int, max_context: int,
                        page_size: int, prompt_lens, new_tokens: int,
                        checked, seed: int) -> None:
    """The same requests served with the pools over ``mesh`` and on one
    chip: the pools must span the mesh, the decode step must compile
    once, every request must finish, each
    first token (from the shared prefill) must match, and the checked
    requests' sharded decode must hold against the fp32 forward."""
    params = random_params(cfg, seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, (s,), dtype=np.int32)
               for s in prompt_lens]
    kw = dict(capacity=capacity, max_context=max_context,
              page_size=page_size, new_tokens=new_tokens, seed=seed)
    _, want = serve(cfg, params, prompts, **kw)
    engine, got = serve(cfg, params, prompts, mesh=mesh, **kw)
    n_dev = mesh.devices.size
    for path, pool in engine.cache.pools.items():
        check(len(pool.sharding.device_set) == n_dev,
              f"pool {path} spans {len(pool.sharding.device_set)} "
              f"devices, not {n_dev}")
    print(f"  {len(engine.cache.pools)} pools span {n_dev} devices; "
          f"top-1 agreement with one chip "
          f"{agreement(np.stack(got), np.stack(want)):.3f}", flush=True)
    for i, (g, w) in enumerate(zip(got, want)):
        check(g[0] == w[0], f"request {i}: first token {g[0]} vs {w[0]} "
                            f"on one chip")
    check_against_fp32(cfg, params, prompts, got, checked,
                       max_context=max_context, label="served on the mesh")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2x2-mesh path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    n_dev = len(jax.devices())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={n_dev}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 1
    if n_dev < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {n_dev}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.cache import compile_cache_stats, enable_compile_cache

    cache_dir = enable_compile_cache()
    print(f"compile cache: {cache_dir}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    # kernels come from committed code only, never from a tuning cache
    # left on the machine
    tune_dir = tempfile.TemporaryDirectory(prefix="repro-tune-")
    os.environ["REPRO_TUNE_CACHE"] = tune_dir.name

    from repro.configs.registry import get_config
    from repro.graph import from_model

    cfg = get_config(SERVE_ARCH)
    serve_kw = dict(capacity=SERVE_CAPACITY, max_context=SERVE_MAX_CONTEXT,
                    page_size=SERVE_PAGE_SIZE, prompt_lens=SERVE_PROMPT_LENS,
                    new_tokens=SERVE_NEW_TOKENS, checked=SERVE_CHECKED,
                    seed=args.seed)
    t0 = time.perf_counter()
    with tune_dir:
        if args.chips == 4:
            from repro.dist.engine import square_submesh

            mesh = square_submesh(2)
            with phase("sharded generator (2x2 mesh)"):
                sharded_generator_phase(GEN_CELLS, mesh, interpret=False,
                                        seed=args.seed)
            with phase("sharded serve (2x2 mesh)"):
                sharded_serve_phase(cfg, mesh, **serve_kw)
        else:
            with phase("generator"):
                generator_phase(GEN_CELLS, interpret=False, seed=args.seed)
            with phase("graph"):
                graph_phase(
                    [("layer l=256 d=256 f=512",
                      from_model.transformer_layer_graph(l=256, d=256, f=512),
                      True),
                     (f"{SERVE_ARCH} layer l=128",
                      from_model.layer_graph_from_config(cfg, l=128), False)],
                    interpret=False, seed=args.seed)
            with phase("serve"):
                serve_phase(cfg, **serve_kw)
    stats = compile_cache_stats()
    print(f"compile cache: {stats['hits']} hits, {stats['misses']} misses "
          f"({'hit' if stats['hits'] else 'no hits'}); total "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
