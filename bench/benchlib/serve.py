"""Serving cells: the program's ``SlotEngine`` behind its
``ContinuousServer``, fed a mix from ``traffic.py``.

Set-up makes the weights on the device from the seed, builds the engine
and warms every shape the mix will use: each prompt bucket's prefill,
the decode step, and the cache insert for each page count.  The window
then sends the requests at their due times (open loop) or all at once
(backlog), from this thread, while the server's own thread serves them.
After the window closes, every request that is due waits up to a minute
to finish; the server is shut down, its pools freed, and the check runs
the plain reference over a sample of the finished requests.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from . import traffic

#: how long a request due in the window may take to finish after the
#: window closes before it counts as never served
DRAIN_S = 60.0


@dataclasses.dataclass
class Record:
    due: float                      # perf_counter time it was due
    request: traffic.Request
    submitted: float = 0.0
    future: Any = None
    first: Optional[float] = None
    finished: Optional[float] = None
    tokens: Optional[np.ndarray] = None
    error: Optional[str] = None

    @property
    def served(self) -> bool:
        return (self.tokens is not None
                and len(self.tokens) == self.request.output_len)


def jax_seed(seed: int, salt: int = 0) -> int:
    """A 31-bit seed for JAX from any whole number (JAX keeps only the
    low 32 bits of a larger one)."""
    return int(np.random.default_rng([seed, salt]).integers(0, 2 ** 31 - 1))


#: a leaf's rule in ``make_weights``: ones (a norm's gain), else the
#: standard deviation of a normal around zero
ONES = "ones"


def make_weights(mcfg, seed: int, architecture):
    """Random weights in the served dtype, made on the device by one
    jitted call.  Each leaf's rule is the architecture's
    ``init_scale(path, leaf)`` where it defines one and returns a rule
    (``ONES`` or a standard deviation), else the default: norms 1, the
    embedding N(0, 0.02^2), every other matrix N(0, 1/fan_in).  The
    tree's layout comes from the program's own initializer, evaluated for
    shapes only."""
    import jax
    import jax.numpy as jnp

    from repro.models import init_params, split

    shapes = jax.eval_shape(lambda k: split(init_params(k, mcfg))[0],
                            jax.random.PRNGKey(0))
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    dtype = jnp.dtype(mcfg.dtype)

    own = getattr(architecture, "init_scale", None)

    def scale(path, leaf):
        rule = own(path, leaf) if own is not None else None
        if rule is not None:
            return rule
        name = getattr(path[-1], "key", "")
        if name in ("ln1", "ln2", "final_norm"):
            return ONES
        if name == "embed":
            return 0.02
        return float(leaf.shape[-2]) ** -0.5

    scales = [scale(p, leaf) for p, leaf in flat]

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(flat))
        out = []
        for k, (_, leaf), s in zip(keys, flat, scales):
            if s == ONES:
                out.append(jnp.ones(leaf.shape, dtype))
            else:
                out.append(jax.random.normal(k, leaf.shape, dtype)
                           * jnp.asarray(s, dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return make(jax.random.PRNGKey(jax_seed(seed, 1)))


def warm_inserts(engine, mix, page_counts) -> None:
    """Compile or load the cache insert for each page count (it is
    compiled per count), through the cache's own alloc, insert and free
    with a zero prefill cache.  Run while no slot is live."""
    import jax
    import jax.numpy as jnp

    from repro.models import decode as dec

    page = int(mix["page_size"])
    ctx = int(mix["max_context"])
    template = jax.eval_shape(
        functools.partial(dec.prefill, cfg=engine.cfg, max_len=ctx),
        engine.params, jax.ShapeDtypeStruct((1, ctx), jnp.int32))[1]
    zeros = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), template)
    for n in page_counts:
        if not engine.cache.alloc(0, n * page):
            raise RuntimeError(f"cannot reserve {n} pages to warm insert")
        engine.cache.insert(0, zeros)
        engine.cache.free(0)
    jax.block_until_ready(engine.cache.pools)


def _warm(engine, server, mix, requests, vocab: int) -> None:
    """Compile or load every program the window will run, and start the
    server: the inserts, then each prompt bucket's prefill and the decode
    step, through the server."""
    warm_inserts(engine, mix, traffic.page_counts(mix, requests))
    rng = np.random.default_rng(0)
    lens = sorted({r.prompt_len for r in requests})
    server.start()
    futs = [server.submit(rng.integers(0, vocab, p, dtype=np.int32),
                          max_new_tokens=2) for p in lens]
    for f in futs:
        f.result(timeout=600)


def setup(cell, seed: int, seconds: float):
    """(engine, server, params, requests): everything before the window."""
    from repro.serve import ContinuousServer, ServeConfig, SlotEngine

    import jax

    mix, arch = cell.traffic, cell.architecture()
    mcfg = arch.program_config(cell.config)
    t = [time.perf_counter()]
    params = jax.block_until_ready(make_weights(mcfg, seed, arch))
    t.append(time.perf_counter())
    requests = traffic.serve_requests(mix, seconds, seed, mcfg.vocab)
    engine = SlotEngine(
        params, mcfg, capacity=int(mix["capacity"]),
        max_context=int(mix["max_context"]),
        page_size=int(mix["page_size"]),
        serve_cfg=ServeConfig(seed=jax_seed(seed, 2)))
    server = ContinuousServer(engine,
                              prefill_per_step=int(mix["prefill_per_step"]))
    t.append(time.perf_counter())
    _warm(engine, server, mix, requests, mcfg.vocab)
    t.append(time.perf_counter())
    print(f"set-up: weights {t[1] - t[0]:.2f} s, engine {t[2] - t[1]:.2f} "
          f"s, warm-up {t[3] - t[2]:.2f} s (inserts for "
          f"{len(traffic.page_counts(mix, requests))} page counts, "
          f"{len({r.prompt_len for r in requests})} prompt lengths)",
          file=sys.stderr)
    return engine, server, params, requests


def window(server, requests, seconds: float, tracer=None,
           trace_at: float = 0.0, trace_s: float = 0.0):
    """Send the requests on schedule for ``seconds``; returns (records,
    t_open, t_close, stats at open, stats at close)."""
    events = [(r.due_s, 0, r) for r in requests if r.due_s < seconds]
    if tracer is not None:
        events.append((trace_at, -1, "trace"))
    events.sort(key=lambda e: (e[0], e[1]))
    records: List[Record] = []
    tracing = None
    t0 = time.perf_counter()
    s0 = dict(server.stats)
    for due, _, what in events:
        delay = t0 + due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if what == "trace":
            # starting the profiler takes seconds: not on this thread,
            # which must send requests on time
            tracing = threading.Thread(target=tracer.run, args=(trace_s,))
            tracing.start()
        else:
            rec = Record(due=t0 + due, request=what)
            rec.submitted = time.perf_counter()
            rec.future = server.submit(what.prompt,
                                       max_new_tokens=what.output_len)
            records.append(rec)
    delay = t0 + seconds - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    t1 = time.perf_counter()
    s1 = dict(server.stats)
    if tracing is not None:
        tracing.join()
    return records, t0, t1, s0, s1


def collect(records: List[Record], t_close: float, backlog: bool
            ) -> List[Record]:
    """Wait for the requests the window is answerable for and read them.
    In a backlog these are the ones admitted (first token) before the
    close; otherwise every one sent."""
    deadline = t_close + DRAIN_S
    if backlog:
        records = [r for r in records
                   if r.future.first_token_at is not None
                   and r.future.first_token_at <= t_close]
    for r in records:
        try:
            r.tokens = r.future.result(
                timeout=max(0.0, deadline - time.perf_counter()))
        except TimeoutError:
            r.error = "not finished a minute after the window closed"
        except Exception as err:            # the server failed it
            r.error = f"{type(err).__name__}: {err}"
        r.first = r.future.first_token_at
        r.finished = r.future.finished_at if r.tokens is not None else None
    return records


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------

def sample(records: List[Record], k: int, seed: int) -> List[Record]:
    """``k`` served requests drawn from the seed, the longest among them."""
    served = [r for r in records if r.served]
    if not served:
        return []
    longest = max(served, key=lambda r: (r.request.prompt_len
                                         + r.request.output_len))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng([seed, 3])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gap_fn(reference, conf, control: bool):
    """jit: (params, tokens) -> per position the gap by which
    the next token's reference logit lies below the reference maximum,
    over the row's max |logit|; with ``control`` also the same gap of the
    token the lower-precision control puts first."""
    import jax
    import jax.numpy as jnp

    def gaps(logits, picked):
        top = logits.max(-1)
        scale = jnp.abs(logits).max(-1)
        got = jnp.take_along_axis(logits, picked[:, None], 1)[:, 0]
        return (top - got) / scale

    @jax.jit
    def run(params, tokens):
        ref = reference.forward(params, tokens, conf)
        nxt = jnp.concatenate([tokens[1:], tokens[:1]])
        served = gaps(ref, nxt)
        if not control:
            return served, served
        ctl = reference.forward(params, tokens, conf, control=True)
        return served, gaps(ref, ctl.argmax(-1).astype(jnp.int32))

    return run


def logit_gaps(cell, params, checked: List[Record], control: bool = False):
    """Widest gap over every served token of ``checked`` (and of the
    control's first choices at the same positions).  Each sequence is
    padded to the context length, so one program serves every request:
    the padding comes after every position read, and attention is
    causal."""
    ctx = int(cell.traffic["max_context"])
    run = gap_fn(cell.reference(), cell.config, control)
    worst, worst_ctl, tokens = 0.0, 0.0, 0
    for r in checked:
        p, out = r.request.prompt, np.asarray(r.tokens, np.int32)
        seq = np.zeros(ctx, np.int32)
        seq[:len(p)] = p
        seq[len(p):len(p) + len(out)] = out
        served, ctl = run(params, seq)
        lo, hi = len(p) - 1, len(p) - 1 + len(out)
        worst = max(worst, float(np.asarray(served)[lo:hi].max()))
        worst_ctl = max(worst_ctl, float(np.asarray(ctl)[lo:hi].max()))
        tokens += len(out)
    return worst, worst_ctl, tokens


def free(engine, server) -> None:
    """Drop the program's state (page pools, compiled steps)."""
    server.shutdown(drain=False, timeout=60)
    engine.cache.pools.clear()
    engine.cache.lanes.clear()
    gc.collect()
