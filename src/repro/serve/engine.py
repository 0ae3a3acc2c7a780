"""Serving engines: batched LM decode + tensor-algebra accelerators.

``DecodeEngine`` is the serving counterpart to the train driver:
jit-compiled prefill and decode_step, a batch of independent sequences,
and per-sequence EOS tracking.

``AcceleratorEngine`` serves the STT side of the repo through the front
door: requests name a registry algebra (plus optional bounds / dataflow)
and the engine answers with the generated accelerator's output.  Repeat
shapes are free — ``repro.generate`` rides the bounded, thread-safe
compile cache — and a mesh-bound engine executes every request through
the CommPlan interpreter.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ModelConfig
from ..models import decode as dec


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0       # 0 = greedy
    eos_id: Optional[int] = None
    seed: int = 0


class DecodeEngine:
    def __init__(self, params, cfg: ModelConfig,
                 serve_cfg: Optional[ServeConfig] = None):
        # NOTE: the default must be None + construct-per-instance.  A
        # ``serve_cfg: ServeConfig = ServeConfig()`` default evaluates ONE
        # shared instance at import time — mutating one engine's config
        # would silently reconfigure every other engine (regression-tested
        # in tests/test_serve_engine.py).
        self.params = params
        self.cfg = cfg
        self.serve_cfg = serve_cfg if serve_cfg is not None else ServeConfig()
        self._prefill = jax.jit(functools.partial(dec.prefill, cfg=cfg),
                                static_argnames=("max_len",))
        self._step = jax.jit(functools.partial(dec.decode_step, cfg=cfg))

    def generate(self, prompts: np.ndarray, *,
                 frontend: Optional[np.ndarray] = None,
                 max_new_tokens: Optional[int] = None,
                 cache_len: Optional[int] = None,
                 ) -> Tuple[np.ndarray, Dict]:
        """prompts: (B, S0) int32.  Returns (generated (B, T), stats).

        ``cache_len`` overrides the decode cache's context budget (default
        ``S0 + max_new_tokens``).  The continuous-batching slot engine
        reads fixed-length page views, so its sequential parity oracle
        is this method with ``cache_len`` pinned to the engine's
        ``max_context`` — same cache shape, bit-identical math."""
        scfg = self.serve_cfg
        t_new = max_new_tokens or scfg.max_new_tokens
        b, s0 = prompts.shape
        max_len = cache_len or (s0 + t_new)
        if max_len < s0 + t_new:
            raise ValueError(f"cache_len {max_len} < prompt {s0} + "
                             f"new tokens {t_new}")
        logits, cache = self._prefill(
            self.params, jnp.asarray(prompts),
            frontend=None if frontend is None else jnp.asarray(frontend),
            max_len=max_len)
        key = jax.random.PRNGKey(scfg.seed)
        out = []
        done = np.zeros((b,), bool)
        tok = self._sample(logits, key)
        for t in range(t_new):
            out.append(np.asarray(tok))
            if scfg.eos_id is not None:
                done |= out[-1][:, 0] == scfg.eos_id
                if done.all():
                    break
            logits, cache = self._step(self.params, tok, cache)
            key, sub = jax.random.split(key)
            tok = self._sample(logits, sub)
        gen = np.concatenate(out, axis=1)
        return gen, {"prefill_len": s0, "generated": gen.shape[1]}

    def _sample(self, logits: jax.Array, key) -> jax.Array:
        if self.serve_cfg.temperature <= 0.0:
            return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        scaled = logits / self.serve_cfg.temperature
        return jax.random.categorical(key, scaled, axis=-1)[:, None].astype(
            jnp.int32)


class AcceleratorEngine:
    """Serve generated tensor-algebra accelerators (the front door, as a
    service).

    ``submit("gemm", {"A": a, "B": b})`` generates (or cache-hits) the
    accelerator for the request's algebra/bounds/dataflow and executes
    it; with ``mesh=`` every request runs multi-chip through the CommPlan
    interpreter.  Request threads are safe: generation goes through the
    locked compile cache and the per-engine stats lock is local.
    """

    def __init__(self, mesh=None, dtype=jnp.float32,
                 interpret: Optional[bool] = None):
        self.mesh = mesh
        self.dtype = dtype
        self.interpret = interpret
        self._lock = threading.Lock()
        #: request signature -> Accelerator.  The compile cache already
        #: dedupes CompiledKernels, but a mesh-bound Accelerator also
        #: carries the compiled MeshProgram (shard_map trace) — reusing
        #: the handle is what makes repeat shapes free multi-chip too.
        self._accs: Dict = {}
        self._stats = {"requests": 0, "algebras": set(), "partitions": {}}

    def _accelerator(self, algebra: str, dataflow, bounds):
        # algebra (str or frozen TensorAlgebra) and dataflow (None, str or
        # frozen Dataflow) are both hashable as-is
        key = (algebra, dataflow, tuple(sorted((bounds or {}).items())))
        with self._lock:
            acc = self._accs.get(key)
        if acc is None:
            from .. import api
            acc = api.generate(algebra, dataflow, bounds=bounds,
                               mesh=self.mesh, dtype=self.dtype,
                               interpret=self.interpret, validate=False)
            with self._lock:
                acc = self._accs.setdefault(key, acc)
        return acc

    def submit(self, algebra: str, operands: Dict[str, jax.Array], *,
               dataflow=None, bounds: Optional[Dict[str, int]] = None
               ) -> jax.Array:
        acc = self._accelerator(algebra, dataflow, bounds)
        out = acc(operands)
        with self._lock:
            self._stats["requests"] += 1
            self._stats["algebras"].add(acc.algebra.name)
            if acc.mesh is not None:
                # the solved partition this request executed (the CI /
                # ops-facing proof no algebra silently replicates)
                sol = acc.partition
                self._stats["partitions"][acc.algebra.name] = {
                    "strategy": sol.strategy,
                    "batch_axis": sol.batch_axis,
                    "replicated_inputs": sol.replicated_inputs()}
        return out

    def describe(self, algebra: str, *, dataflow=None,
                 bounds: Optional[Dict[str, int]] = None) -> str:
        """The served accelerator's ``describe()`` — per-tensor partition
        and comm bytes included when the engine is mesh-bound."""
        return self._accelerator(algebra, dataflow, bounds).describe()

    def stats(self) -> Dict:
        from ..compile import cache_info
        with self._lock:
            return {"requests": self._stats["requests"],
                    "algebras": sorted(self._stats["algebras"]),
                    "partitions": dict(self._stats["partitions"]),
                    "compile_cache": cache_info()}
