"""Continuous-batching slot engine: a fixed-capacity decode batch.

The decode batch has ``capacity`` slots.  Each slot holds one in-flight
sequence: its last sampled token, its absolute position, and its share of
the paged KV/SSM cache (``pages.py``).  The jitted decode step is keyed
on **capacity, never occupancy** — insert (a freshly prefilled request
lands in a free slot) and evict (a finished sequence frees its pages)
mutate host-side state and tiny device inputs only, so the batch never
drains and the step never recompiles (asserted via
:attr:`SlotEngine.decode_compiles`).

Prefill/decode split: prefill runs per request at its exact prompt
length (jit cached per length — bounded, bucket your workload), decode
runs the whole slot batch every step.  Per-slot positions ride the
``(B,)``-vector ``cache["pos"]`` support in ``models/decode.py``, so
sequences of different lengths coexist in one step.

Every step returns a :class:`ResultTokens`: tokens + validity + lengths
packed into **one** array — one device→host copy per step is much
faster than three (the JetStream observation).

The step works on the page pools in place: it donates the pools and the
lane pools, its layer loop reads each layer's K/V of every slot from the
pool (``PageLayout.layer_kv``), and only the one row per slot and layer
that the token wrote leaves the loop, to be written into the donated
pools (``PageLayout.scatter_written``).  No dense copy of the cache is
made.

Tracing: ``insert`` records the profiler spans ``serve.prefill`` (args
``rid``, ``tokens``) and ``serve.cache_insert`` (``pages``), ``step``
records ``serve.step`` (``live``) around ``serve.fetch``, the host read
of the packed result; they record nothing while the profiler is off.
Inside the step program, ``jax.named_scope`` names ``cache_gather``
(each layer's pool read), ``sample`` and ``cache_scatter`` (the row
writes; ``decode_step`` names ``attention``, ``mlp`` and ``head``);
:meth:`SlotEngine.step_hlo_text` returns the compiled step, whose op
metadata carries those scopes.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec

from ..configs.base import ModelConfig
from ..models import decode as dec
from .engine import ServeConfig
from .pages import PagedKVCache, _flatten_cache, _nest


@dataclasses.dataclass(frozen=True)
class ResultTokens:
    """One decode step's results, packed into a single (capacity, 3)
    int32 array so only one device→host copy happens per step.

    Column ranges (JetStream-style index tuples):
    ``tokens_idx`` the sampled token, ``valid_idx`` whether the slot was
    live this step, ``length_idx`` the slot's absolute position after
    the step (prompt + generated so far).
    """

    data: np.ndarray
    tokens_idx: Tuple[int, int] = (0, 1)
    valid_idx: Tuple[int, int] = (1, 2)
    length_idx: Tuple[int, int] = (2, 3)

    def token_at(self, slot: int) -> int:
        return int(self.data[slot, self.tokens_idx[0]])

    def valid_at(self, slot: int) -> bool:
        return bool(self.data[slot, self.valid_idx[0]])

    def length_at(self, slot: int) -> int:
        return int(self.data[slot, self.length_idx[0]])


class SlotEngine:
    """Fixed-capacity continuous-batching decode engine over a paged
    cache.  Thread-compatible (one caller drives step/insert/evict; the
    async server in ``server.py`` is that caller)."""

    def __init__(self, params, cfg: ModelConfig, *, capacity: int = 8,
                 max_context: int = 256, page_size: int = 16,
                 total_pages: Optional[int] = None,
                 serve_cfg: Optional[ServeConfig] = None):
        self.params = params
        self.cfg = cfg
        self.capacity = int(capacity)
        self.max_context = int(max_context)
        self.serve_cfg = serve_cfg or ServeConfig()

        fe = None
        if cfg.family in ("encdec", "vlm"):
            fe = jax.ShapeDtypeStruct(
                (self.capacity, cfg.frontend_tokens, cfg.d_model),
                jnp.float32)
        # template prompt length: attention leaves are length-independent
        # (``_fit_cache`` pads/rolls to max_len) but the SSM conv window is
        # (B, min(s0, conv_kernel - 1), cd) — a full-length prompt yields
        # the steady-state shape every real insert must match.
        _, template = jax.eval_shape(
            functools.partial(dec.prefill, cfg=cfg, max_len=self.max_context),
            params,
            jax.ShapeDtypeStruct((self.capacity, self.max_context), jnp.int32),
            frontend=fe)
        self.cache = PagedKVCache(template, capacity=self.capacity,
                                  page_size=page_size,
                                  total_pages=total_pages)

        self._prefill = jax.jit(functools.partial(dec.prefill, cfg=cfg),
                                static_argnames=("max_len",))
        # the step donates its pools and lanes: the row writes alias them
        self._step_fn = jax.jit(self._build_step(), donate_argnums=(5, 6))
        self._base_key = jax.random.PRNGKey(self.serve_cfg.seed)
        self._step_count = 0
        self._prefill_count = 0

        c = self.capacity
        self._tokens = np.zeros((c, 1), np.int32)
        self._pos = np.zeros((c,), np.int32)
        self._active = np.zeros((c,), bool)
        self._live = 0                     # == self._active.sum()
        #: device twin of (tokens, pos, active, table).  The jitted step
        #: carries tokens/pos forward on device, so steady-state decode
        #: does ZERO host->device transfers — the twin re-syncs from the
        #: host mirrors only after insert/evict touched them.
        self._dev: Optional[Tuple] = None

    # -- introspection ----------------------------------------------------
    @property
    def decode_compiles(self) -> int:
        """Jit cache entries of the decode step — stays 1 across any
        sequence of insert/evict (the continuous-batching contract)."""
        return self._step_fn._cache_size()

    @property
    def prefill_compiles(self) -> int:
        return self._prefill._cache_size()

    def free_slots(self) -> Tuple[int, ...]:
        return tuple(int(i) for i in np.flatnonzero(~self._active))

    def live_slots(self) -> Tuple[int, ...]:
        return tuple(int(i) for i in np.flatnonzero(self._active))

    @property
    def occupancy(self) -> float:
        return self._live / self.capacity

    def position(self, slot: int) -> int:
        return int(self._pos[slot])

    # -- the jitted step ---------------------------------------------------
    def _build_step(self):
        cfg, lay = self.cfg, self.cache.layout
        scfg = self.serve_cfg

        def sample(logits: jax.Array, key) -> jax.Array:
            if scfg.temperature <= 0.0:
                return jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
            scaled = logits / scfg.temperature
            return (jax.random.categorical(key, scaled, axis=-1)[:, None]
                .astype(jnp.int32))

        def step(params, tokens, pos, active, table, pools, lanes, key):
            cache: Dict[str, Any] = _nest({**lay.layer_inputs(), **lanes})
            cache["pos"] = pos
            logits, new_cache = dec.decode_step(
                params, tokens, cache, cfg, kv=lay.layer_kv(pools, table))
            flat_new = _flatten_cache(new_cache)
            with jax.named_scope("cache_scatter"):
                pools2 = lay.scatter_written(
                    pools, table, {p: flat_new[p] for p, _ in lay.paged},
                    pos, active)
                lanes2 = lay.freeze_inactive(
                    lanes, {p: flat_new[p] for p in lanes}, active)
            with jax.named_scope("sample"):
                tok = sample(logits, key)
            new_pos = jnp.where(active, pos + 1, pos)
            new_tokens = jnp.where(active[:, None], tok, tokens)
            packed = jnp.concatenate(
                [tok, active[:, None].astype(jnp.int32),
                 new_pos[:, None]], axis=1)
            return packed, (new_tokens, new_pos), pools2, lanes2

        return step

    # -- slot lifecycle ----------------------------------------------------
    def insert(self, prompt: np.ndarray, *, max_new_tokens: int,
               frontend: Optional[np.ndarray] = None, rid: int = -1
               ) -> Optional[Tuple[int, int]]:
        """Prefill one request and land it in a free slot.

        ``prompt``: (s0,) int32.  Returns ``(slot, first_token)`` — the
        first token is sampled from the prefill logits, exactly like
        ``DecodeEngine.generate`` — or None when no slot or not enough
        free pages (the caller keeps the request queued).  ``rid`` only
        labels the request's ``serve.prefill`` span.
        """
        s0 = int(prompt.shape[-1])
        if s0 + max_new_tokens > self.max_context:
            raise ValueError(
                f"prompt ({s0}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_context ({self.max_context})")
        if (self.cfg.family in ("ssm", "hybrid")
                and s0 < self.cfg.conv_kernel - 1):
            # model-level floor (the sequential path shares it): the SSM
            # decode recurrence needs a full conv window from prefill
            raise ValueError(
                f"prompt ({s0}) shorter than the SSM conv window "
                f"({self.cfg.conv_kernel - 1})")
        free = self.free_slots()
        if not free:
            return None
        slot = free[0]
        if not self.cache.alloc(slot, s0 + max_new_tokens):
            return None
        fe = None if frontend is None else jnp.asarray(frontend)
        with TraceAnnotation("serve.prefill", rid=rid, tokens=s0):
            logits, cache_p = self._prefill(
                self.params, jnp.asarray(prompt, jnp.int32)[None],
                frontend=fe, max_len=self.max_context)
            self._prefill_count += 1
            if self.serve_cfg.temperature <= 0.0:
                tok = int(jnp.argmax(logits, axis=-1)[0])
            else:
                key = jax.random.fold_in(self._base_key, self._prefill_count)
                tok = int(jax.random.categorical(
                    key, logits / self.serve_cfg.temperature, axis=-1)[0])
        pages = self.cache.pages_needed(s0 + max_new_tokens)
        with TraceAnnotation("serve.cache_insert", pages=pages):
            self.cache.insert(slot, cache_p)
        self._pos[slot] = s0
        self._tokens[slot, 0] = tok
        self._active[slot] = True
        self._live += 1
        self._dev = None
        return slot, tok

    def evict(self, slot: int) -> None:
        """Free a finished slot's pages; the decode batch keeps running
        for the other slots (no drain, no recompile)."""
        self.cache.free(slot)
        self._live -= int(self._active[slot])
        self._active[slot] = False
        self._pos[slot] = 0
        self._tokens[slot, 0] = 0
        self._dev = None

    # -- one decode step ---------------------------------------------------
    def _twin_put(self):
        """Placement of the device twin: replicated over the mesh the page
        pools were placed on (``pages.place_pools``), which is how the
        step returns the tokens and positions it carries, else the default
        device.  Either way every step sees one set of input shardings,
        so the decode step compiles once on a mesh too."""
        for pool in self.cache.pools.values():
            sharding = pool.sharding
            if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1:
                rep = NamedSharding(sharding.mesh, PartitionSpec())
                return lambda x: jax.device_put(x, rep)
        return jnp.asarray

    def _step_args(self, key) -> Tuple:
        """The decode step's arguments; re-syncs the device twin from the
        host mirrors after an insert or evict."""
        if self._dev is None:
            put = self._twin_put()
            self._dev = (put(self._tokens), put(self._pos),
                         put(self._active), put(self.cache.device_table()))
        return (self.params, *self._dev, self.cache.pools, self.cache.lanes,
                key)

    def step(self) -> ResultTokens:
        """Advance every live slot one token; packed device→host copy."""
        key = self._base_key
        if self.serve_cfg.temperature > 0.0:
            key = jax.random.fold_in(self._base_key, -1 - self._step_count)
        with TraceAnnotation("serve.step", live=self._live):
            packed, (tokens, pos), pools, lanes = self._step_fn(
                *self._step_args(key))
            self._dev = (tokens, pos, *self._dev[2:])
            self.cache.pools, self.cache.lanes = pools, lanes
            self._step_count += 1
            with TraceAnnotation("serve.fetch"):
                data = np.asarray(packed)  # the one device->host copy
            live = self._active
            self._tokens[live, 0] = data[live, 0]
            self._pos[live] += 1
        return ResultTokens(data)

    def step_hlo_text(self) -> str:
        """The compiled decode step's optimized HLO for the engine's
        current shapes.  Each op's ``metadata={op_name=...}`` carries the
        step's named scopes.  Once the step has run this is the jit's
        own executable: nothing is compiled."""
        return self._step_fn.lower(
            *self._step_args(self._base_key)).compile().as_text()
