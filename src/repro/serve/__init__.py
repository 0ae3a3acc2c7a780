"""Serving: batched LM decode, continuous batching, accelerator serving.

Layered like a real inference stack:

* ``engine``  — per-call engines: ``DecodeEngine`` (static batch, the
  sequential parity oracle) and ``AcceleratorEngine`` (STT front door as
  a service);
* ``pages``   — paged decode cache (fixed-size pages, slot→page-table
  indirection, shared pool) + mesh placement via the partition solver;
* ``slots``   — fixed-capacity continuous-batching slot engine over the
  paged cache (insert/evict without draining or recompiling);
* ``server``  — thread-safe async dispatch loop with per-request futures.
"""
from . import engine, pages, server, slots
from .engine import AcceleratorEngine, DecodeEngine, ServeConfig
from .pages import PagedKVCache, PageLayout, place_pools, solve_page_placement
from .server import ContinuousServer, Request, RequestFuture
from .slots import ResultTokens, SlotEngine

__all__ = [
    "engine", "pages", "server", "slots",
    "AcceleratorEngine", "DecodeEngine", "ServeConfig",
    "PagedKVCache", "PageLayout", "place_pools", "solve_page_placement",
    "ContinuousServer", "Request", "RequestFuture",
    "ResultTokens", "SlotEngine",
]
