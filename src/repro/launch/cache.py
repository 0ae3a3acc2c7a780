"""JAX's persistent compilation cache for the repository's entry points.

Entry points (``chip_smoke.py``, ``examples/``, ``benchmarks/run.py``,
``launch/serve.py``, ``launch/train.py``) call
:func:`enable_compile_cache` once before they compile anything; importing
``repro`` never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and that directory is the only cache.  Otherwise the
cache sits at ``<checkout>/.jax_cache``: a fixed path, because the path
is part of what makes a later process find an entry.
"""
from __future__ import annotations

import os
import pathlib
from typing import Dict, Optional

#: the checkout's own cache directory (listed in .gitignore)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
           "/jax/compilation_cache/cache_misses": "misses"}
_COUNTS: Dict[str, int] = {"hits": 0, "misses": 0}
_enabled = False


def _count(event: str, **_) -> None:
    if event in _EVENTS:
        _COUNTS[_EVENTS[event]] += 1


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache on a TPU and count its
    hits and misses (:func:`compile_cache_stats`).  Returns the cache
    directory, or None off the chip: XLA:CPU's cached executables are
    tied to the CPU features of the host that compiled them, so CPU runs
    compile afresh.  Every chip compile is cached, however short: a cold
    process on the chip pays for kernels as well as for whole steps."""
    global _enabled
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if jax.default_backend() != "tpu":
        return path
    if not path:
        CACHE_DIR.mkdir(exist_ok=True)
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not _enabled:
        jax.monitoring.register_event_listener(_count)
        _enabled = True
    return path


def compile_cache_stats() -> Dict[str, int]:
    """Persistent-cache hits and misses since :func:`enable_compile_cache`
    (a miss is a compile the cache did not hold)."""
    return dict(_COUNTS)
