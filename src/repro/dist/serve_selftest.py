"""Paged-cache mesh placement selftest (run in a fresh interpreter).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.dist.serve_selftest

Checks, on 8 fake devices:
  * ``solve_page_placement`` routes the decode-attention algebra
    (batched_gemv) through the partition solver and yields a page-axis
    PartitionSpec on the batch-carrying mesh axis;
  * ``place_pools`` shards every page pool over that axis (page axis
    padded to the axis size, scratch page preserved);
  * continuous decode over the SHARDED pools stays bit-identical to the
    unsharded slot engine, insert/evict churn included, and compiles
    the decode step once.
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs import get_config
from repro.models import init_params, split
from repro.serve import SlotEngine, place_pools, solve_page_placement


def _drive(eng, prompts, steps=6):
    """Insert two requests, decode, evict one mid-flight, decode on —
    returns the packed per-step results."""
    out = []
    eng.insert(prompts[0], max_new_tokens=steps + 1)
    eng.insert(prompts[1], max_new_tokens=steps + 1)
    for t in range(steps):
        out.append(np.asarray(eng.step().data))
        if t == steps // 2:
            eng.evict(1)                   # churn: no drain, no recompile
    return out


def main() -> None:
    assert len(jax.devices()) >= 8, "selftest needs 8 fake devices"
    cfg = get_config("granite-8b").reduced()
    params, _ = split(init_params(jax.random.PRNGKey(0), cfg))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (s,)).astype(np.int32)
               for s in (9, 14)]

    def build():
        return SlotEngine(params, cfg, capacity=4, max_context=32,
                          page_size=8)

    want = _drive(build(), prompts)

    eng = build()
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(2, 4), ("x", "y"))
    sol, spec = solve_page_placement(cfg, eng.cache.layout,
                                     axes=("x", "y"), shape=(2, 4))
    assert spec[0] in ("x", "y") and spec[1] is None and spec[2] is None, (
        spec)
    print(f"page placement: strategy={sol.strategy} spec={spec}")

    place_pools(eng.cache, mesh, spec)
    axis = dict(zip(mesh.axis_names, mesh.devices.shape))[spec[0]]
    for path, pool in eng.cache.pools.items():
        assert pool.shape[0] % axis == 0, (path, pool.shape)
        assert not pool.sharding.is_fully_replicated, path
    print(f"pools sharded over '{spec[0]}' "
          f"({len(eng.cache.pools)} pools, page axis padded to x{axis})")

    got = _drive(eng, prompts)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    print(f"sharded continuous decode bit-matches unsharded "
          f"({len(got)} steps)")

    # the no-recompile contract under sharding: the placed pools and the
    # device twin carry the shardings the step returns, so insert/evict
    # churn never adds an entry — and results must repeat.
    for slot in eng.live_slots():
        eng.evict(slot)
    got2 = _drive(eng, prompts)
    for g, w in zip(got2, want):
        np.testing.assert_array_equal(g, w)
    assert eng.decode_compiles == 1, eng.decode_compiles
    print("insert/evict churn on the sharded engine: one decode compile")
    print("serve placement selftest OK")


if __name__ == "__main__":
    main()
