"""Serve a small model through the continuous-batching server.

    PYTHONPATH=src python examples/serve_lm.py [--arch h2o-danube-1.8b]

Submits a mixed-length wave of requests to ``ContinuousServer`` (slot
engine + paged KV cache underneath), then replays each prompt through
the static-batch ``DecodeEngine`` — the sequential oracle — and checks
the continuous outputs are bit-identical.
"""
import argparse

import numpy as np
import jax

from repro.configs import get_config
from repro.models import init_params, split
from repro.serve import ContinuousServer, DecodeEngine, ServeConfig, SlotEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--max-context", type=int, default=64)
    ap.add_argument("--page-size", type=int, default=8)
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    cfg = get_config(args.arch).reduced()
    print(f"serving {cfg.name} ({cfg.family}); "
          f"{cfg.param_count() / 1e6:.2f}M params (reduced config)")
    params, _ = split(init_params(jax.random.PRNGKey(0), cfg))

    engine = SlotEngine(params, cfg, capacity=args.capacity,
                        max_context=args.max_context,
                        page_size=args.page_size,
                        serve_cfg=ServeConfig())
    rng = np.random.default_rng(0)
    requests = [(rng.integers(0, cfg.vocab, (s0,)).astype(np.int32), t_new)
                for s0, t_new in [(24, 16), (12, 8), (32, 12), (8, 20),
                                  (16, 16), (24, 8)]]

    with ContinuousServer(engine, prefill_per_step=2) as server:
        futures = [server.submit(p, max_new_tokens=t) for p, t in requests]
        server.drain(timeout=600)
        outputs = [f.result() for f in futures]
        print(f"served {len(requests)} requests in {server.stats['steps']} "
              f"decode steps (mean occupancy "
              f"{server.mean_occupancy():.2f}, decode compiles "
              f"{engine.decode_compiles})")
    for i, out in enumerate(outputs):
        print(f"  req {i} ({len(requests[i][0])} -> {len(out)}): "
              f"{out.tolist()}")

    # oracle: sequential static-batch decode with the same cache budget
    oracle = DecodeEngine(params, cfg)
    for (prompt, t_new), out in zip(requests, outputs):
        want, _ = oracle.generate(prompt[None], max_new_tokens=t_new,
                                  cache_len=args.max_context)
        assert np.array_equal(out, want[0]), "continuous != sequential"
    print("serve OK (continuous outputs bit-identical to sequential decode)")


if __name__ == "__main__":
    main()
