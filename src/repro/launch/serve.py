"""Production serving entry point.

    python -m repro.launch.serve --arch mixtral-8x22b [--smoke]

``--smoke`` serves the reduced config with random weights on this container;
on hardware, point --ckpt at a training checkpoint and the engine restores
bf16 weights sharded over the production mesh.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    from .cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import numpy as np

    from ..checkpoint import store
    from ..configs import get_config
    from ..models import init_params, split
    from ..serve.engine import DecodeEngine, ServeConfig

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    params, _ = split(init_params(jax.random.PRNGKey(0), cfg))
    if args.ckpt:
        params, step, _ = store.restore(args.ckpt, params)
        print(f"restored checkpoint step {step}")

    engine = DecodeEngine(params, cfg,
                          ServeConfig(max_new_tokens=args.new_tokens))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, size=(args.batch, args.prompt_len)
                           ).astype(np.int32)
    frontend = None
    if cfg.family in ("encdec", "vlm"):
        frontend = 0.05 * rng.standard_normal(
            (args.batch, cfg.frontend_tokens, cfg.d_model)).astype(np.float32)
    gen, stats = engine.generate(prompts, frontend=frontend)
    print(f"generated {stats['generated']} tokens x {args.batch} sequences")
    print(gen[:2])


if __name__ == "__main__":
    main()
