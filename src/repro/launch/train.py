"""Production training entry point.

    python -m repro.launch.train --arch qwen2.5-32b --steps 1000 \
        [--multi-pod] [--smoke]

On real TPU hardware this builds the production mesh and runs the sharded
fault-tolerant driver; ``--smoke`` scales the config down and runs on
whatever devices exist (CI / this CPU container).
"""
from __future__ import annotations

import argparse
import dataclasses


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=1000)
    ap.add_argument("--global-batch", type=int, default=256)
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="ckpts")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config, no mesh (CPU CI)")
    args = ap.parse_args()
    from .cache import enable_compile_cache

    enable_compile_cache()

    import jax

    from ..configs import get_config
    from ..data.pipeline import DataConfig
    from ..launch.mesh import make_production_mesh
    from ..launch.specs import opt_config_for
    from ..runtime.driver import RunConfig, TrainDriver

    cfg = get_config(args.arch)
    mesh = None
    if args.smoke:
        cfg = cfg.reduced()
        batch, seq = 8, 64
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
        batch, seq = args.global_batch, args.seq_len

    opt_cfg = dataclasses.replace(opt_config_for(cfg), lr=args.lr,
                                  total_steps=args.steps)
    driver = TrainDriver(
        cfg, opt_cfg,
        DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                   n_shards=max(1, jax.process_count()),
                   shard=jax.process_index()),
        RunConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                  ckpt_dir=args.ckpt_dir),
        mesh=mesh,
    )
    out = driver.run()
    for m in out["metrics"][-5:]:
        print(m)
    print(f"finished at step {out['final_step']}")


if __name__ == "__main__":
    main()
