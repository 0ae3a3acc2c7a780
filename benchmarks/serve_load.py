"""Open-loop serving load: continuous batching vs static batching.

    PYTHONPATH=src python benchmarks/serve_load.py [--smoke] [--arch ...]

Generates a mixed prompt/output-length workload (``configs.SERVE_MIXES``),
drives it through both serving paths and reports throughput (tok/s),
p50/p99 request latency and slot occupancy:

* **continuous** — ``ContinuousServer`` over a ``SlotEngine``: requests
  land in free slots as they arrive, finished sequences are evicted
  without draining, the decode step never recompiles (asserted);
* **static** — the baseline ``DecodeEngine``: arrival-order batches of
  ``capacity``, prompts padded to the batch max, every batch decodes
  ``max(output_lens)`` steps and drains before the next batch starts.

Emits ``BENCH_serve.json`` (schema: ``repro.serve.report``) at the repo
root.  ``--smoke`` uses the burst mix, checks per-request bit-parity
against sequential ``DecodeEngine.generate`` and asserts the >= 1.5x
continuous-over-static throughput floor (the CI gate).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import time
from typing import Dict, List, Tuple

import numpy as np
import jax

from repro.configs import SERVE_MIXES, get_config
from repro.models import init_params, split
from repro.serve import (ContinuousServer, DecodeEngine, ServeConfig,
                         SlotEngine, serve_entry, validate_serve)

ROOT = pathlib.Path(__file__).resolve().parent.parent


def build_workload(mix, seed: int = 0) -> List[Tuple[float, np.ndarray, int]]:
    """[(arrival_time_s, prompt, max_new_tokens)] — Poisson arrivals at
    ``mix.rate_rps`` (all zero for a burst mix).  Lengths cycle through
    the buckets so every (prompt, output) combination appears."""
    rng = np.random.default_rng(seed)
    t, out = 0.0, []
    for i in range(mix.requests):
        if mix.rate_rps > 0:
            t += rng.exponential(1.0 / mix.rate_rps)
        s0 = mix.prompt_lens[i % len(mix.prompt_lens)]
        t_new = mix.output_lens[(i // len(mix.prompt_lens))
                                % len(mix.output_lens)]
        prompt = rng.integers(0, 64, (s0,)).astype(np.int32)
        out.append((t if mix.rate_rps > 0 else 0.0, prompt, t_new))
    return out


def run_continuous(params, cfg, workload, *, capacity: int, page_size: int,
                   max_context: int) -> Tuple[Dict, List[np.ndarray]]:
    engine = SlotEngine(params, cfg, capacity=capacity,
                        max_context=max_context, page_size=page_size,
                        serve_cfg=ServeConfig())
    # warmup outside the clock: compile prefill (per prompt length) and
    # the one decode step
    for s0 in sorted({p.shape[0] for _, p, _ in workload}):
        slot, _ = engine.insert(np.ones((s0,), np.int32), max_new_tokens=1)
        engine.step()
        engine.evict(slot)
    assert engine.decode_compiles == 1, engine.decode_compiles

    futures = []
    t0 = time.perf_counter()
    with ContinuousServer(engine, prefill_per_step=2) as server:
        for arrive_at, prompt, t_new in workload:
            now = time.perf_counter() - t0
            if arrive_at > now:
                time.sleep(arrive_at - now)
            futures.append(server.submit(prompt, max_new_tokens=t_new))
        server.drain(timeout=600)
        elapsed = time.perf_counter() - t0
        outputs = [f.result() for f in futures]
        lat = np.array([f.latency_s for f in futures])
        stats = {
            "throughput_tok_s": sum(map(len, outputs)) / elapsed,
            "p50_latency_s": float(np.percentile(lat, 50)),
            "p99_latency_s": float(np.percentile(lat, 99)),
            "mean_occupancy": server.mean_occupancy(),
            "steps": server.stats["steps"],
            "decode_compiles": engine.decode_compiles,
        }
    assert engine.decode_compiles == 1, (
        f"decode recompiled: {engine.decode_compiles} entries")
    return stats, outputs


def run_static(params, cfg, workload, *, capacity: int,
               pad_to: Tuple[int, ...]) -> Tuple[Dict, List[np.ndarray]]:
    """Arrival-order batches of ``capacity``; prompts right-padded to the
    batch max (bucketed so jit reuse is fair) and every batch decodes
    ``max(t_new)`` steps — the drain the slot engine avoids."""
    engine = DecodeEngine(params, cfg, ServeConfig())
    batches = []
    for start in range(0, len(workload), capacity):
        batch = workload[start:start + capacity]
        s_max = min(p for p in pad_to
                    if p >= max(q.shape[0] for _, q, _ in batch))
        t_max = max(t for _, _, t in batch)
        prompts = np.ones((len(batch), s_max), np.int32)
        for i, (_, q, _) in enumerate(batch):
            prompts[i, :q.shape[0]] = q   # right-pad: same left-aligned rope
        if len(batch) < capacity:         # static batches are fixed-size
            prompts = np.pad(prompts, ((0, capacity - len(batch)), (0, 0)),
                             constant_values=1)
        batches.append((batch, prompts, t_max))
    # warmup outside the clock: compile each (prompt_len, cache_len) the
    # timed loop will actually hit — same treatment the continuous path got
    for shape in sorted({(p.shape[1], t) for _, p, t in batches}):
        engine.generate(np.ones((capacity, shape[0]), np.int32),
                        max_new_tokens=shape[1])

    t0 = time.perf_counter()
    outputs: List[np.ndarray] = []
    finished_at: List[float] = []
    for batch, prompts, t_max in batches:
        gen, _ = engine.generate(prompts, max_new_tokens=t_max)
        done = time.perf_counter() - t0
        for i, (_, _, t_new) in enumerate(batch):
            outputs.append(gen[i, :t_new])
            finished_at.append(done)
    elapsed = time.perf_counter() - t0
    lat = np.array(finished_at) - np.array([a for a, _, _ in workload])
    return {
        "throughput_tok_s": sum(map(len, outputs)) / elapsed,
        "p50_latency_s": float(np.percentile(lat, 50)),
        "p99_latency_s": float(np.percentile(lat, 99)),
    }, outputs


def check_parity(params, cfg, workload, outputs, *, max_context: int) -> None:
    """Continuous outputs must be bit-identical to sequential
    ``DecodeEngine.generate`` with the cache pinned to max_context."""
    oracle = DecodeEngine(params, cfg, ServeConfig())
    for (_, prompt, t_new), got in zip(workload, outputs):
        want, _ = oracle.generate(prompt[None], max_new_tokens=t_new,
                                  cache_len=max_context)
        assert np.array_equal(got, want[0]), (
            f"parity broke: got {got.tolist()} want {want[0].tolist()}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube-1.8b")
    ap.add_argument("--mix", default=None,
                    help="workload mix name (default: smoke/mixed by mode)")
    ap.add_argument("--capacity", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="burst mix + parity check + speedup assertion (CI)")
    args = ap.parse_args(argv)

    mix = SERVE_MIXES[args.mix or ("smoke" if args.smoke else "mixed")]
    cfg = get_config(args.arch).reduced()
    params, _ = split(init_params(jax.random.PRNGKey(0), cfg))
    workload = build_workload(mix)
    max_context = mix.max_context()
    if max_context % args.page_size:
        max_context += args.page_size - max_context % args.page_size

    print(f"serve load: {cfg.name} ({cfg.family}) | mix={mix.name} "
          f"({mix.requests} reqs, {mix.arrival}) | capacity={args.capacity} "
          f"page={args.page_size} context={max_context}")

    cont, outputs = run_continuous(params, cfg, workload,
                                   capacity=args.capacity,
                                   page_size=args.page_size,
                                   max_context=max_context)
    static, _ = run_static(params, cfg, workload, capacity=args.capacity,
                           pad_to=tuple(sorted(mix.prompt_lens)))

    parity = False
    if args.smoke:
        check_parity(params, cfg, workload, outputs, max_context=max_context)
        parity = True
        print("parity: continuous == sequential generate (bit-identical)")

    doc = serve_entry(smoke=args.smoke, arch=cfg.name,
                      capacity=args.capacity, page_size=args.page_size,
                      max_context=max_context,
                      workload={"requests": mix.requests,
                                "arrival": mix.arrival,
                                "rate_rps": mix.rate_rps,
                                "prompt_lens": list(mix.prompt_lens),
                                "output_lens": list(mix.output_lens)},
                      continuous=cont, static=static, parity_checked=parity)
    problems = validate_serve(doc)
    assert not problems, f"BENCH_serve schema violations: {problems}"
    out_path = ROOT / "BENCH_serve.json"
    out_path.write_text(json.dumps(doc, indent=2) + "\n")

    print(f"continuous: {cont['throughput_tok_s']:8.1f} tok/s | "
          f"p50 {cont['p50_latency_s'] * 1e3:7.1f} ms | "
          f"p99 {cont['p99_latency_s'] * 1e3:7.1f} ms | "
          f"occupancy {cont['mean_occupancy']:.2f} | "
          f"steps {cont['steps']}")
    print(f"static:     {static['throughput_tok_s']:8.1f} tok/s | "
          f"p50 {static['p50_latency_s'] * 1e3:7.1f} ms | "
          f"p99 {static['p99_latency_s'] * 1e3:7.1f} ms")
    print(f"speedup: {doc['speedup']:.2f}x | wrote {out_path.name}")

    if args.smoke:
        assert doc["speedup"] >= 1.5, (
            f"continuous batching speedup {doc['speedup']:.2f}x < 1.5x floor")


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    main()
