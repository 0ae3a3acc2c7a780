"""The shared wall-clock measurement harness (ISSUE 6 satellite 2).

One deterministic timing loop for the whole repo: the measured autotuner
(``repro.tune.tuner``), the perf benchmarks (``benchmarks/*.py``) and the
calibration fit all time through :func:`measure`, so every number the
tuning cache persists and every number a benchmark prints was produced
the same way —

  * a fixed number of **warmup** calls runs first (compilation/tracing
    lands outside the clock),
  * each timed call blocks on the result (``jax.block_until_ready`` — a
    dispatch-only time would flatter every asynchronous backend),
  * the reported statistic is the **median** of ``repeats`` timed calls
    (robust to one-off scheduler noise; the min and mean are kept for
    benchmarks that historically printed best-of).

The harness is backend-agnostic: it times whatever callable it is given,
so interpret-mode Pallas (the CPU fallback every environment can run),
compiled Mosaic on a real TPU, and plain XLA baselines all measure
identically.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Tuple

import jax

#: defaults shared by the tuner, the benchmarks and the CI smoke step
DEFAULT_WARMUP = 1
DEFAULT_REPEATS = 5


@dataclasses.dataclass(frozen=True)
class Measurement:
    """One harness run: every timed sample plus the warmup cost."""

    times_s: Tuple[float, ...]
    warmup_s: float

    @property
    def median_s(self) -> float:
        s = sorted(self.times_s)
        n = len(s)
        mid = n // 2
        return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])

    @property
    def best_s(self) -> float:
        return min(self.times_s)

    @property
    def mean_s(self) -> float:
        return sum(self.times_s) / len(self.times_s)

    def cycles(self, freq_mhz: float) -> float:
        """The median expressed in cycles of a ``freq_mhz`` clock — the
        unit the calibration fit compares against ``CostReport.cycles``."""
        return self.median_s * freq_mhz * 1e6


def measure(fn: Callable, *args, warmup: int = DEFAULT_WARMUP,
            repeats: int = DEFAULT_REPEATS, **kwargs) -> Measurement:
    """Time ``fn(*args, **kwargs)``: warmup outside the clock, then
    median-of-``repeats`` with ``block_until_ready`` on every result."""
    if repeats < 1:
        raise ValueError(f"repeats must be >= 1, got {repeats}")
    t0 = time.perf_counter()
    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn(*args, **kwargs))
    warmup_s = time.perf_counter() - t0
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args, **kwargs))
        times.append(time.perf_counter() - t0)
    return Measurement(times_s=tuple(times), warmup_s=warmup_s)
