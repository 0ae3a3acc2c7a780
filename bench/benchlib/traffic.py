"""The one traffic generator: a mix file's parameters -> requests.

A serving mix (``"kind": "serve"``) fixes the deployment it is served
under (``capacity``, ``max_context``, ``page_size``,
``prefill_per_step``), its arrivals and its length distributions:

* ``"arrival"``: ``"poisson"`` at ``rate_rps``; ``"backlog"``:
  ``requests`` all due at 0.
* ``"prompt"`` / ``"output"``: lognormal ``median`` and ``sigma``,
  clipped to [``min``, ``max``]; with ``"buckets"`` a length is rounded
  up to the next bucket.

The arrival times and each request's (prompt, output) lengths are drawn
once from the mix's ``draw_seed``.  The run's seed chooses only the
token ids, so every seed asks for the same work on the same schedule,
in the same order.  Other keys of a mix (``source``, ``assumed``) say
where its numbers come from; the generator does not read them.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Mapping

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float             # seconds after the window opens
    prompt: np.ndarray       # (prompt_len,) int32
    output_len: int

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])


def lengths(rng: np.random.Generator, n: int, spec: Mapping) -> np.ndarray:
    """``n`` lengths at the (i + 1/2) / n quantiles of the lognormal,
    clipped and bucketed, in an order drawn from ``rng``: a short window
    holds the distribution's own median and spread, not a sample's."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(np.ceil(x), spec["min"], spec["max"]).astype(np.int64)
    buckets = spec.get("buckets")
    if buckets:
        b = np.asarray(sorted(buckets))
        if x.max(initial=0) > b[-1]:
            raise ValueError(f"largest bucket {b[-1]} < max {spec['max']}")
        x = b[np.searchsorted(b, x)]
    return rng.permutation(x)


def _due(rng: np.random.Generator, mix: Mapping, seconds: float
         ) -> np.ndarray:
    """Poisson arrivals at ``rate_rps`` given their expected number in
    the window: that many times, each uniform over the window."""
    if mix["arrival"] != "poisson":
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    n = int(round(float(mix["rate_rps"]) * seconds))
    return np.sort(rng.uniform(0.0, seconds, n))


def request_set(mix: Mapping, seconds: float):
    """(due times, prompt lengths, output lengths) drawn from
    ``draw_seed``: the same for every run seed."""
    draw = np.random.default_rng(int(mix["draw_seed"]))
    if mix["arrival"] == "backlog":
        due = np.zeros(int(mix["requests"]))
    else:
        due = _due(draw, mix, seconds)
    n = len(due)
    return due, lengths(draw, n, mix["prompt"]), lengths(draw, n,
                                                         mix["output"])


def serve_requests(mix: Mapping, seconds: float, seed: int, vocab: int
                   ) -> List[Request]:
    """The run's requests, sorted by due time."""
    due, plen, olen = request_set(mix, seconds)
    rng = np.random.default_rng(seed)
    return [Request(float(t), rng.integers(0, vocab, int(p), dtype=np.int32),
                    int(o))
            for t, p, o in zip(due, plen, olen)]


def page_counts(mix: Mapping, requests: List[Request]) -> List[int]:
    """Distinct page counts the requests reserve (prompt + output over
    the page size, rounded up): the shapes the cache insert sees."""
    page = int(mix["page_size"])
    return sorted({math.ceil(min(r.prompt_len + r.output_len,
                                 int(mix["max_context"])) / page)
                   for r in requests})

