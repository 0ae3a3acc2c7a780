"""The traffic generator and the latency metrics read from its
requests."""
import json
import math
import os
import types

import _bench_path  # noqa: F401
import numpy as np
import pytest

from benchlib import spec, traffic
from benchlib.stats import percentile

MIXES = os.path.join(_bench_path.BENCH, "traffic")


def mix(name):
    with open(os.path.join(MIXES, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_requests_are_seeded_bucketed_and_fit_the_context(name):
    m = mix(name)
    a = traffic.serve_requests(m, 30.0, 2 ** 31 + 7, 32000)
    b = traffic.serve_requests(m, 30.0, 2 ** 31 + 7, 32000)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert {r.prompt_len for r in a} <= set(m["prompt"]["buckets"])
    for r in a:
        assert r.prompt_len + r.output_len <= m["max_context"]
        assert m["output"]["min"] <= r.output_len <= m["output"]["max"]
        assert 0 <= r.due_s < 30.0
    assert [r.due_s for r in a] == sorted(r.due_s for r in a)


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_every_seed_asks_for_the_same_work_on_the_same_schedule(name):
    m = mix(name)
    a = traffic.serve_requests(m, 30.0, 1, 32000)
    b = traffic.serve_requests(m, 30.0, 2 ** 33 + 1, 32000)
    assert ([(r.due_s, r.prompt_len, r.output_len) for r in a]
            == [(r.due_s, r.prompt_len, r.output_len) for r in b])
    assert not np.array_equal(a[0].prompt, b[0].prompt)


@pytest.mark.parametrize("name", ["chat", "batch"])
def test_lengths_hold_the_mix_medians(name):
    """The lengths are the distribution's quantiles, so even a short
    window's requests have the source's medians (up to the bucket)."""
    m = mix(name)
    reqs = traffic.serve_requests(m, 51.0, 3, 32000)
    prompt = float(np.median([r.prompt_len for r in reqs]))
    output = float(np.median([r.output_len for r in reqs]))
    above = min(b for b in m["prompt"]["buckets"]
                if b >= m["prompt"]["median"])
    assert m["prompt"]["median"] <= prompt <= above
    assert abs(output - m["output"]["median"]) <= 1


def test_rate_sets_the_number_of_requests():
    m = dict(mix("chat"), rate_rps=4.0)
    n = len(traffic.serve_requests(m, 50.0, 3, 32000))
    assert 150 < n < 250


def test_backlog_is_due_at_once():
    m = mix("batch")
    reqs = traffic.serve_requests(m, 30.0, 5, 49152)
    assert len(reqs) == m["requests"] and all(r.due_s == 0 for r in reqs)


def test_nearest_rank_percentile():
    assert percentile(list(range(1, 101)), 95) == 95
    assert percentile([3.0, 1.0, math.inf], 95) == math.inf
    assert percentile([], 95) is None


def _rec(due, first, finished, n, out_len=None):
    r = types.SimpleNamespace(due=due, first=first, finished=finished,
                              tokens=None if n is None else np.zeros(n),
                              request=types.SimpleNamespace(
                                  output_len=out_len or n or 4))
    r.served = n is not None and n == r.request.output_len
    return r


def test_ttft_is_timed_from_the_due_time_not_the_send():
    cell = spec.load_cell(_bench_path.REPO, "danube.chat")
    ttft = next(m for m in cell.per_layer if m.name == "ttft_p90_ms.chat")
    # sent 5 s late, as a stalled sender would: the wait still counts
    recs = [_rec(10.0, 15.0 + 0.01 * i, 16.0, 5) for i in range(10)]
    for r in recs:
        r.submitted = 15.0
    run = types.SimpleNamespace(kind="serve", records=recs)
    assert ttft.read(run) == pytest.approx(5080.0)     # 9th of 10
    recs.append(_rec(10.0, None, None, None))          # never served
    assert ttft.read(run) == pytest.approx(5090.0)     # 10th of 11


def test_tpot_counts_unserved_requests_against_the_tail():
    cell = spec.load_cell(_bench_path.REPO, "danube.chat")
    tpot = next(m for m in cell.end_to_end if m.name == "tpot_p90_ms")
    # 20 requests of 5 tokens, 0.1 s .. 2.0 s from first to last token
    recs = [_rec(10.0, 10.0, 10.0 + 0.1 * (i + 1), 5) for i in range(20)]
    run = types.SimpleNamespace(kind="serve", records=recs)
    assert tpot.read(run) == pytest.approx(450.0)     # 18th of 20: 1.8/4
    recs.append(_rec(10.0, None, None, None))         # never served
    assert tpot.read(run) == pytest.approx(475.0)     # 19th of 21: 1.9/4
    run.records = recs[:1] + [_rec(10.0, None, None, None)]
    assert tpot.read(run) is None
