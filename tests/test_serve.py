"""Continuous-batching serving: paged cache, slot engine, async server.

The load-bearing claims, each tested directly:
  * the paged gather reads one layer of the pool through the page table,
    and the row scatter writes one row per slot and layer;
  * the page pool's host accounting (alloc/free/oversubscription) is sound;
  * the slot engine reproduces sequential ``DecodeEngine.generate``
    token-for-token under staggered insert/evict, for every cache family
    (dense, SWA, SSM, hybrid) — with exactly ONE decode compilation;
  * its step works on the pools in place: the pools are donated, no
    dense copy of the cache is made, and a step writes one row per live
    slot into each pool and nothing else;
  * the async server delivers the same bit-identical outputs to many
    submitting threads at once;
  * page placement flows through the partition solver.
"""
import dataclasses
import re
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.paged import paged_gather, paged_scatter_token
from repro.models import init_params, split
from repro.serve import (ContinuousServer, DecodeEngine, PagedKVCache,
                         ServeConfig, SlotEngine, solve_page_placement)
from repro.serve.slots import ResultTokens


def setup_arch(arch):
    cfg = get_config(arch).reduced()
    params, _ = split(init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def make_prompts(cfg, reqs, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (s,)).astype(np.int32)
            for s, _ in reqs]


# ---------------------------------------------------------------------------
# paged gather/scatter kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layer", [0, 2])
def test_paged_gather_reads_one_layer(layer):
    """A layer's view is its rows of each mapped page, in table order."""
    rng = np.random.default_rng(1)
    pool = rng.standard_normal((9, 3, 8, 16)).astype(np.float32)
    table = rng.integers(0, 9, (3, 4)).astype(np.int32)
    want = pool[:, layer][table].reshape(3, 32, 16)
    got = paged_gather(jnp.asarray(pool), jnp.asarray(table),
                       jnp.int32(layer))
    np.testing.assert_array_equal(np.asarray(got), want)


def test_paged_scatter_token_writes_every_layers_row():
    pool = jnp.zeros((4, 3, 8, 16))
    vals = jnp.arange(1, 4, dtype=jnp.float32)[None, :, None] * jnp.ones(
        (2, 3, 16))
    out = np.asarray(paged_scatter_token(pool, jnp.array([1, 3]),
                                         jnp.array([0, 7]), vals))
    for layer in range(3):
        assert (out[1, layer, 0] == layer + 1).all()
        assert (out[3, layer, 7] == layer + 1).all()
    assert out.sum() == 2 * 16 * (1 + 2 + 3)  # nothing else written


def test_paged_scatter_token_writes_one_row():
    pool = jnp.zeros((4, 8, 16))
    vals = jnp.ones((2, 16))
    out = paged_scatter_token(pool, jnp.array([1, 3]), jnp.array([0, 7]),
                              vals)
    out = np.asarray(out)
    assert out[1, 0].sum() == 16 and out[3, 7].sum() == 16
    assert out.sum() == 32  # nothing else written


# ---------------------------------------------------------------------------
# page pool accounting
# ---------------------------------------------------------------------------

def _tiny_cache(capacity=4, page_size=8, seq=32, total_pages=None):
    template = {
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
        "self": {
            "k": jax.ShapeDtypeStruct((2, capacity, seq, 16), jnp.float32),
            "v": jax.ShapeDtypeStruct((2, capacity, seq, 16), jnp.float32)},
    }
    return PagedKVCache(template, capacity=capacity, page_size=page_size,
                        total_pages=total_pages)


def test_page_pool_alloc_free_roundtrip():
    cache = _tiny_cache(total_pages=8)     # 4 slots x 4 pages/slot max
    assert cache.free_pages == 8
    assert cache.alloc(0, 9)               # 9 positions -> 2 pages
    assert cache.free_pages == 6
    assert (cache.table[0] != cache.layout.scratch_page).sum() == 2
    cache.free(0)
    assert cache.free_pages == 8
    assert (cache.table[0] == cache.layout.scratch_page).all()


def test_page_pool_oversubscription_refused():
    cache = _tiny_cache(total_pages=5)
    assert cache.alloc(0, 32)              # 4 pages
    assert not cache.alloc(1, 32)          # would need 4, only 1 left
    assert cache.alloc(1, 8)               # 1 page still fits
    assert cache.free_pages == 0
    assert not cache.can_alloc(1)
    cache.free(0)
    assert cache.can_alloc(32)


def test_page_pool_double_alloc_refused():
    cache = _tiny_cache()
    assert cache.alloc(0, 8)
    assert not cache.alloc(0, 8)           # slot already holds pages


def test_shared_pool_long_and_short():
    """Long + short sequences draw from one pool: two full-context slots
    would not fit, but one long + two short do."""
    cache = _tiny_cache(total_pages=6)
    assert cache.alloc(0, 32)              # 4 pages (long)
    assert not cache.alloc(1, 32)
    assert cache.alloc(1, 8)               # 1 page (short)
    assert cache.alloc(2, 8)
    assert cache.free_pages == 0


# ---------------------------------------------------------------------------
# slot engine: bit-exact continuous decode
# ---------------------------------------------------------------------------

PARITY_ARCHS = ["granite-8b", "h2o-danube-1.8b", "mamba2-370m", "zamba2-1.2b"]
REQS = [(8, 6), (12, 4), (5, 8), (9, 3), (11, 6)]


def drive_continuous(eng, prompts, reqs):
    """Queue -> insert/step/evict until every request finished; returns
    per-request token lists."""
    got = {}
    queue = list(range(len(reqs)))
    resident, left = {}, {}
    while queue or resident:
        while queue and eng.free_slots():
            i = queue[0]
            res = eng.insert(prompts[i], max_new_tokens=reqs[i][1])
            if res is None:
                break
            queue.pop(0)
            slot, tok = res
            got[i] = [tok]
            if reqs[i][1] == 1:
                eng.evict(slot)
            else:
                resident[slot], left[slot] = i, reqs[i][1] - 1
        if not resident:
            continue
        r = eng.step()
        for slot, i in list(resident.items()):
            if not r.valid_at(slot):
                continue
            got[i].append(r.token_at(slot))
            left[slot] -= 1
            if left[slot] == 0:
                eng.evict(slot)
                del resident[slot], left[slot]
    return [np.asarray(got[i], np.int32) for i in range(len(reqs))]


@pytest.mark.parametrize("arch", PARITY_ARCHS)
def test_slot_engine_bit_parity(arch):
    cfg, params = setup_arch(arch)
    base = DecodeEngine(params, cfg)
    eng = SlotEngine(params, cfg, capacity=3, max_context=32, page_size=8)
    prompts = make_prompts(cfg, REQS)
    want = [base.generate(p[None], max_new_tokens=t, cache_len=32)[0][0]
            for p, (_, t) in zip(prompts, REQS)]
    got = drive_continuous(eng, prompts, REQS)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    # the continuous-batching contract: insert/evict never recompiled
    assert eng.decode_compiles == 1


def test_slot_engine_no_recompile_across_churn():
    cfg, params = setup_arch("granite-8b")
    eng = SlotEngine(params, cfg, capacity=2, max_context=16, page_size=8)
    p = np.arange(5, dtype=np.int32) % cfg.vocab
    for _ in range(3):                     # churn: insert/step/evict cycles
        slot, _ = eng.insert(p, max_new_tokens=2)
        eng.step()
        eng.evict(slot)
    assert eng.decode_compiles == 1
    assert eng.prefill_compiles == 1       # one prompt length -> one entry


#: tiny configs whose whole cache view cannot share an element count with
#: a per-layer tensor (L * kv differs from the attention's Hq * dh)
STEP_CONFIGS = {"dense": ("granite-8b", dict(n_layers=3)),
                "hybrid": ("zamba2-1.2b", dict(n_layers=6))}


def _shapes(text):
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in re.finditer(r"\b(?:bf16|f16|f32|s32|u32|pred)\[([\d,]*)\]",
                                 text)]


@pytest.mark.parametrize("kind", sorted(STEP_CONFIGS))
def test_slot_step_in_place_on_pools(kind):
    """The compiled step aliases every pool to an output, and no
    instruction holds the whole (stack, C, S, kv) view of a paged leaf or
    the stacked updated cache (the same element count)."""
    arch, over = STEP_CONFIGS[kind]
    cfg = dataclasses.replace(get_config(arch).reduced(), **over)
    params, _ = split(init_params(jax.random.PRNGKey(0), cfg))
    eng = SlotEngine(params, cfg, capacity=3, max_context=32, page_size=8)
    eng.insert(np.arange(12, dtype=np.int32) % cfg.vocab, max_new_tokens=4)
    eng.step()
    text = eng.step_hlo_text()
    lay = eng.cache.layout
    assert lay.paged

    header = text.splitlines()[0]
    aliased = {int(p) for p in re.findall(r"\}: \((\d+), \{\}", header)}
    params_sig = header.split("entry_computation_layout={(", 1)[1]
    params_sig = params_sig.split(")->", 1)[0]
    arg_shapes = _shapes(params_sig)
    for path, pool in eng.cache.pools.items():
        at = [i for i, s in enumerate(arg_shapes) if s == pool.shape]
        assert at and set(at) <= aliased, (path, pool.shape, header)

    for path, (stack, feat, _) in lay.paged:
        whole = stack * lay.capacity * lay.seq_len * feat
        big = [s for s in _shapes(text) if int(np.prod(s)) == whole]
        assert not big, (path, big)


def test_slot_step_writes_one_row_per_live_slot():
    """One step changes, in each pool, exactly the row each live slot
    writes (in every layer) and nothing else; an inactive slot writes
    only the scratch page."""
    cfg, params = setup_arch("granite-8b")
    eng = SlotEngine(params, cfg, capacity=3, max_context=32, page_size=8)
    lay = eng.cache.layout
    p = np.arange(11, dtype=np.int32) % cfg.vocab
    live = [eng.insert(p, max_new_tokens=6)[0],
            eng.insert(p[:7], max_new_tokens=6)[0]]
    assert eng.free_slots()                   # one slot stays inactive
    eng.step()                                # positions 11 and 7 written
    for _ in range(2):
        before = {k: np.asarray(v).copy() for k, v in eng.cache.pools.items()}
        table = eng.cache.table.copy()
        pos = {s: eng.position(s) for s in live}
        eng.step()
        for path, old in before.items():
            new = np.asarray(eng.cache.pools[path])
            changed = {tuple(int(i) for i in at) for at in
                       zip(*np.nonzero((new != old).any(axis=-1)))}
            want = {(int(table[s, pos[s] // lay.page_size]), layer,
                     pos[s] % lay.page_size)
                    for s in live for layer in range(old.shape[1])}
            assert want <= changed, (path, want, changed)
            assert {at[0] for at in changed - want} <= {lay.scratch_page}
        assert {s: eng.position(s) for s in live} == {
            s: q + 1 for s, q in pos.items()}


def test_slot_engine_rejects_oversized_request():
    cfg, params = setup_arch("granite-8b")
    eng = SlotEngine(params, cfg, capacity=2, max_context=16, page_size=8)
    with pytest.raises(ValueError, match="max_context"):
        eng.insert(np.zeros((10,), np.int32), max_new_tokens=10)


def test_slot_engine_pool_exhaustion_returns_none():
    cfg, params = setup_arch("granite-8b")
    eng = SlotEngine(params, cfg, capacity=4, max_context=32, page_size=8,
                     total_pages=4)       # one full-length slot's worth
    p = np.arange(8, dtype=np.int32) % cfg.vocab
    assert eng.insert(p, max_new_tokens=24) is not None   # takes all 4
    assert eng.insert(p, max_new_tokens=8) is None        # pool exhausted
    eng.evict(0)
    assert eng.insert(p, max_new_tokens=8) is not None    # pages recycled


def test_result_tokens_packing():
    data = np.array([[7, 1, 12], [0, 0, 0]], np.int32)
    r = ResultTokens(data)
    assert r.token_at(0) == 7 and r.valid_at(0) and r.length_at(0) == 12
    assert not r.valid_at(1)


# ---------------------------------------------------------------------------
# async server
# ---------------------------------------------------------------------------

def test_server_multithreaded_submit_bit_parity():
    cfg, params = setup_arch("granite-8b")
    base = DecodeEngine(params, cfg)
    reqs = [(8, 6), (12, 4), (5, 8), (9, 3), (11, 6), (6, 5)]
    prompts = make_prompts(cfg, reqs)
    want = [base.generate(p[None], max_new_tokens=t, cache_len=32)[0][0]
            for p, (_, t) in zip(prompts, reqs)]

    eng = SlotEngine(params, cfg, capacity=3, max_context=32, page_size=8)
    futures = [None] * len(reqs)
    with ContinuousServer(eng, prefill_per_step=2) as server:
        def client(lo, hi):
            for i in range(lo, hi):
                futures[i] = server.submit(prompts[i],
                                           max_new_tokens=reqs[i][1])
        threads = [threading.Thread(target=client, args=(0, 3)),
                   threading.Thread(target=client, args=(3, 6))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        server.drain(timeout=300)
    for fut, w in zip(futures, want):
        np.testing.assert_array_equal(fut.result(timeout=5), w)
    assert eng.decode_compiles == 1
    assert server.stats["prefills"] == len(reqs)
    assert server.stats["evictions"] == len(reqs)


def test_server_eos_stops_request():
    """A request whose first decoded token is EOS finishes immediately
    with that single token (the slot never enters the decode batch)."""
    cfg, params = setup_arch("granite-8b")
    prompt = np.arange(7, dtype=np.int32) % cfg.vocab
    # learn what greedy emits first, then declare that token to be EOS
    probe = SlotEngine(params, cfg, capacity=2, max_context=16, page_size=8)
    _, first = probe.insert(prompt, max_new_tokens=4)

    eng = SlotEngine(params, cfg, capacity=2, max_context=16, page_size=8,
                     serve_cfg=ServeConfig(eos_id=int(first)))
    with ContinuousServer(eng) as server:
        fut = server.submit(prompt, max_new_tokens=4)
        out = fut.result(timeout=300)
    assert out.tolist() == [int(first)]
    assert not eng.live_slots()            # slot was evicted on EOS


def test_server_rejects_oversized_request_via_future():
    cfg, params = setup_arch("granite-8b")
    eng = SlotEngine(params, cfg, capacity=2, max_context=16, page_size=8)
    with ContinuousServer(eng) as server:
        fut = server.submit(np.zeros((12,), np.int32), max_new_tokens=12)
        with pytest.raises(ValueError, match="max_context"):
            fut.result(timeout=300)


# ---------------------------------------------------------------------------
# mesh placement of the page pools
# ---------------------------------------------------------------------------

def test_solve_page_placement_through_partition_solver():
    cfg, params = setup_arch("granite-8b")
    eng = SlotEngine(params, cfg, capacity=4, max_context=32, page_size=8)
    sol, spec = solve_page_placement(cfg, eng.cache.layout)
    assert isinstance(sol.strategy, str) and sol.strategy
    # pages shard over the batch-carrying mesh axis; page/feature axes
    # stay whole
    assert spec[0] in ("x", "y")
    assert len(spec) == 3 and spec[1] is None and spec[2] is None
