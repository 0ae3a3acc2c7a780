"""Measured autotuning (ISSUE 6): harness, cache, tuner, calibration."""
import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.compile import pipeline
from repro.core import dse, linalg, stt as stt_mod
from repro.core.algebra import batched_gemv, gemm
from repro.core.costmodel import PaperCycleModel
from repro.core.tiling import ArrayConfig
from repro.kernels import ops
from repro.tune import cache, calibrate, report, tuner
from repro.tune.measure import Measurement, measure

#: fast interpret-mode tuning knobs shared by the e2e tests
FAST = dict(interpret=True, repeats=2, warmup=1, validate=False)


def small_gemm():
    return gemm(16, 16, 16)


# ---------------------------------------------------------------------------
# Shared measurement harness
# ---------------------------------------------------------------------------

class TestMeasure:
    def test_counts_and_blocks(self):
        calls = []

        def fn(x):
            calls.append(x)
            return jnp.asarray([1.0])

        m = measure(fn, 7, warmup=2, repeats=5)
        assert len(calls) == 7          # 2 warmup + 5 timed
        assert len(m.times_s) == 5
        assert m.warmup_s >= 0.0
        assert all(t >= 0.0 for t in m.times_s)

    def test_statistics(self):
        m = Measurement(times_s=(3.0, 1.0, 2.0), warmup_s=0.1)
        assert m.median_s == 2.0
        assert m.best_s == 1.0
        assert m.mean_s == pytest.approx(2.0)
        m2 = Measurement(times_s=(1.0, 2.0, 3.0, 4.0), warmup_s=0.0)
        assert m2.median_s == 2.5
        assert m2.cycles(320.0) == pytest.approx(2.5 * 320e6)

    def test_repeats_validation(self):
        with pytest.raises(ValueError):
            measure(lambda: None, repeats=0)


# ---------------------------------------------------------------------------
# On-disk tuning cache
# ---------------------------------------------------------------------------

class TestTuneCache:
    def test_roundtrip_and_persistence(self):
        key = cache.key_of(("some", "compile", "key", 1))
        assert cache.lookup_variant(key) is None
        cache.store_variant(key, blocks=(8, 16, 32), grid_order="kmn",
                            accum="inplace", measured_s=0.5, untuned_s=1.0)
        entry = cache.lookup_variant(key)
        assert entry["blocks"] == [8, 16, 32]
        assert entry["grid_order"] == "kmn"
        assert entry["measured_s"] == 0.5
        # survives a memo reset (simulates a fresh process)
        cache.cache_clear(counters_only=True)
        assert cache.lookup_variant(key)["blocks"] == [8, 16, 32]

    def test_key_stability(self):
        # sha256 over repr: deterministic across processes, unlike hash()
        import hashlib
        tup = ("alg", ("m", "n"), 3.5)
        assert cache.key_of(tup) == hashlib.sha256(
            repr(tup).encode()).hexdigest()
        assert cache.key_of(tup) == cache.key_of(("alg", ("m", "n"), 3.5))
        assert cache.key_of(tup) != cache.key_of(("alg", ("m", "n"), 3.6))

    def test_corrupt_file_warns_and_falls_back(self):
        key = cache.key_of(("k",))
        cache.store_variant(key, blocks=(1, 1, 1), grid_order="default",
                            accum="auto")
        cache.cache_path().write_text("{ not json !!!")
        cache.cache_clear(counters_only=True)
        with pytest.warns(RuntimeWarning, match="unreadable"):
            assert cache.lookup_variant(key) is None
        assert cache.cache_info()["corrupt"] >= 1
        # the lower() consult path degrades to analytical, not an error
        k = pipeline.lower(small_gemm(), interpret=True, validate=False)
        assert k.source == "analytical"

    def test_version_mismatch_drops_entries(self):
        key = cache.key_of(("k2",))
        cache.store_variant(key, blocks=(2, 2, 2), grid_order="default",
                            accum="auto")
        doc = json.loads(cache.cache_path().read_text())
        doc["version"] = 999
        cache.cache_path().write_text(json.dumps(doc))
        cache.cache_clear(counters_only=True)
        assert cache.lookup_variant(key) is None
        assert cache.cache_info()["invalid"] >= 1

    def test_invalid_entry_rejected(self):
        key = cache.key_of(("k3",))
        cache.store_variant(key, blocks=(2, 2, 2), grid_order="default",
                            accum="auto")
        doc = json.loads(cache.cache_path().read_text())
        doc["variants"][key]["blocks"] = [0, -1]     # malformed
        cache.cache_path().write_text(json.dumps(doc))
        cache.cache_clear(counters_only=True)
        assert cache.lookup_variant(key) is None
        assert cache.cache_info()["invalid"] >= 1

    def test_counters(self):
        cache.cache_clear()
        key = cache.key_of(("k4",))
        assert cache.lookup_variant(key) is None
        cache.store_variant(key, blocks=(4, 4, 4), grid_order="default",
                            accum="auto")
        assert cache.lookup_variant(key) is not None
        info = cache.cache_info()
        assert info["hits"] == 1 and info["misses"] == 1
        assert info["stores"] == 1 and info["variants"] == 1

    def test_choice_roundtrip(self):
        key = cache.shape_key_for(small_gemm(), ArrayConfig(), jnp.float32,
                                  True, "pallas")
        variant = cache.store_variant(
            cache.key_of(("base",)), blocks=(16, 16, 16),
            grid_order="default", accum="auto")
        cache.store_choice(key, selected=("m", "n", "k"),
                           T=[[1, 0, 0], [0, 1, 0], [0, 0, 1]],
                           variant=variant, dataflow_name="MNK-X")
        got = cache.lookup_choice(key)
        assert got["selected"] == ["m", "n", "k"]
        assert got["variant"]["blocks"] == [16, 16, 16]


# ---------------------------------------------------------------------------
# Tuner end-to-end
# ---------------------------------------------------------------------------

class TestTuner:
    def test_tuned_never_slower_and_cache_hit(self):
        alg = small_gemm()
        res = tuner.tune(alg, search=1, **FAST)
        assert not res.cache_hit
        assert res.trials, "tuner must run trials on a cache miss"
        assert res.tuned_s <= res.untuned_s      # untuned is trial #0
        assert res.speedup >= 1.0
        assert res.kernel.source == "tuned"
        assert res.kernel.measured_s == res.tuned_s
        # second call: pure cache hit, no measurement
        res2 = tuner.tune(alg, search=1, **FAST)
        assert res2.cache_hit and res2.trials == ()
        assert res2.variant == res.variant
        assert res2.kernel.blocks == tuple(res.variant.blocks)

    def test_lower_consults_tuning_cache(self):
        alg = small_gemm()
        res = tuner.tune(alg, search=1, **FAST)
        pipeline.cache_clear()
        cache.cache_clear(counters_only=True)    # fresh memo, same file
        k = pipeline.lower(alg, res.dataflow, interpret=True,
                           validate=False)
        assert k.source == "tuned"
        assert k.blocks == tuple(res.variant.blocks)
        assert k.grid_order == res.variant.grid_order
        assert k.accum == res.variant.accum
        assert k.measured_s == pytest.approx(res.tuned_s)
        # tuned=False bypasses the consult
        k2 = pipeline.lower(alg, res.dataflow, interpret=True,
                            validate=False, tuned=False)
        assert k2.source == "analytical"

    def test_tuned_kernel_matches_oracle(self):
        alg = small_gemm()
        res = tuner.tune(alg, search=1, **FAST)
        assert res.kernel.validate() <= 1e-3

    def test_pinned_dataflow(self):
        alg = small_gemm()
        df = pipeline.default_dataflow(alg)
        res = tuner.tune(alg, df, force=True, **FAST)
        assert res.dataflow.signature == df.signature
        assert all(t.dataflow_name == df.name for t in res.trials)

    def test_measured_cycles_in_report(self):
        alg = small_gemm()
        res = tuner.tune(alg, search=1, **FAST)
        rep = res.kernel.cost_report()
        assert rep.measured_cycles == pytest.approx(
            res.tuned_s * ArrayConfig().freq_mhz * 1e6)

    def test_rank_measured_is_permutation(self):
        alg = batched_gemv(4, 16, 16)
        pairs = dse.search(alg, top_k=3)
        ranked = tuner.rank_measured(alg, pairs, **{
            k: v for k, v in FAST.items() if k != "validate"})
        assert len(ranked) == len(pairs)
        assert {id(df) for _, df, _ in ranked} == {id(df) for _, df in pairs}
        medians = [t for _, _, t in ranked]
        assert medians == sorted(medians)

    def test_generate_tune_front_door(self):
        import repro
        acc = repro.generate("gemm", bounds=dict(m=16, n=16, k=16),
                             tune=1, interpret=True, validate=False)
        assert acc.tune_result is not None
        assert not acc.tune_result.cache_hit
        assert "tuned:" in acc.describe()
        acc2 = repro.generate("gemm", bounds=dict(m=16, n=16, k=16),
                              tune=1, interpret=True, validate=False)
        assert acc2.tune_result.cache_hit
        with pytest.raises(ValueError):
            repro.generate("gemm", "output_stationary", tune=True)


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------

class TestCalibration:
    def test_fit_scales(self):
        cal = calibrate.fit([
            {"template": "os", "algebra": "a",
             "model_cycles": 100.0, "measured_cycles": 200.0},
            {"template": "os", "algebra": "b",
             "model_cycles": 100.0, "measured_cycles": 800.0},
        ])
        assert cal.scale_for("os", "a") == pytest.approx(2.0)
        assert cal.scale_for("os", "b") == pytest.approx(8.0)
        # unseen algebra: per-template geomean fallback
        assert cal.scale_for("os", "zz") == pytest.approx(4.0)
        assert cal.scale_for("unknown") == 1.0

    def test_bad_records_skipped_and_scales_positive(self):
        cal = calibrate.fit([
            {"template": "t", "algebra": "a",
             "model_cycles": 0.0, "measured_cycles": 5.0},
            {"template": "t", "algebra": "a",
             "model_cycles": -3.0, "measured_cycles": 5.0},
            {"template": "t", "algebra": "a", "model_cycles": float("nan"),
             "measured_cycles": 5.0},
            {"template": "t"},                     # missing fields
        ])
        assert not cal                             # nothing usable
        assert cal.scale_for("t", "a") == 1.0
        # extreme ratios clamp to a positive band — never zero/negative
        ext = calibrate.fit([{"template": "t", "algebra": "a",
                              "model_cycles": 1e30,
                              "measured_cycles": 1e-30}])
        assert ext.scale_for("t", "a") > 0.0

    def test_calibrated_model_positive_and_flagged(self):
        alg = small_gemm()
        df = pipeline.default_dataflow(alg)
        cal = calibrate.Calibration(per_template={"output_stationary": 3.0})
        base = PaperCycleModel().evaluate(alg, df)
        rep = PaperCycleModel(calibration=cal).evaluate(alg, df)
        assert rep.calibrated and not base.calibrated
        assert rep.cycles == pytest.approx(3.0 * base.cycles)
        assert rep.cycles > 0
        # peak / normalized follow the calibrated cycles
        assert rep.normalized_perf == pytest.approx(
            rep.macs / rep.peak_macs)

    def test_calibration_requires_scale_for(self):
        with pytest.raises(TypeError):
            PaperCycleModel(calibration=object())

    def test_uniform_calibration_preserves_ranking(self):
        alg = batched_gemv(4, 16, 16)
        plain = dse.search(alg, top_k=0)
        templates = {p[0].dataflow_name for p in plain}  # noqa: F841
        cal = calibrate.Calibration(per_template={
            t: 2.5 for t in ("output_stationary", "operand_stationary",
                             "reduction_tree", "streaming")})
        scaled = dse.search(alg, top_k=0, calibration=cal)
        key = lambda p: (p[1].selected, p[1].signature)  # noqa: E731
        assert [key(p) for p in scaled] == [key(p) for p in plain]
        assert all(p[0].calibrated for p in scaled)

    def test_calibrated_search_is_permutation(self):
        alg = batched_gemv(4, 16, 16)
        plain = dse.search(alg, top_k=0)
        cal = calibrate.fit([
            {"template": "output_stationary", "algebra": alg.name,
             "model_cycles": 1.0, "measured_cycles": 250.0},
            {"template": "reduction_tree", "algebra": alg.name,
             "model_cycles": 1.0, "measured_cycles": 40.0},
        ])
        scaled = dse.search(alg, top_k=0, calibration=cal)
        key = lambda p: (p[1].selected, p[1].signature)  # noqa: E731
        assert sorted(map(key, scaled)) == sorted(map(key, plain))

    def test_record_persists_and_reloads(self):
        calibrate.record("output_stationary", "gemm", 1000.0, 250000.0)
        cal = calibrate.load()
        assert (cal.scale_for("output_stationary", "gemm") ==
            pytest.approx(250.0))
        # re-recording the same pair replaces, not dilutes
        calibrate.record("output_stationary", "gemm", 1000.0, 500000.0)
        assert calibrate.load().scale_for(
            "output_stationary", "gemm") == pytest.approx(500.0)

    def test_tune_records_calibration_within_2x(self):
        alg = small_gemm()
        res = tuner.tune(alg, search=1, **FAST)
        cal = calibrate.load()
        scale = cal.scale_for(res.kernel.template, alg.name)
        predicted = res.kernel.cost_report().cycles * scale
        measured = res.tuned_s * ArrayConfig().freq_mhz * 1e6
        assert 0.5 <= predicted / measured <= 2.0


# ---------------------------------------------------------------------------
# Kernel knobs (grid order / accumulation strategy)
# ---------------------------------------------------------------------------

class TestKnobs:
    @pytest.mark.parametrize("grid_order, accum", [
        ("default", "auto"), ("default", "inplace"),
        ("nmk", "auto"), ("nmk", "inplace"),
        # k-outer orders revisit the output block: inplace only
        ("kmn", "inplace"), ("knm", "inplace"),
    ])
    def test_os_variants_match(self, grid_order, accum):
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.integers(-4, 5, (32, 24)), jnp.float32)
        b = jnp.asarray(rng.integers(-4, 5, (24, 16)), jnp.float32)
        got = ops.stt_matmul(a, b, template="output_stationary",
                             bm=8, bn=8, bk=8, interpret=True,
                             grid_order=grid_order, accum=accum)
        np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                                   rtol=1e-5)

    def test_scratch_rejects_k_outer(self):
        a = jnp.zeros((8, 8), jnp.float32)
        with pytest.raises(ValueError, match="scratch"):
            ops.stt_matmul(a, a, template="output_stationary",
                           bm=4, bn=4, bk=4, interpret=True,
                           grid_order="kmn", accum="scratch")

    def test_rt_grid_orders_match(self):
        rng = np.random.default_rng(1)
        a = jnp.asarray(rng.integers(-4, 5, (16, 16)), jnp.float32)
        b = jnp.asarray(rng.integers(-4, 5, (16, 16)), jnp.float32)
        for order in ("default", "nm", "nmk"):
            got = ops.stt_matmul(a, b, template="reduction_tree",
                                 bm=8, bn=8, interpret=True,
                                 grid_order=order)
            np.testing.assert_allclose(np.asarray(got), np.asarray(a @ b),
                                       rtol=1e-5)

    def test_resolve_accum(self):
        assert ops.resolve_accum("auto", jnp.float32) == "scratch"
        assert ops.resolve_accum("auto", jnp.bfloat16) == "scratch"
        assert ops.resolve_accum("inplace", jnp.float32) == "inplace"
        with pytest.raises(ValueError):
            ops.resolve_accum("bogus", jnp.float32)

    def test_variant_key_distinguishes_knobs(self):
        alg = small_gemm()
        df = pipeline.default_dataflow(alg)
        k1 = pipeline.lower(alg, df, interpret=True, validate=False,
                            tuned=False)
        k2 = pipeline.lower(alg, df, interpret=True, validate=False,
                            grid_order="kmn", accum="inplace")
        assert k1 is not k2
        assert k1.grid_order == "default" and k2.grid_order == "kmn"
        # same explicit knobs share one cache entry
        k3 = pipeline.lower(alg, df, interpret=True, validate=False,
                            grid_order="kmn", accum="inplace")
        assert k3 is k2


# ---------------------------------------------------------------------------
# BENCH_tune.json schema
# ---------------------------------------------------------------------------

def _valid_doc():
    cell = report.cell_entry(
        cell="tune_gemm", algebra="gemm", dataflow="MNK-MMT",
        template="output_stationary",
        variant={"blocks": (64, 64, 64), "grid_order": "kmn",
                 "accum": "inplace"},
        model_cycles=1024.0, calibrated_cycles=170000.0,
        measured_cycles=171000.0, untuned_s=1e-3, tuned_s=5e-4,
        tune_cache_hit=False)
    return {
        "version": report.BENCH_SCHEMA_VERSION,
        "smoke": True, "interpret": True, "cells": [cell],
        "calibration": {
            "per_template": {"output_stationary": 170.0},
            "anchors": [{"template": "output_stationary",
                         "algebra": "gemm", "scale": 170.0}],
        },
    }


class TestBenchSchema:
    def test_valid_doc_passes(self):
        assert report.validate_bench(_valid_doc()) == []

    @pytest.mark.parametrize("mutate, frag", [
        (lambda d: d.update(version=99), "version"),
        (lambda d: d.pop("smoke"), "smoke"),
        (lambda d: d.update(cells=[]), "cells"),
        (lambda d: d["cells"][0].pop("speedup"), "speedup"),
        (lambda d: d["cells"][0]["variant"].update(blocks=[0, 1]),
         "blocks"),
        (lambda d: d["calibration"]["per_template"].update(x=-1.0),
         "per_template"),
        (lambda d: d["calibration"]["anchors"].append({"bad": 1}),
         "anchors"),
    ])
    def test_mutations_rejected(self, mutate, frag):
        doc = _valid_doc()
        mutate(doc)
        errors = report.validate_bench(doc)
        assert errors and any(frag in e for e in errors), errors

    def test_speedup_computed(self):
        cell = _valid_doc()["cells"][0]
        assert cell["speedup"] == pytest.approx(2.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_candidates_are_chip_legal(dtype):
    from repro.kernels import stt_gemm

    dims = (512, 6912, 2560)
    analytical = stt_gemm.legal_blocks((16, 16, 16), dims, dtype)
    cands = tuner.block_candidates(analytical, dims, dtype)
    assert cands[0] == analytical
    assert len(set(cands)) == len(cands)
    for c in cands:
        assert stt_gemm.legal_blocks(c, dims, dtype) == c
