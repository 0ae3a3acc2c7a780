"""Algebra graphs (PR 8): IR validation, planning, fusion, execution.

Covers the graph tentpole's contract surface:

* IR construction catches bad wiring (cycles, shape mismatches, unknown
  edges) at build time,
* a single-node graph degenerates bit-exactly to ``generate(alg)`` and
  shares its compile-cache entry,
* the attention+MLP chain is bit-identical to the explicit-schedule
  oracle with strictly fewer HBM bytes than the unfused pricing,
* non-fusable edges (B-side operand, dtype change) fall back to an HBM
  materialization with the cost charged,
* a diamond DAG executes its shared producer exactly once,
* the tuning cache never replays a standalone variant for a fused-group
  or epilogue'd lowering (the ``_cache_key`` regression).
"""
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.compile import pipeline
from repro.core.algebra import get_algebra
from repro.core.costmodel import GraphCostReport
from repro.core import dse
from repro.graph import AlgebraGraph, GraphNode, plan_graph
from repro.graph import executor as graph_executor
from repro.kernels import fused_chain
from repro.models import chains
from repro.tune import cache as tune_cache


#: Relative tolerance (of max|oracle|) for comparing an fp32 execution
#: path with another fp32 path or the fp64 oracle.  Merged, sequential
#: and XLA paths sum the same products in different orders; each dot
#: here has at most 64 terms, so their rounding stays near
#: k * 2^-24 ~ 4e-6 of the output scale.  A bf16 stage anywhere on the
#: path would sit near 2^-8 ~ 4e-3, 400x above this bound.
FP32_RTOL = 1e-5


def assert_fp32_close(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    err = float(np.abs(got - want).max())
    assert err <= FP32_RTOL * float(np.abs(want).max()), err


def small_gemm(m=16, n=16, k=16):
    return get_algebra("gemm", m=m, n=n, k=k)


def single_node_graph():
    return AlgebraGraph(
        nodes=(GraphNode(name="mm", inputs=("A", "B"), output="C",
                         algebra=small_gemm()),),
        inputs=("A", "B"), output="C")


def chain_graph():
    """gemm -> gelu -> gemm, all fusable (the quickstart shape)."""
    return AlgebraGraph(
        nodes=(
            GraphNode(name="g1", inputs=("x", "W1"), output="h_raw",
                      algebra=small_gemm()),
            GraphNode(name="act", inputs=("h_raw",), output="h",
                      op="gelu"),
            GraphNode(name="g2", inputs=("h", "W2"), output="y",
                      algebra=small_gemm()),
        ),
        inputs=("x", "W1", "W2"), output="y")


# ---------------------------------------------------------------------------
# IR validation
# ---------------------------------------------------------------------------

class TestIR:
    def test_cycle_rejected(self):
        with pytest.raises(ValueError, match="cycle"):
            AlgebraGraph(
                nodes=(GraphNode(name="a", inputs=("y",), output="x",
                                 op="relu"),
                       GraphNode(name="b", inputs=("x",), output="y",
                                 op="relu")),
                inputs=(), output="y")

    def test_shape_mismatch_rejected(self):
        with pytest.raises(ValueError, match="shape mismatch"):
            AlgebraGraph(
                nodes=(GraphNode(name="g1", inputs=("x", "W"), output="h",
                                 algebra=small_gemm(16, 32, 16)),
                       GraphNode(name="g2", inputs=("h", "V"), output="y",
                                 algebra=small_gemm(16, 16, 16))),
                inputs=("x", "W", "V"), output="y")

    def test_unknown_edge_rejected(self):
        with pytest.raises(ValueError, match="unknown edge"):
            AlgebraGraph(
                nodes=(GraphNode(name="g", inputs=("x", "nope"),
                                 output="y", algebra=small_gemm()),),
                inputs=("x",), output="y")

    def test_duplicate_producer_rejected(self):
        with pytest.raises(ValueError, match="produced by both"):
            AlgebraGraph(
                nodes=(GraphNode(name="a", inputs=("x",), output="y",
                                 op="relu"),
                       GraphNode(name="b", inputs=("x",), output="y",
                                 op="tanh")),
                inputs=("x",), output="y")

    def test_epilogue_arity(self):
        with pytest.raises(ValueError, match="input edge"):
            GraphNode(name="b", inputs=("x",), output="y", op="bias")

    def test_reference_matches_manual(self):
        g = chain_graph()
        ops = g.random_operands(0)
        h = ops["x"].astype(np.float64) @ ops["W1"].T.astype(np.float64)
        from repro.kernels.epilogue import apply_epilogue_np
        want = apply_epilogue_np(h, ("gelu",)) @ ops["W2"].T
        got = g.reference(ops)
        np.testing.assert_allclose(got, want, atol=1e-9)


# ---------------------------------------------------------------------------
# Degeneration: one node == generate(alg)
# ---------------------------------------------------------------------------

class TestSingleNode:
    def test_bit_exact_and_cache_shared(self):
        g = single_node_graph()
        acc_g = repro.generate(g)
        acc_a = repro.generate(small_gemm())
        # the unconstrained node lowers with no fused_group/epilogue and
        # therefore shares the standalone compile-cache entry
        assert acc_g.kernels["mm"] is acc_a.kernel
        ops = g.random_operands(0)
        got = np.asarray(acc_g(ops))
        want = np.asarray(acc_a({"A": ops["A"], "B": ops["B"]}))
        assert (got == want).all()

    def test_cost_report_shape(self):
        rep = repro.generate(single_node_graph()).cost_report()
        assert isinstance(rep, GraphCostReport)
        assert rep.fused_edges == ()
        assert rep.hbm_bytes == rep.hbm_bytes_unfused  # nothing to fuse
        assert rep.cycles > 0


# ---------------------------------------------------------------------------
# Fusion: chain parity + honest byte accounting
# ---------------------------------------------------------------------------

class TestFusedChains:
    def test_gelu_chain_fuses_and_validates(self):
        g = chain_graph()
        acc = repro.generate(g)
        p = acc.plan.nodes["g1"]
        assert p.epilogue == ("gelu",) and p.epilogue_fused
        rep = acc.cost_report()
        assert len(rep.fused_edges) == 1
        assert rep.hbm_bytes < rep.hbm_bytes_unfused
        acc.validate(seed=0)

    def test_attention_mlp_bit_parity(self):
        g = chains.attention_mlp_graph(lq=32, lkv=32, d=32, dv=32, f=64)
        acc = repro.generate(g)
        ops = g.random_operands(1)
        got = np.asarray(acc(ops))
        want = np.asarray(chains.attention_mlp_oracle(
            {k: v for k, v in ops.items()}))
        assert got.shape == want.shape
        assert (got == want).all(), (
            f"max err {np.abs(got - want).max():.3e}")

    def test_attention_mlp_fewer_hbm_bytes(self):
        g = chains.attention_mlp_graph(lq=32, lkv=32, d=32, dv=32, f=64)
        rep = repro.generate(g).cost_report()
        assert len(rep.fused_edges) == 3     # probs, attn, mlp_h
        assert rep.hbm_bytes < rep.hbm_bytes_unfused
        assert rep.saved_hbm_bytes > 0
        assert rep.hbm_ratio > 1.3
        # the softmax/gelu epilogues are folded into the gemm kernels
        plan = repro.generate(g).plan
        assert (plan.nodes["scores"].epilogue ==
            (chains._scale_op(32), "softmax"))
        assert plan.nodes["mlp_up"].epilogue == ("bias", "gelu")

    def test_search_graph_returns_plan(self):
        g = chain_graph()
        plan = dse.search_graph(g, search=2)
        assert set(plan.nodes) == {"g1", "g2"}
        rep = plan.cost_report()
        assert rep.cycles > 0 and rep.hbm_bytes <= rep.hbm_bytes_unfused


# ---------------------------------------------------------------------------
# Non-fusable edges fall back to materialization, cost charged
# ---------------------------------------------------------------------------

class TestMaterialization:
    def b_side_graph(self):
        """g2 consumes g1's output as its *B* operand: the edge arrives
        in B's (n, k) storage layout, so the merged DAG kernel reads the
        producer's scratch transposed — no materialized transpose."""
        return AlgebraGraph(
            nodes=(
                GraphNode(name="g1", inputs=("x", "W1"), output="h",
                          algebra=small_gemm()),
                GraphNode(name="g2", inputs=("y2", "h"), output="z",
                          algebra=small_gemm()),
            ),
            inputs=("x", "W1", "y2"), output="z")

    def test_b_side_edge_fuses_on_rhs(self):
        g = self.b_side_graph()
        acc = repro.generate(g)
        edge = next(e for e in acc.plan.edges if e.producer == "g1")
        assert edge.fused and edge.side == "rhs"
        rep = acc.cost_report()
        assert "g1->g2:h" in rep.fused_edges
        # no "stores transposed" fallback anywhere, nothing charged for h
        assert not any("transposed" in why
                       for _, why in rep.materialized_edges)
        assert rep.edge_bytes.get("h", 0.0) == 0.0
        (grp,) = acc.plan.groups
        assert grp.kind == "dag" and grp.eligible
        assert list(acc.group_kernels) == [grp.name]
        acc.validate(seed=0)
        # bit-identical to sequential dispatch of the same plan
        ops = g.random_operands(0)
        seq = graph_executor.build(g, interpret=True, merge=False)
        np.testing.assert_array_equal(np.asarray(acc(ops)),
                                      np.asarray(seq(ops)))

    def test_dtype_change_blocks_fusion(self):
        g = AlgebraGraph(
            nodes=(
                GraphNode(name="g1", inputs=("x", "W1"), output="h",
                          algebra=small_gemm()),
                GraphNode(name="g2", inputs=("h", "W2"), output="y",
                          algebra=small_gemm(), dtype="bfloat16"),
            ),
            inputs=("x", "W1", "W2"), output="y")
        plan = plan_graph(g)
        edge = next(e for e in plan.edges if e.producer == "g1")
        assert not edge.fused and "dtype" in edge.reason
        rep = plan.cost_report()
        assert rep.fused_edges == ()

    def test_fanout_blocks_epilogue_folding(self):
        # h_raw has two consumers: the epilogue cannot fold into g1
        g = AlgebraGraph(
            nodes=(
                GraphNode(name="g1", inputs=("x", "W1"), output="h_raw",
                          algebra=small_gemm()),
                GraphNode(name="act", inputs=("h_raw",), output="h",
                          op="relu"),
                GraphNode(name="g2", inputs=("h", "W2"), output="y1",
                          algebra=small_gemm()),
                GraphNode(name="g3", inputs=("h_raw", "W3"), output="y2",
                          algebra=small_gemm()),
                GraphNode(name="last", inputs=("y1", "y2"), output="z",
                          algebra=small_gemm()),
            ),
            inputs=("x", "W1", "W2", "W3"), output="z")
        acc = repro.generate(g)
        assert acc.plan.nodes["g1"].epilogue == ()
        # the standalone relu node pays its round trip in the pricing
        assert acc.cost_report().edge_bytes["h"] > 0
        acc.validate(seed=1)


# ---------------------------------------------------------------------------
# Diamond DAG: shared producer executes once
# ---------------------------------------------------------------------------

class TestDiamond:
    def diamond(self):
        return AlgebraGraph(
            nodes=(
                GraphNode(name="p", inputs=("x", "W"), output="c",
                          algebra=small_gemm()),
                GraphNode(name="q1", inputs=("c", "W1"), output="o1",
                          algebra=small_gemm()),
                GraphNode(name="q2", inputs=("c", "W2"), output="o2",
                          algebra=small_gemm()),
                GraphNode(name="r", inputs=("o1", "o2"), output="z",
                          algebra=small_gemm()),
            ),
            inputs=("x", "W", "W1", "W2"), output="z")

    def test_producer_runs_once(self, monkeypatch):
        # merge=False: the PR 8 sequential path — one dispatch per node
        g = self.diamond()
        acc = graph_executor.build(g, interpret=True, merge=False)
        calls = []
        orig = pipeline.CompiledKernel.__call__

        def counting(self, operands):
            calls.append(self.algebra.name)
            return orig(self, operands)

        monkeypatch.setattr(pipeline.CompiledKernel, "__call__", counting)
        ops = g.random_operands(0)
        got = np.asarray(acc(ops))
        assert len(calls) == 4        # p, q1, q2, r — p not re-computed
        np.testing.assert_allclose(
            got, g.reference(ops).astype(np.float64), atol=1e-3)

    def test_producer_runs_once_merged(self, monkeypatch):
        # default path: the whole diamond merges into ONE dag megakernel
        # (q2->r lands on r's rhs; the shared c strip feeds q1 AND q2
        # from scratch) — zero per-node dispatches, one pallas_call
        g = self.diamond()
        acc = repro.generate(g)
        assert list(acc.group_kernels) == ["mg:p+q1+q2+r"]
        assert acc.plan.groups[0].kind == "dag"
        calls, group_calls = [], []
        orig = pipeline.CompiledKernel.__call__
        gorig = pipeline.CompiledGroupKernel.__call__

        def counting(self, operands):
            calls.append(self.algebra.name)
            return orig(self, operands)

        def gcounting(self, lhs, rhss=(), biases=()):
            group_calls.append(self.group)
            return gorig(self, lhs, rhss, biases)

        monkeypatch.setattr(pipeline.CompiledKernel, "__call__", counting)
        monkeypatch.setattr(pipeline.CompiledGroupKernel, "__call__",
                            gcounting)
        ops = g.random_operands(0)
        got = np.asarray(acc(ops))
        assert calls == []            # everything ran inside the group
        # one megakernel dispatch (its .group label may name another
        # graph's structurally-identical chain — entries are shared)
        assert len(group_calls) == 1
        np.testing.assert_allclose(
            got, g.reference(ops).astype(np.float64), atol=1e-3)

    def test_fanout_edge_priced_per_consumer(self):
        rep = plan_graph(self.diamond()).cost_report()
        # every diamond edge fuses (c feeds both consumers from the
        # merged group's scratch); the model can only save bytes
        assert rep.hbm_bytes <= rep.hbm_bytes_unfused


# ---------------------------------------------------------------------------
# Tuning-cache keys: fused-group / epilogue never alias standalone
# ---------------------------------------------------------------------------

class TestTuneCacheKeys:
    def test_fused_group_not_served_standalone_variant(self):
        alg = small_gemm()
        df = pipeline.default_dataflow(alg)
        base = pipeline._cache_key(alg, df, pipeline.ArrayConfig(),
                                   "float32", True, "pallas")
        # (8, 16, 16) is chip-legal for fp32 at 16^3, so lower() runs
        # the stored blocks unchanged
        tune_cache.store_variant(tune_cache.key_of(base),
                                 blocks=(8, 16, 16), grid_order="mnk",
                                 accum="scratch")
        pipeline.cache_clear()
        plain = pipeline.lower(alg, df, interpret=True)
        assert plain.source == "tuned" and plain.blocks == (8, 16, 16)
        fused = pipeline.lower(alg, df, interpret=True,
                               fused_group="g:test")
        assert fused.source == "analytical" and fused.blocks != (8, 16, 16)
        epi = pipeline.lower(alg, df, interpret=True, epilogue=("relu",))
        assert epi.source == "analytical"


# ---------------------------------------------------------------------------
# Merged-kernel execution (ISSUE 9): one pallas_call per fused chain
# ---------------------------------------------------------------------------

def group_operands(group, ops):
    """The group's external operands picked out of a graph operand dict."""
    return (ops[group.lhs_edge],
            [ops[e] for e in group.rhs_edges],
            [ops[e] for e in group.bias_edges if e is not None])


class TestMergedKernel:
    def test_merged_single_pallas_call(self, monkeypatch):
        # the acceptance chain: gemm·gelu·gemm runs as ONE megakernel —
        # zero per-node dispatches — and is bit-exact vs the sequential
        # path (bm == m: identical dot + epilogue sequence)
        g = chain_graph()
        acc = repro.generate(g)
        assert list(acc.group_kernels) == ["mg:g1+g2"]
        seq = graph_executor.build(g, interpret=True, merge=False)
        ops = g.random_operands(0)
        want_seq = np.asarray(seq(ops))

        calls, group_calls = [], []
        orig = pipeline.CompiledKernel.__call__
        gorig = pipeline.CompiledGroupKernel.__call__
        monkeypatch.setattr(
            pipeline.CompiledKernel, "__call__",
            lambda self, operands: calls.append(self.algebra.name)
            or orig(self, operands))
        monkeypatch.setattr(
            pipeline.CompiledGroupKernel, "__call__",
            lambda self, lhs, rhss, biases=():
            group_calls.append(self.group) or gorig(self, lhs, rhss, biases))
        got = np.asarray(acc(ops))
        assert calls == []                 # nothing dispatched per-node
        assert len(group_calls) == 1       # the whole chain: ONE pallas_call
        np.testing.assert_array_equal(got, want_seq)      # bit-exact
        np.testing.assert_allclose(
            got, g.reference(ops).astype(np.float64), atol=1e-3)

    def test_merged_attention_mlp_parity(self):
        # the scores->softmax->attend pair + MLP merges into one chain,
        # still matching the numpy graph oracle and the sequential path
        g = chains.attention_mlp_graph(lq=32, lkv=32, d=32, dv=32, f=64)
        acc = repro.generate(g)
        assert list(acc.group_kernels) == ["mg:scores+attend+mlp_up+mlp_down"]
        gk = acc.group_kernels["mg:scores+attend+mlp_up+mlp_down"]
        assert gk.bm == gk.m              # whole-tensor degenerate phase
        seq = graph_executor.build(g, interpret=True, merge=False)
        ops = g.random_operands(0)
        got = np.asarray(acc(ops))
        assert_fp32_close(got, g.reference(ops))
        assert_fp32_close(got, seq(ops))
        acc.validate()

    def test_merged_nondivisible_m_blocks(self):
        # m=24 against bm in {7, 16}: the pad-to-multiple + slice path,
        # on both stage interleaves
        g = AlgebraGraph(
            nodes=(
                GraphNode(name="g1", inputs=("x", "W1"), output="h_raw",
                          algebra=small_gemm(m=24, n=32, k=16)),
                GraphNode(name="act", inputs=("h_raw",), output="h",
                          op="gelu"),
                GraphNode(name="g2", inputs=("h", "W2"), output="y",
                          algebra=small_gemm(m=24, n=16, k=32)),
            ),
            inputs=("x", "W1", "W2"), output="y")
        plan = plan_graph(g)
        grp = next(x for x in plan.groups if x.eligible)
        ops = g.random_operands(0)
        want = np.asarray(g.reference(ops), np.float64)
        bound = 1e-3 + 1e-5 * np.abs(want).max()
        lhs, rhss, biases = group_operands(grp, ops)
        for bm in (7, 16):
            for il in fused_chain.FUSED_INTERLEAVES:
                gk = pipeline.lower_group(plan, grp, interpret=True,
                                          bm=bm, interleave=il)
                got = np.asarray(gk(lhs, rhss, biases), np.float64)
                assert got.shape == want.shape
                assert np.abs(got - want).max() <= bound, (bm, il)

    def test_merged_bf16_chain(self):
        # validate=False: the per-node lower-time oracle check uses an
        # fp32 atol; the bf16-tolerance oracle comparison happens below
        g = chain_graph()
        acc = graph_executor.build(g, interpret=True, dtype=jnp.bfloat16,
                                   validate=False)
        assert list(acc.group_kernels) == ["mg:g1+g2"]
        seq = graph_executor.build(g, interpret=True, dtype=jnp.bfloat16,
                                   merge=False, validate=False)
        ops = g.random_operands(0)
        got = np.asarray(acc(ops), np.float64)
        # same per-stage math (fp32 dot, fp32 epilogue, bf16 cast between
        # stages) in the same order: bit-equal to sequential dispatch
        np.testing.assert_array_equal(got, np.asarray(seq(ops), np.float64))
        want = np.asarray(g.reference(ops), np.float64)
        scale = np.abs(want).max() + 1e-30
        assert np.abs(got - want).max() / scale <= 2e-2

    def test_merged_vmem_overflow_falls_back(self):
        # a budget too small for the intermediate strip: the planner
        # keeps the group as documentation (eligible=False) and the
        # executor stays sequential — still matching the oracle
        from repro.core.tiling import ArrayConfig
        g = chain_graph()
        cfg = ArrayConfig(vmem_budget_bytes=2048)
        plan = plan_graph(g, cfg=cfg)
        assert plan.groups and not plan.groups[0].eligible
        assert "VMEM" in plan.groups[0].reason
        acc = graph_executor.build(g, plan=plan, interpret=True, cfg=cfg)
        assert not acc.group_kernels
        acc.validate()

    def test_merged_sequential_verdict_respected(self):
        # a persisted merged=False verdict (sequential measured faster)
        # makes lower_group decline and build() keep per-node dispatch
        g = chain_graph()
        plan = plan_graph(g)
        grp = next(x for x in plan.groups if x.eligible)
        digest = tune_cache.key_of(
            pipeline._group_cache_key(plan, grp, True, "pallas"))
        tune_cache.store_group(digest, merged=False)
        assert pipeline.lower_group(plan, grp, interpret=True) is None
        acc = graph_executor.build(g, plan=plan, interpret=True)
        assert not acc.group_kernels
        acc.validate()

    def test_merged_tune_group_verdict_cached(self):
        from repro.tune import tuner
        g = chain_graph()
        plan = plan_graph(g)
        grp = next(x for x in plan.groups if x.eligible)
        res = tuner.tune_group(plan, grp, interpret=True,
                               repeats=1, warmup=0)
        assert not res.cache_hit and res.trials
        assert all(t.ok for t in res.trials)
        res2 = tuner.tune_group(plan, grp, interpret=True)
        assert res2.cache_hit and res2.merged == res.merged
        # and build(tune=...) consumes the same verdict without measuring
        acc = graph_executor.build(g, plan=plan, interpret=True, tune=8)
        assert acc.group_tuning[grp.name].cache_hit
        assert bool(acc.group_kernels) == res.merged
        acc.validate()

    def test_merged_bias_key_collision_rejected(self):
        # regression (ISSUE 9 bugfix): a tensor name inside the reserved
        # "bias:" operand namespace would silently shadow the injected
        # bias vector; build() must reject it
        g = AlgebraGraph(
            nodes=(GraphNode(name="mm", inputs=("bias:x", "B"),
                             output="C", algebra=small_gemm()),),
            inputs=("bias:x", "B"), output="C")
        with pytest.raises(ValueError, match="bias:"):
            graph_executor.build(g, interpret=True)

    def test_merged_group_cache_key_separates_epilogues(self):
        # two chains identical but for one stage's folded epilogue must
        # not share a merged compile/tune cache entry
        g1 = chain_graph()
        g2 = AlgebraGraph(
            nodes=(
                GraphNode(name="g1", inputs=("x", "W1"), output="h_raw",
                          algebra=small_gemm()),
                GraphNode(name="act", inputs=("h_raw",), output="h",
                          op="relu"),
                GraphNode(name="g2", inputs=("h", "W2"), output="y",
                          algebra=small_gemm()),
            ),
            inputs=("x", "W1", "W2"), output="y")
        p1, p2 = plan_graph(g1), plan_graph(g2)
        k1 = pipeline._group_cache_key(p1, p1.groups[0], True, "pallas")
        k2 = pipeline._group_cache_key(p2, p2.groups[0], True, "pallas")
        assert k1 != k2

    def test_variant_stored_for_fused_group_is_found(self):
        alg = small_gemm()
        df = pipeline.default_dataflow(alg)
        key = pipeline._cache_key(alg, df, pipeline.ArrayConfig(),
                                  "float32", True, "pallas",
                                  fused_group="g:test")
        tune_cache.store_variant(tune_cache.key_of(key),
                                 blocks=(8, 16, 16), grid_order="kmn",
                                 accum="inplace")
        pipeline.cache_clear()
        fused = pipeline.lower(alg, df, interpret=True,
                               fused_group="g:test")
        assert fused.source == "tuned" and fused.blocks == (8, 16, 16)


# ---------------------------------------------------------------------------
# Multi-output taps (ISSUE 10): merged groups exporting intermediates
# ---------------------------------------------------------------------------

def tap_diamond_graph(m=16, n=16, k=16):
    """p -> t read by an in-group lhs consumer AND an out-of-group
    residual add: the merged group must export ``t`` as a tap."""
    return AlgebraGraph(
        nodes=(
            GraphNode(name="p", inputs=("x", "w0"), output="t",
                      algebra=get_algebra("gemm", m=m, n=n, k=k)),
            GraphNode(name="c1", inputs=("t", "w1"), output="y1",
                      algebra=get_algebra("gemm", m=m, n=n, k=n)),
            GraphNode(name="fin", inputs=("y1", "t"), output="out",
                      op="add"),
        ),
        inputs=("x", "w0", "w1"), output="out")


class TestTaps:
    def test_tap_exported_for_residual_add(self):
        g = tap_diamond_graph()
        plan = plan_graph(g)
        grp = next(x for x in plan.groups if x.eligible)
        assert grp.kind == "dag" and grp.taps == (("p", "t"),)
        rep = plan.cost_report()
        assert any(t.endswith(":t") for t in rep.tapped_edges)
        assert rep.tap_hbm_bytes > 0
        acc = graph_executor.build(g, plan=plan, interpret=True)
        assert acc.group_kernels[grp.name].n_tap == 1
        acc.validate()
        ops = g.random_operands(0)
        seq = graph_executor.build(g, interpret=True, merge=False)
        assert np.array_equal(np.asarray(acc(ops)), np.asarray(seq(ops)))

    def test_tap_nondivisible_m(self):
        # whole-tensor dag phases don't need m % pe == 0
        g = tap_diamond_graph(m=24, n=16, k=16)
        acc = graph_executor.build(g, interpret=True)
        assert any(gk.n_tap == 1 for gk in acc.group_kernels.values())
        acc.validate()
        ops = g.random_operands(1)
        seq = graph_executor.build(g, interpret=True, merge=False)
        assert np.array_equal(np.asarray(acc(ops)), np.asarray(seq(ops)))

    def test_tap_bf16_dtype(self):
        g = tap_diamond_graph()
        acc = graph_executor.build(g, interpret=True,
                                   dtype=jnp.bfloat16)
        assert any(gk.n_tap == 1 for gk in acc.group_kernels.values())
        ops = g.random_operands(2)
        out = np.asarray(acc(ops), dtype=np.float64)
        ref = g.reference(ops)
        assert np.max(np.abs(out - ref) / (np.abs(ref) + 1.0)) < 2e-2
        seq = graph_executor.build(g, interpret=True, merge=False,
                                   dtype=jnp.bfloat16)
        assert np.array_equal(np.asarray(acc(ops)), np.asarray(seq(ops)))

    def test_tap_consumer_on_other_mesh_partition_priced(self):
        # the tap's out-of-group consumer takes the edge on its rhs,
        # whose partition disagrees with the producer's out shards on a
        # (1, 2) mesh -> the read is priced as an inter-chip reshard
        # while the producer's group still merges and exports the tap
        g = AlgebraGraph(
            nodes=(
                GraphNode(name="p", inputs=("x", "w0"), output="t",
                          algebra=small_gemm()),
                GraphNode(name="c1", inputs=("t", "w1"), output="y1",
                          algebra=small_gemm()),
                GraphNode(name="c2", inputs=("u", "t"), output="y2",
                          algebra=small_gemm()),
                GraphNode(name="fin", inputs=("y1", "y2"),
                          output="out", op="add"),
            ),
            inputs=("x", "w0", "w1", "u"), output="out")
        plan = plan_graph(g, mesh=(1, 2))
        grp = next(x for x in plan.groups if x.eligible)
        assert grp.taps == (("p", "t"),)
        e = next(e for e in plan.edges
                 if e.edge == "t" and e.consumer == "c2")
        assert not e.fused and e.reshard_bytes > 0
        assert "partition mismatch" in e.reason
        rep = plan.cost_report()
        assert rep.reshard_bytes.get("t", 0.0) > 0
        assert any(t.endswith(":t") for t in rep.tapped_edges)
        acc = graph_executor.build(g, plan=plan, interpret=True)
        assert grp.name in acc.group_kernels
        acc.validate()


# ---------------------------------------------------------------------------
# Whole-model graphs (ISSUE 10): the dense-family layer end to end
# ---------------------------------------------------------------------------

class TestModelLayer:
    def _graph(self):
        from repro.graph import from_model
        return from_model.transformer_layer_graph(l=32, d=32, dv=32,
                                                  f=64)

    def test_model_layer_merges_attention_and_mlp(self):
        plan = plan_graph(self._graph())
        groups = [g for g in plan.groups if g.eligible]
        assert len(groups) == 1
        grp = groups[0]
        assert grp.kind == "dag" and len(grp.dag) == 8
        for member in ("scores", "attend", "up", "down"):
            assert member in grp.stages
        assert grp.taps == (("oproj", "r1"),)
        # the PR 9 fallback reasons must be gone for registry gemms
        for e in plan.edges:
            assert "batched" not in e.reason
            assert "transposed" not in e.reason
        # k and vt land on consumer rhs sides, q/p/a/r1/h on lhs
        sides = {(e.edge, e.consumer): e.side
                 for e in plan.edges if e.fused}
        assert sides[("k", "scores")] == "rhs"
        assert sides[("vt", "attend")] == "rhs"
        assert sides[("r1", "up")] == "lhs"

    def test_model_layer_bit_parity_vs_forward(self):
        from repro.graph import from_model
        g = self._graph()
        ops = g.random_operands(0)
        acc = graph_executor.build(g, interpret=True)
        assert len(acc.group_kernels) == 1
        out = np.asarray(acc(ops))
        assert_fp32_close(out, from_model.layer_oracle(ops))
        seq = graph_executor.build(g, interpret=True, merge=False)
        assert_fp32_close(out, seq(ops))
        acc.validate()

    def test_model_layer_wide_ffn_tile_agreement_converges(self):
        # h2o-danube-1.8b's d_model:d_ff = 2560:6912 at l=128 mixes
        # whole-tensor edges (q, k, r1) with an MLP edge too wide for
        # VMEM residency; agreement used to flip the shared nodes between
        # the two forever.  Same width ratio (1:27) at a small l (still
        # above the 16-row PE tile, which is what the narrowing meets),
        # with a residency limit (budget // 8) that keeps the same split.
        from repro.core.tiling import ArrayConfig
        from repro.graph import from_model
        g = from_model.transformer_layer_graph(l=32, d=16, f=432)
        cfg = ArrayConfig(vmem_budget_bytes=8 * 16384)
        assert 4 * 32 * 32 <= 16384 < 4 * 32 * 432
        plan = plan_graph(g, cfg=cfg)
        (grp,) = plan.groups
        assert not grp.eligible and "VMEM" in grp.reason
        for e in plan.edges:
            if e.fused:
                p, c = plan.nodes[e.producer], plan.nodes[e.consumer]
                if e.side == "lhs":
                    assert p.blocks[:2] == (c.blocks[0], c.blocks[2])
        acc = graph_executor.build(g, plan=plan, cfg=cfg, interpret=True)
        assert not acc.group_kernels
        assert f"sequential {grp.name}: {grp.reason}" in acc.describe()
        ops = g.random_operands(0)
        assert_fp32_close(acc(ops), from_model.layer_oracle(ops))

    def test_model_layer_from_config(self):
        from repro.configs.registry import get_config
        from repro.graph import from_model
        cfg = get_config("granite-8b").reduced()
        g = from_model.layer_graph_from_config(cfg, l=16)
        assert g.edge_shape("x") == (16, cfg.d_model)
        assert g.edge_shape("h_raw") == (16, cfg.d_ff)
        bad = get_config("mamba2-370m").reduced()
        with pytest.raises(ValueError, match="dense"):
            from_model.layer_graph_from_config(bad)

    def test_model_layer_batched_producer_fuses(self):
        # "producer lowering is batched" is gone: an effective-2D
        # batched_gemv producer merges into its gemm consumer
        g = AlgebraGraph(
            nodes=(
                GraphNode(name="bv", inputs=("A3", "v"), output="t",
                          algebra=get_algebra("batched_gemv",
                                              m=16, k=8, n=16)),
                GraphNode(name="c1", inputs=("t", "w"), output="y",
                          algebra=small_gemm()),
            ),
            inputs=("A3", "v", "w"), output="y")
        plan = plan_graph(g)
        e = next(e for e in plan.edges if e.edge == "t")
        assert e.fused
        grp = next(x for x in plan.groups if x.eligible)
        assert grp.kind == "dag"
        assert [s.kind for s in grp.dag] == ["batched", "dot"]
        acc = graph_executor.build(g, plan=plan, interpret=True)
        assert grp.name in acc.group_kernels
        acc.validate()
        ops = g.random_operands(3)
        seq = graph_executor.build(g, interpret=True, merge=False)
        assert np.array_equal(np.asarray(acc(ops)), np.asarray(seq(ops)))


# ---------------------------------------------------------------------------
# describe() surfaces fallback reasons (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

class TestDescribeReasons:
    def test_describe_surfaces_ineligible_reason(self):
        # a VMEM-starved config declines the merge; the group's reason
        # string must appear verbatim in the accelerator's describe()
        g = chain_graph()
        cfg = dse.ArrayConfig(vmem_budget_bytes=256)
        plan = plan_graph(g, cfg=cfg)
        grp = plan.groups[0]
        assert not grp.eligible and grp.reason
        acc = graph_executor.build(g, plan=plan, cfg=cfg,
                                   interpret=True)
        text = acc.describe()
        assert f"sequential {grp.name}: {grp.reason}" in text

    def test_describe_surfaces_merge_disabled(self):
        g = chain_graph()
        acc = graph_executor.build(g, interpret=True, merge=False)
        assert "merging disabled (merge=False)" in acc.describe()

    def test_describe_surfaces_merged_knobs(self):
        g = chain_graph()
        acc = graph_executor.build(g, interpret=True)
        grp = next(x for x in acc.plan.groups if x.eligible)
        assert f"merged {grp.name}" in acc.describe()


# ---------------------------------------------------------------------------
# Tune-cache groups-map robustness (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

def _write_group_entry(digest, entry):
    import json
    path = tune_cache.cache_path()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps({
        "version": tune_cache.SCHEMA_VERSION,
        "variants": {}, "choices": {},
        "groups": {digest: entry},
    }))


class TestTuneCacheGroups:
    def _digest(self, plan, grp):
        return tune_cache.key_of(
            pipeline._group_cache_key(plan, grp, True, "pallas"))

    def test_group_corrupt_entry_warns_and_falls_back(self):
        g = chain_graph()
        plan = plan_graph(g)
        grp = next(x for x in plan.groups if x.eligible)
        digest = self._digest(plan, grp)
        _write_group_entry(digest, {"version": tune_cache.SCHEMA_VERSION,
                                    "merged": "yes"})
        with pytest.warns(RuntimeWarning, match="corrupt or version"):
            assert tune_cache.lookup_group(digest) is None
        assert tune_cache.cache_info()["invalid"] >= 1
        # the lower path degrades to the analytical merge, not a crash
        with pytest.warns(RuntimeWarning, match="corrupt or version"):
            acc = graph_executor.build(g, plan=plan, interpret=True)
        assert grp.name in acc.group_kernels
        assert acc.group_kernels[grp.name].source == "analytical"
        acc.validate()

    def test_group_version_skew_warns_and_falls_back(self):
        g = chain_graph()
        plan = plan_graph(g)
        grp = next(x for x in plan.groups if x.eligible)
        digest = self._digest(plan, grp)
        _write_group_entry(digest,
                           {"version": tune_cache.SCHEMA_VERSION + 1,
                            "merged": True, "bm": 16,
                            "interleave": "chain"})
        with pytest.warns(RuntimeWarning, match="corrupt or version"):
            assert tune_cache.lookup_group(digest) is None
        with pytest.warns(RuntimeWarning, match="corrupt or version"):
            acc = graph_executor.build(g, plan=plan, interpret=True)
        assert acc.group_kernels[grp.name].source == "analytical"
        acc.validate()

    def test_group_unreadable_file_warns_and_falls_back(self):
        path = tune_cache.cache_path()
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text("{ this is not json")
        with pytest.warns(RuntimeWarning, match="unreadable"):
            assert tune_cache.lookup_group("deadbeef") is None
        assert tune_cache.cache_info()["corrupt"] >= 1
        g = chain_graph()
        acc = graph_executor.build(g, interpret=True)
        assert acc.group_kernels
        acc.validate()
