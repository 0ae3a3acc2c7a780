"""Cost model (paper Fig. 5/6 claims) and plan generation tests."""
import pytest

from repro.core import algebra, costmodel, dse, plan, stt
from repro.core.stt import DataflowClass as DC

MNK = ("m", "n", "k")
MODEL = costmodel.PaperCycleModel()


def df_of(alg, sel, kind):
    return stt.apply_stt(alg, sel, stt.stt_from_name(kind))


class TestFig5Claims:
    """Assert the paper's qualitative performance findings (§VI-A)."""

    def test_gemm_multicast_beats_systolic(self):
        # "the performance of multicast dataflows (MTM) is better than
        #  systolic dataflow (STS) because of smaller pipeline overhead"
        g = algebra.gemm(256, 256, 256)
        mmt = MODEL.evaluate(g, df_of(g, MNK, "identity"))
        sts = MODEL.evaluate(g, df_of(g, MNK, "weight_stationary"))
        assert mmt.normalized_perf > sts.normalized_perf
        assert sts.fill_overhead_frac > 0 and mmt.fill_overhead_frac == 0

    def test_mttkrp_unicast_is_bandwidth_bound(self):
        # "unicast dataflows (e.g. IKL-UBBB) perform worse ... bandwidth
        #  becomes insufficient"
        mt = algebra.mttkrp(64, 64, 32, 32)
        ubbb = MODEL.evaluate(mt, df_of(mt, ("i", "k", "l"), "identity"))
        mmbt = MODEL.evaluate(mt, df_of(mt, ("i", "j", "k"), "identity"))
        assert ubbb.bw_stall_factor > 2.0
        assert ubbb.normalized_perf < 0.5 * mmbt.normalized_perf

    def test_batched_gemv_bandwidth_limited(self):
        bg = algebra.batched_gemv(64, 256, 256)
        r = MODEL.evaluate(bg, df_of(bg, MNK, "identity"))
        assert r.bw_stall_factor > 1.0      # A is unicast -> starved

    def test_conv_small_loop_bounds_idle_pes(self):
        # "XYP-SMM ... 1/16 idle PEs since the range of p is 3"
        cv = algebra.conv2d(64, 64, 16, 16, 3, 3)
        df = stt.apply_stt(cv, ("p", "x", "y"), stt.stt_from_name("identity"))
        r = MODEL.evaluate(cv, df)
        assert r.utilization == pytest.approx(15 / 16, abs=1e-9)

    def test_conv_resnet_layer5_lower_util(self):
        # x = y = 7 on layer5-like shapes -> worse utilization than layer2
        cv2 = algebra.conv2d(64, 64, 28, 28, 3, 3)
        cv5 = algebra.conv2d(512, 512, 7, 7, 3, 3)
        sel = ("x", "y", "c")
        r2 = MODEL.evaluate(cv2, stt.apply_stt(cv2, sel, stt.stt_from_name("identity")))
        r5 = MODEL.evaluate(cv5, stt.apply_stt(cv5, sel, stt.stt_from_name("identity")))
        assert r5.utilization < r2.utilization

    def test_conv_kcx_beats_xyp(self):
        # "selecting KCX iterations can deliver better performance because it
        #  becomes standard GEMM with large loop bounds"
        cv = algebra.conv2d(64, 64, 14, 14, 3, 3)
        kcx = MODEL.evaluate(cv, stt.apply_stt(
            cv, ("k", "c", "x"), stt.stt_from_name("identity")))
        xyp = MODEL.evaluate(cv, stt.apply_stt(
            cv, ("x", "y", "p"), stt.stt_from_name("identity")))
        assert kcx.normalized_perf > xyp.normalized_perf


class TestFig6Claims:
    def test_multicast_inputs_cost_more_power(self):
        # "dataflow with two multicast input (MMT, MMS) consumes more energy"
        g = algebra.gemm(256, 256, 256)
        mmt = MODEL.evaluate(g, df_of(g, MNK, "identity"))
        sst = MODEL.evaluate(g, df_of(g, MNK, "output_stationary"))
        assert mmt.power_mw > sst.power_mw

    def test_stationary_costs_area(self):
        # "dataflows with stationary tensor consume more area"
        g = algebra.gemm(256, 256, 256)
        sst = MODEL.evaluate(g, df_of(g, MNK, "output_stationary"))
        # a hypothetical all-streaming dataflow: MM + reduction output
        T = stt.stt_from_name("identity")
        # k->space, m->time gives C reduction, no stationary tensor
        df = stt.apply_stt(g, ("k", "n", "m"), T)
        r = MODEL.evaluate(g, df)
        assert any(t.cls is DC.REDUCTION for t in df.tensors)
        assert sst.area_units > r.area_units

    def test_power_range_calibration(self):
        # paper GEMM sweep spans roughly 35–63 mW (1.8x); require our sweep
        # to land in a comparable band
        g = algebra.gemm(256, 256, 256)
        sweep = [MODEL.evaluate(g, df) for df in
                 dse.enumerate_dataflows(g, selections=[MNK]).values()]
        # compare over efficient designs (perf >= 0.5), as inefficient
        # mappings idle the array and legitimately draw less power
        powers = sorted(r.power_mw for r in sweep if r.normalized_perf >= 0.5)
        assert 30 < powers[0] < powers[-1] < 80
        assert powers[-1] / powers[0] > 1.3   # meaningful spread


class TestDSE:
    def test_gemm_design_space_size(self):
        # paper reports 148 distinct GEMM dataflow points; our enumeration
        # universe is stated in dse.py — require a comparably rich space
        g = algebra.gemm(256, 256, 256)
        flows = dse.enumerate_dataflows(g)
        assert len(flows) >= 100
        classes = {t.cls for df in flows.values() for t in df.tensors}
        # the space must exercise every rank<=1 dataflow class
        assert {DC.STATIONARY, DC.SYSTOLIC, DC.MULTICAST,
                DC.REDUCTION}.issubset(classes)

    def test_depthwise_design_space(self):
        dw = algebra.depthwise_conv(64, 14, 14, 3, 3)
        sels = [("k", "x", "y"), ("k", "p", "x"), ("x", "y", "p")]
        flows = dse.enumerate_dataflows(dw, selections=sels)
        assert len(flows) >= 30   # paper: 33 points

    def test_pareto_front(self):
        g = algebra.gemm(256, 256, 256)
        reports = dse.sweep(g, selections=[MNK])
        front = dse.pareto_front(reports)
        assert 0 < len(front) < len(reports)


class TestPlans:
    def test_output_stationary_kernel_plan(self):
        g = algebra.gemm()
        p = plan.plan_for(df_of(g, MNK, "output_stationary"))
        assert p.kernel.template == "output_stationary"
        assert p.kernel.resident_tensor == "C"
        assert p.kernel.reduction_in_kernel

    def test_weight_stationary_kernel_plan(self):
        g = algebra.gemm()
        p = plan.plan_for(df_of(g, MNK, "weight_stationary"))
        assert p.kernel.template == "operand_stationary"
        assert p.kernel.resident_tensor == "B"

    def test_comm_plan_classes(self):
        g = algebra.gemm()
        # SST -> Cannon-like: two ppermute rings + sharded output
        p = plan.plan_for(df_of(g, MNK, "output_stationary"))
        kinds = {t.tensor: t.kind for t in p.comm.tensors}
        assert kinds == {"A": "ppermute_ring", "B": "ppermute_ring",
                         "C": "shard"}
        # MMT -> SUMMA: two all_gathers + sharded output
        p = plan.plan_for(df_of(g, MNK, "identity"))
        kinds = {t.tensor: t.kind for t in p.comm.tensors}
        assert kinds == {"A": "all_gather", "B": "all_gather", "C": "shard"}

    def test_paper_module_selection(self):
        # paper §V-A: "output stationary contains two modules (a) and one (d);
        #  weight stationary contains one (a), one (b) and one (c)"
        g = algebra.gemm()
        p = plan.plan_for(df_of(g, MNK, "output_stationary"))
        mods = " ".join(p.pe_modules)
        assert mods.count("a:systolic-in") == 2 and "d:stationary-out" in mods
        p = plan.plan_for(df_of(g, MNK, "weight_stationary"))
        mods = " ".join(p.pe_modules)
        assert ("a:systolic-in" in mods and "b:systolic-out" in mods
                and "c:stationary-in" in mods)

    def test_unicast_plan_streams(self):
        bg = algebra.batched_gemv()
        p = plan.plan_for(df_of(bg, MNK, "identity"))
        assert p.comm.by_tensor()["A"].kind == "stream"


class TestParetoFront:
    """Sort-based pareto_front (ISSUE 1 satellite): known front + oracle."""

    @staticmethod
    def _report(cycles, area, power, name="pt"):
        return costmodel.CostReport(
            dataflow_name=name, cycles=cycles, macs=0, peak_macs=0,
            normalized_perf=0.0, utilization=0.0, bw_stall_factor=1.0,
            fill_overhead_frac=0.0, traffic_bytes={},
            area_units=area, power_mw=power)

    def test_known_front(self):
        r = self._report
        pts = [
            r(1, 5, 5, "a"),   # front: best cycles
            r(1, 5, 5, "h"),   # exact duplicate of a: neither dominates
            r(2, 4, 6, "b"),   # front: beats c on area, loses on power
            r(2, 6, 4, "c"),   # front
            r(2, 4, 6, "d"),   # duplicate of b -> front
            r(3, 4, 6, "e"),   # dominated by b (same area/power, more cycles)
            r(3, 9, 9, "f"),   # dominated by everything
            r(2, 5, 5, "g"),   # dominated by a (equal area/power, cycles<)
        ]
        front = {p.dataflow_name for p in dse.pareto_front(pts)}
        assert front == {"a", "h", "b", "c", "d"}
        assert front == {p.dataflow_name
                         for p in dse.pareto_front_reference(pts)}

    def test_matches_reference_on_sweep(self):
        g = algebra.gemm(128, 128, 128)
        reports = dse.sweep(g, selections=[MNK])
        fast = dse.pareto_front(reports)
        slow = dse.pareto_front_reference(reports)
        assert {id(r) for r in fast} == {id(r) for r in slow}
        assert len(fast) >= 1


class TestEnumerationFastPath:
    """The cached enumeration must be indistinguishable from the original."""

    def test_gemm_matches_reference(self):
        g = algebra.gemm(64, 64, 64)
        fast = dse.enumerate_dataflows(g, selections=[MNK])
        slow = dse.enumerate_dataflows_reference(g, selections=[MNK])
        assert set(fast) == set(slow)
        for key in fast:
            assert fast[key].signature == slow[key].signature
            assert fast[key].T == slow[key].T     # same representative

    def test_rank3_selection_skipped_not_crashing(self):
        # conv2d with selection (c, p, q): the output C[k,y,x] has a rank-3
        # reuse subspace for every T -> the selection is unbuildable and
        # must be skipped silently by both paths
        cv = algebra.conv2d(4, 4, 4, 4, 2, 2)
        sel = [("c", "p", "q")]
        assert dse.enumerate_dataflows(cv, selections=sel) == {}
        assert dse.enumerate_dataflows_reference(cv, selections=sel) == {}


def test_peaks_are_keyed_by_device_kind():
    from repro.core import tpu

    v5e = tpu.spec_for("TPU v5 lite")
    assert v5e is tpu.V5E
    assert v5e.peak_flops_bf16 == 197e12 and v5e.hbm_bw == 819e9
    assert v5e.vmem_scoped_limit_bytes == 16 * 2 ** 20 < v5e.vmem_bytes


def test_unknown_device_kind_raises():
    from repro.core import tpu

    with pytest.raises(KeyError, match="no peaks recorded"):
        tpu.spec_for("cpu")
