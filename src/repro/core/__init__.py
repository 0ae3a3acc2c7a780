"""TensorLib core: Space-Time Transformation dataflow generation.

Public API:
    algebra.get_algebra / PAPER_ALGEBRAS  — Table II tensor algebras
    stt.apply_stt                          — STT matrix -> Dataflow
    stt.simulate                           — space-time functional simulator
    plan.plan_for                          — Dataflow -> kernel + collectives
    costmodel.PaperCycleModel              — paper Fig. 5/6 analytical model
    dse.enumerate_dataflows / sweep        — design-space exploration
    tpu.PEAKS / spec_for / RooflineTerms   — chip peaks by device_kind, roofline
"""
from . import algebra, costmodel, dse, linalg, plan, stt, tiling, tpu
from .algebra import PAPER_ALGEBRAS, Sparsity, TensorAlgebra, get_algebra
from .costmodel import ArrayConfig, CostReport, PaperCycleModel
from .plan import CommPlan, ExecutionPlan, KernelPlan, plan_for
from .stt import Dataflow, DataflowClass, InvalidSTT, apply_stt, simulate, stt_from_name
from .tpu import PEAKS, V5E, RooflineTerms, TpuSpec, spec_for

__all__ = [
    "algebra", "costmodel", "dse", "linalg", "plan", "stt", "tiling", "tpu",
    "PAPER_ALGEBRAS", "Sparsity", "TensorAlgebra", "get_algebra",
    "ArrayConfig", "CostReport", "PaperCycleModel",
    "CommPlan", "ExecutionPlan", "KernelPlan", "plan_for",
    "Dataflow", "DataflowClass", "InvalidSTT", "apply_stt", "simulate",
    "stt_from_name", "PEAKS", "V5E", "RooflineTerms", "TpuSpec", "spec_for",
]
