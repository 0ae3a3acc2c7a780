"""The program's spans, counters and named scopes, and the reduction
that reads them (``bench/benchlib/spans.py``, the readers of
``host_ms_per_step``, ``queue_wait_p90_ms.chat`` and
``gen_call_host_ms``)."""
import dataclasses
import os

import _bench_path  # noqa: F401
import numpy as np
import pytest

from benchlib import decode_work, spans, spec
from benchlib import trace as trace_mod

METRICS = os.path.join(_bench_path.BENCH, "metrics")


def ev(name, a, b):
    return trace_mod.Event(name, a, b)


def reader(name):
    return spec.load_module(os.path.join(METRICS, name + ".py")).read


def serve_trace(host, ops=(), modules=(), window=(0, 1000)):
    dev = trace_mod.Device("/device:TPU:0", list(modules), list(ops))
    return trace_mod.Trace(window=window, devices=[dev], host=host)


# ---------------------------------------------------------------------------
# reducer
# ---------------------------------------------------------------------------

LOOP = [ev("$server.py:250 _run", 0, 1000),
        ev("serve.loop", 0, 100), ev("serve.admit", 2, 40),
        ev("serve.prefill", 5, 30), ev("serve.cache_insert", 30, 38),
        ev("serve.step", 40, 90), ev("serve.fetch", 50, 88),
        ev("serve.emit", 90, 98),
        ev("serve.loop", 100, 160), ev("serve.admit", 101, 102),
        ev("serve.step", 102, 150), ev("serve.fetch", 110, 148),
        ev("serve.emit", 150, 158),
        ev("serve.wait", 170, 400)]


def test_spans_and_their_self_time():
    t = serve_trace([LOOP, [ev("graph.call", 0, 10)]])
    loops = spans.spans(t, "serve.loop")
    assert [(e.start, e.end) for e in loops] == [(0, 100), (100, 160)]
    assert [e.name for e in spans.spans(t, "graph.")] == ["graph.call"]
    own = spans.self_ns(t, "serve.")
    assert own["serve.loop"] == (100 - 38 - 50 - 8) + (60 - 1 - 48 - 8)
    assert own["serve.admit"] == 38 - 25 - 8 + 1
    assert own["serve.step"] == (50 - 38) + (48 - 38)
    assert own["serve.fetch"] == 38 + 38
    assert own["serve.wait"] == 230
    late = dataclasses.replace(t, window=(100, 1000))
    assert spans.self_ns(late, "serve.loop") == {"serve.loop": 60}


def test_span_args_kept_by_load(tmp_path):
    """A recorded CPU trace keeps each span's arguments."""
    import jax

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("serve.prefill", rid=7, tokens=12):
        with jax.profiler.TraceAnnotation("serve.admit") as span:
            span.set_metadata(admitted=1, queued=3)
    jax.profiler.stop_trace()
    found = spans.load(trace_mod.find_xplane(str(tmp_path)))
    assert [(s.name, s.args) for s in found] == [
        ("serve.prefill", {"rid": 7, "tokens": 12}),
        ("serve.admit", {"admitted": 1, "queued": 3})]
    assert found[0].start <= found[1].start < found[1].end <= found[0].end


def test_idle_split_across_two_spans_and_no_span():
    ops = [ev("%fusion", 0, 10), ev("%fusion", 60, 100)]
    host = [[ev("serve.loop", 5, 40), ev("serve.fetch", 5, 20),
             ev("serve.emit", 25, 30)],
            [ev("$time sleep", 0, 100)]]
    t = serve_trace(host, ops=ops, window=(0, 100))
    # the gap 10..60: fetch 10..20, loop 20..25, emit 25..30, loop
    # 30..40, then no span 40..60
    got = dict(spans.idle_by_span(t))
    assert got == pytest.approx({"serve.loop": 15e-9, "serve.fetch": 10e-9,
                                 "serve.emit": 5e-9, "no span": 20e-9})
    assert list(dict(spans.idle_by_span(t, n=1))) == ["serve.loop",
                                                      "no span"]


def test_loop_split_and_host_work_per_loop():
    t = serve_trace([LOOP])
    split = spans.loop_split(t)
    assert split["serve.loop"] == pytest.approx(80e-6)
    assert split["serve.fetch"] == pytest.approx(38e-6)
    assert split["serve.prefill"] == pytest.approx(12.5e-6)
    # loop less fetch and prefill: (100 - 38 - 25) and (60 - 38)
    assert spans.host_ms_per_loop(t) == pytest.approx((37 + 22) / 2 / 1e6)
    late = dataclasses.replace(t, window=(50, 1000))
    assert spans.host_ms_per_loop(late) == pytest.approx(22e-6)
    assert spans.host_ms_per_loop(serve_trace([])) is None


HLO = """HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %sin.2 = f32[8]{0} sine(%param_0.2), metadata={op_name="jit(step)/while/body/closed_call/mlp/sin"}
  ROOT %add.4 = f32[8]{0} add(%sin.2, %sin.2), metadata={op_name="jit(step)/while/body/closed_call/attention/add"}
}

%region_0.2 (arg_tuple.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg_tuple.1 = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.5 = f32[8]{0} get-tuple-element(%arg_tuple.1), index=1
  %add_fusion = f32[8]{0} fusion(%get-tuple-element.5), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/closed_call/mlp/add"}
  %dot.3 = f32[8]{0} dot(%add_fusion, %add_fusion), metadata={op_name="jit(step)/while/body/closed_call/mlp/dot_general"}
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%wrapped_add, %dot.3)
}

ENTRY %main.5 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %gather.1 = f32[8]{0} gather(%x.1), metadata={op_name="jit(step)/cache_gather/gather"}
  %while.0 = (s32[], f32[8]{0}) while(%tuple), condition=%region_1.3, body=%region_0.2, metadata={op_name="jit(step)/while"}
  %copy.6 = f32[8]{0} copy(%gather.1)
  ROOT %scatter.2 = f32[8]{0} scatter(%copy.6), metadata={op_name="jit(step)/cache_scatter/scatter"}
}
"""


def test_hlo_scopes_read_every_computation_and_fusion_roots():
    got = spans.hlo_scopes(HLO)
    assert got == {"sin.2": "mlp", "add.4": "attention",
                   "add_fusion": "attention", "dot.3": "mlp",
                   "gather.1": "cache_gather", "scatter.2": "cache_scatter"}
    assert spans.hlo_scopes(HLO, ("mlp",))["add_fusion"] == "mlp"


def test_program_split_by_scope_per_run():
    mods = [ev("jit_step(1)", 0, 100), ev("jit__unknown(2)", 100, 150),
            ev("jit_step(1)", 200, 300)]
    ops = [ev("%gather.1 = f32[8] gather(...)", 0, 10),
           ev("%while.0 = (...) while(...)", 10, 90),
           ev("%add_fusion = f32[8] fusion(...)", 12, 50),
           ev("%dot.3 = f32[8] dot(...)", 50, 80),
           ev("%copy.6 = f32[8] copy(...)", 90, 95),
           ev("%fusion.9 = f32[8] fusion(...)", 100, 150),
           ev("%gather.1 = f32[8] gather(...)", 200, 220),
           ev("%copy.6 = f32[8] copy(...)", 220, 290)]
    t = serve_trace([], ops=ops, modules=mods, window=(0, 1000))
    split = spans.program_split(t, "jit_step", spans.hlo_scopes(HLO))
    assert (split.runs, split.total_ns) == (2, 200)
    assert split.by_scope == {"cache_gather": 30, "attention": 38, "mlp": 30}
    assert split.other_ops == {"while.0": 12, "copy.6": 75}
    assert spans.scope_ms(split, "cache_gather", "cache_scatter") == 15e-6
    assert spans.program_split(t, "jit_other", {}) is None


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FakeRun:
    kind: str
    trace: object = None
    records: list = dataclasses.field(default_factory=list)


def test_host_ms_per_step_reader():
    read = reader("host_ms_per_step")
    assert read(FakeRun("serve", serve_trace([LOOP]))) == pytest.approx(
        29.5e-6)
    assert read(FakeRun("serve", serve_trace([[]]))) is None   # no spans
    assert read(FakeRun("graph", serve_trace([LOOP]))) is None
    assert read(FakeRun("serve")) is None


def test_gen_call_host_ms_reader():
    read = reader("gen_call_host_ms")
    host = [[ev("graph.call", 0, 30), ev("graph.unit", 1, 10),
             ev("graph.call", 40, 50), ev("graph.call", 2000, 2100)]]
    assert read(FakeRun("graph", serve_trace(host))) == pytest.approx(
        20e-6)
    assert read(FakeRun("graph", serve_trace([[]]))) is None
    assert read(FakeRun("serve", serve_trace(host))) is None


def test_queue_wait_p90_reader():
    read = reader("queue_wait_p90_ms.chat")

    def rec(submitted, admitted):
        fut = type("F", (), {})()
        fut.request = type("R", (), {"submitted_at": submitted})()
        if admitted is not None:
            fut.admitted_at = admitted
        return type("Rec", (), {"future": fut})()

    recs = [rec(0.0, 0.001 * (i + 1)) for i in range(10)]
    assert read(FakeRun("serve", records=recs)) == pytest.approx(9.0)
    recs[3] = rec(0.0, None)        # never admitted: infinitely slow
    assert read(FakeRun("serve", records=recs)) == pytest.approx(10.0)
    assert read(FakeRun("serve", records=recs[:3] + recs[3:4])) is None
    assert read(FakeRun("serve")) is None


# ---------------------------------------------------------------------------
# the program on the CPU
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    import jax

    from repro.configs import get_config
    from repro.models import init_params, split

    cfg = get_config("granite-8b").reduced()
    params, _ = split(init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def test_server_spans_nest_and_counters_order(tiny, tmp_path):
    import jax

    from repro.serve import ContinuousServer, SlotEngine

    cfg, params = tiny
    eng = SlotEngine(params, cfg, capacity=2, max_context=32, page_size=8)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (8, 12, 8, 12, 8)]
    with ContinuousServer(eng) as server:
        server.submit(prompts[0], max_new_tokens=2).result(timeout=300)
        jax.profiler.start_trace(str(tmp_path))
        futs = [server.submit(p, max_new_tokens=4) for p in prompts]
        for f in futs:
            f.result(timeout=300)
        server.drain(timeout=60)
        jax.profiler.stop_trace()
    path = trace_mod.find_xplane(str(tmp_path))
    t = trace_mod.load(path)
    loops = spans.spans(t, "serve.loop")
    steps = spans.spans(t, "serve.step")
    fetches = spans.spans(t, "serve.fetch")
    assert steps and len(fetches) == len(steps)

    def inside(e, outer):
        return any(o.start <= e.start and e.end <= o.end for o in outer)

    assert all(inside(s, loops) for s in steps)
    assert all(inside(f, steps) for f in fetches)
    found = spans.load(path)
    prefills = [s.args["rid"] for s in found if s.name == "serve.prefill"]
    assert sorted(prefills) == sorted(f.request.rid for f in futs)
    assert all(s.args["live"] >= 1 for s in found if s.name == "serve.step")
    for f in futs:
        assert (f.request.submitted_at <= f.admitted_at
                <= f.first_token_at <= f.finished_at)
    assert futs[-1].admitted_at > futs[0].first_token_at   # two slots


def test_step_scopes_and_program_names(tiny):
    """The compiled step's ops carry the scopes the split reads, and the
    programs keep the names the outside readers match."""
    import jax
    import jax.numpy as jnp

    from repro.serve import SlotEngine

    cfg, params = tiny
    eng = SlotEngine(params, cfg, capacity=2, max_context=32, page_size=8)
    slot, _ = eng.insert(np.arange(8, dtype=np.int32), max_new_tokens=4)
    eng.step()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    text = eng.step_hlo_text()
    assert compiles == [] and eng.decode_compiles == 1
    found = set(spans.hlo_scopes(text).values())
    assert set(spans.DECODE_SCOPES) <= found

    prefill = spec.load_module(os.path.join(METRICS, "prefill_ms_per_ktok.py"))

    def module_name(lowered):
        return lowered.as_text().split("module @", 1)[1].split()[0]

    step_args = eng._step_args(eng._base_key)
    assert module_name(eng._step_fn.lower(*step_args)) == \
        decode_work.DECODE_PROGRAM
    tokens = jnp.zeros((1, 8), jnp.int32)
    assert module_name(eng._prefill.lower(
        params, tokens, frontend=None, max_len=32)) == \
        prefill.PREFILL_PROGRAM


def test_graph_call_spans(tmp_path):
    import jax

    import repro
    from repro.graph import from_model
    from repro.configs.base import ModelConfig

    mcfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=64,
                       n_heads=4, n_kv_heads=2, d_ff=128, vocab=256)
    graph = from_model.layer_graph_from_config(mcfg, l=16)
    acc = repro.generate(graph, validate=False, interpret=True)
    ops = graph.random_operands(0)
    acc(ops).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    acc(ops).block_until_ready()
    jax.profiler.stop_trace()
    found = spans.load(trace_mod.find_xplane(str(tmp_path)))
    calls = [s for s in found if s.name == "graph.call"]
    units = [s for s in found if s.name == "graph.unit"]
    assert len(calls) == 1 and units
    assert all(calls[0].start <= u.start and u.end <= calls[0].end
               for u in units)
    assert {u.args["kind"] for u in units} <= {"node", "group"}
    assert all(u.args["name"] for u in units)


def test_spans_report_splits_sum_to_the_program_time():
    """``bench/spans_report.py``'s reading of a traced serve run: the
    step split (scopes plus ``other``) sums to the program's time per
    run, and idle time is split by span."""
    report = spec.load_module(os.path.join(_bench_path.BENCH,
                                           "spans_report.py")).report
    mods = [ev("jit_step(1)", 0, 100), ev("jit_step(1)", 200, 300)]
    ops = [ev("%gather.1 = f32[8] gather(...)", 0, 10),
           ev("%copy.6 = f32[8] copy(...)", 20, 90),
           ev("%add_fusion = f32[8] fusion(...)", 200, 290)]
    t = serve_trace([LOOP], ops=ops, modules=mods)
    found = [spans.Span(e.name, e.start, e.end, {"rid": 1, "live": 2})
             for e in LOOP[1:]]
    rep = report({"trace": t, "spans": found, "serve": True, "hlo": HLO})
    split = rep["step_split_ms"]
    assert split["cache_gather"] == pytest.approx(5e-6)
    assert split["attention"] == pytest.approx(45e-6)
    assert sum(split.values()) == pytest.approx(rep["decode_step_ms"])
    assert rep["other_ops_ms"] == [("copy.6", 35e-6, "f32[8]{0} copy")]
    idle = dict(rep["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(rep["idle_s"])
    assert rep["prefills"] == 1 and rep["mean_live_slots"] == 2
    assert report({"trace": None}) == {}
