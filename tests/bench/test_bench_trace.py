"""The reduction from a profiler trace to device metrics."""
import os

import _bench_path  # noqa: F401
import pytest

from benchlib import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")


def ev(name, a, b):
    return trace.Event(name, a, b)


def test_union_merges_overlaps_and_nesting():
    assert trace.union([(5, 9), (0, 3), (2, 4), (6, 7), (10, 10)]) == [
        (0, 4), (5, 9)]


def test_busy_and_gaps_inside_window():
    dev = trace.Device("/device:TPU:0", [], [
        ev("%while", 10, 50), ev("%fusion.1", 12, 20), ev("%copy", 60, 70),
        ev("%late", 95, 130)])
    assert trace.busy(dev, (0, 100)) == [(10, 50), (60, 70), (95, 100)]
    assert trace.gaps(dev, (0, 100)) == [(0, 10), (50, 60), (70, 95)]
    t = trace.Trace(window=(0, 100), devices=[dev], host=[])
    assert trace.busy_ns(t) == 55


def test_self_time_subtracts_nested_ops():
    outer, inner = ev("%while.3 = ...", 0, 100), ev("%fusion = ...", 10, 40)
    own = {e.name: t for e, t in trace.self_times([inner, outer])}
    assert own == {"%while.3 = ...": 70, "%fusion = ...": 30}


def test_program_runs_by_name_without_fingerprint():
    dev = trace.Device("/device:TPU:0", [
        ev("jit_step(123)", 0, 10), ev("jit_prefill(9)", 12, 30),
        ev("jit_step(123)", 40, 52), ev("jit_step(123)", 200, 210)], [])
    t = trace.Trace(window=(0, 100), devices=[dev], host=[])
    runs = trace.program_runs(t, "jit_step")
    assert (runs.count, runs.total_ns, runs.first_start, runs.last_end) == (
        2, 22, 0, 52)
    assert trace.program_runs(t, "jit_other") is None
    assert trace.program_totals(t) == {"jit_step": (2, 22),
                                       "jit_prefill": (1, 18)}


def test_idle_gap_named_by_innermost_python_frame():
    dev = trace.Device("/device:TPU:0", [], [ev("%op", 0, 10),
                                              ev("%op", 40, 50)])
    host = [[ev("$server.py:218 _run", 0, 100),
             ev("$slots.py:173 insert", 15, 35)],
            [ev("ReadSyncFlag", 20, 30), ev("$time sleep", 0, 100)]]
    t = trace.Trace(window=(0, 50), devices=[dev], host=host)
    assert trace.idle_gaps(t) == [("$slots.py:173 insert", 30e-9)]


def test_recorded_tpu_trace():
    """A trace of three runs of a small jitted program, recorded on one
    TPU v5e."""
    if not os.path.exists(DATA):
        pytest.fail(f"missing {DATA}")
    t = trace.load(DATA)
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    assert t.window == (0, 289238335)
    assert trace.program_totals(t) == {"jit__lambda": (3, 8892)}
    runs = trace.program_runs(t, "jit__lambda")
    assert (runs.first_start, runs.last_end) == (40034970, 46805904)
    assert trace.busy_ns(t) == 8870
    assert len(trace.gaps(t.devices[0], t.window)) == 10
    top = trace.top_ops(t, 1)
    assert top[0][0] == "jit__lambda/%fusion"
    assert top[0][1] == pytest.approx(8822e-9)
    names = [n for n, _ in trace.idle_gaps(t, 2)]
    assert names == ["$profiler.py:213 stop_trace",
                     "$profiler.py:101 start_trace"]
