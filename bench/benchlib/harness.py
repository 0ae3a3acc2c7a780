"""One run of one cell: set-up, the measured window, the check, and the
result line.

``run_cell`` is the whole run after the look for a chip, which
``bench/run.py`` makes; tests call it directly on the CPU.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

from . import spec, trace as trace_mod
from .peaks import peaks_for

#: the traced part of a ``--trace 1`` window, at most
TRACE_S = 4.0

#: JAX events that mean a program was compiled or loaded
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_hits")


class CompileCounter:
    """Counts compilations and persistent-cache loads while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event: str, secs: float, **_) -> None:
        if self.on and event in _COMPILE_EVENTS:
            self.count += 1

    def _ev(self, event: str, **_) -> None:
        if self.on and event in _COMPILE_EVENTS:
            self.count += 1


class GcWatch:
    """Times the garbage collector's passes while ``on``.  A pass stops
    every Python thread, the server's and the sender's alike, so a long
    one shows in the tails; this says whether it came."""

    def __init__(self):
        self.on = False
        self.passes: List[Tuple[int, float]] = []    # (generation, s)
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.passes.append((info["generation"],
                                time.perf_counter() - self._t))

    def close(self) -> str:
        """Stop watching; a line for standard error."""
        gc.callbacks.remove(self._cb)
        full = [s for g, s in self.passes if g == 2]
        return (f"garbage collections inside the window: "
                f"{len(self.passes)}, {sum(s for _, s in self.passes):.3f} "
                f"s; full {len(full)}, longest "
                f"{max(full, default=0.0):.3f} s")


class Tracer:
    """The profiler over part of the window; the traced window's ends are
    read on the host clock (epoch ns) just after the trace starts and just
    before it stops."""

    def __init__(self, directory: str):
        self.directory = directory
        self.epoch: List[int] = []
        self.host: List[float] = []

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.epoch.append(time.time_ns())
        self.host.append(time.perf_counter())

    def stop(self) -> None:
        import jax

        self.epoch.append(time.time_ns())
        self.host.append(time.perf_counter())
        jax.profiler.stop_trace()

    def run(self, seconds: float) -> None:
        """Trace ``seconds`` from when the profiler is up."""
        self.start()
        time.sleep(seconds)
        self.stop()

    def load(self) -> trace_mod.Trace:
        return trace_mod.load(trace_mod.find_xplane(self.directory),
                              (self.epoch[0], self.epoch[1]))


@dataclasses.dataclass
class Run:
    """What the metric readers (``bench/metrics/*.py``) read."""

    cell: Any
    kind: str                       # "serve" | "graph"
    window_s: float
    peaks: Any                      # benchlib.peaks.Peaks, or None off-chip
    trace: Optional[trace_mod.Trace] = None
    trace_host: Tuple[float, float] = (0.0, 0.0)
    # serve
    records: List[Any] = dataclasses.field(default_factory=list)
    stats0: Dict[str, Any] = dataclasses.field(default_factory=dict)
    stats1: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # graph
    calls: int = 0
    traced_calls: int = 0
    # set-up and the window's own readings
    setup_s: float = 0.0
    memory_peak: int = 0
    compiles_in_window: int = 0

    @property
    def dims(self):
        """The configuration's shape, as its architecture counts it."""
        return self.cell.architecture().dims(self.cell.config)


def device_info(chips: int) -> Dict[str, Any]:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def memory_peak(chips: int) -> int:
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run_cell(root: str, name: str, seed: int, seconds: float, traced: bool,
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """One run; returns the result line as a dict (``checks`` last)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = spec.load_cell(root, name)
    info = device_info(cell.chips)
    try:
        peaks = peaks_for(info["kind"])
    except KeyError:
        if info["platform"] == "tpu":
            raise
        peaks = None                      # off the chip: tests only
    compiles = CompileCounter()
    gcw = GcWatch()
    kind = cell.traffic["kind"]
    trace_s = min(TRACE_S, seconds / 2)
    trace_at = (seconds - trace_s) / 2
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tdir:
        tracer = Tracer(tdir) if traced else None
        if kind == "serve":
            run, checks, attempted, failed = _serve(
                cell, seed, seconds, tracer, trace_at, trace_s, peaks,
                (compiles, gcw), t_start)
        elif kind == "graph":
            run, checks, attempted, failed = _graph(
                cell, seed, seconds, tracer, trace_at, trace_s, peaks,
                (compiles, gcw), t_start)
        else:
            raise ValueError(f"unknown traffic kind {kind!r}")
        if tracer is not None and tracer.epoch:
            run.trace = tracer.load()
            run.trace_host = (tracer.host[0], tracer.host[1])
    metrics: Dict[str, Dict[str, Any]] = {}
    wanted = cell.per_layer if traced else cell.end_to_end
    for m in wanted:
        value = m.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    info["memory_peak_bytes"] = run.memory_peak
    if traced and run.trace is not None:
        info["busy_s"] = trace_mod.busy_ns(run.trace) / 1e9
        info["window_s"] = run.trace.window_ns / 1e9
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    line: Dict[str, Any] = {"correct": correct, "attempted": attempted,
                            "failed": failed, "metrics": metrics,
                            "device": info}
    if traced and run.trace is not None:
        line["breakdown"] = {
            "device_ops": [list(x) for x in trace_mod.top_ops(run.trace)],
            "idle_gaps": [list(x) for x in trace_mod.idle_gaps(run.trace)]}
    line["checks"] = checks
    if traced and run.trace is not None:
        for prog, (n, ns) in sorted(trace_mod.program_totals(
                run.trace).items(), key=lambda kv: -kv[1][1]):
            print(f"traced program {prog}: {n} runs, {ns / 1e6:.3f} ms",
                  file=sys.stderr)
    print(f"compiles or cache loads inside the window: "
          f"{run.compiles_in_window}", file=sys.stderr)
    print(gcw.close(), file=sys.stderr)
    return line


def _watch(watches, on: bool) -> None:
    for w in watches:
        w.on = on


def _check(value: float, limit: float) -> Dict[str, float]:
    return {"value": value, "limit": limit}


def _serve(cell, seed, seconds, tracer, trace_at, trace_s, peaks, watches,
           t_start):
    from . import serve

    engine, server, params, requests = serve.setup(cell, seed, seconds)
    setup_s = time.perf_counter() - t_start
    _watch(watches, True)
    records, t0, t1, s0, s1 = serve.window(
        server, requests, seconds, tracer, trace_at, trace_s)
    _watch(watches, False)
    backlog = cell.traffic["arrival"] == "backlog"
    records = serve.collect(records, t1, backlog)
    peak = memory_peak(cell.chips)
    run = Run(cell=cell, kind="serve", window_s=t1 - t0, peaks=peaks,
              records=records, stats0=s0, stats1=s1)
    run.setup_s, run.memory_peak = setup_s, peak
    run.compiles_in_window = watches[0].count
    late = max((r.submitted - r.due for r in records), default=0.0)
    print(f"requests {len(records)}, generator at most {late * 1e3:.1f} ms "
          f"late", file=sys.stderr)
    ttft = sorted((r.first - r.due) * 1e3 for r in records
                  if r.first is not None)
    steps = s1["steps"] - s0["steps"]
    if ttft and steps:
        print(f"ttft ms: median {ttft[len(ttft) // 2]:.1f}, five longest "
              f"{[round(x, 1) for x in ttft[-5:]]}; server loop "
              f"{(t1 - t0) / steps * 1e3:.2f} ms over {steps} steps",
              file=sys.stderr)
    serve.free(engine, server)
    del engine, server
    gc.collect()
    checked = serve.sample(records, int(cell.traffic["check_requests"]), seed)
    gap, _, tokens = serve.logit_gaps(cell, params, checked)
    unserved = sum(1 for r in records if not r.served)
    for r in [r for r in records if not r.served][:3]:
        print(f"unserved request: {r.error or 'wrong token count'}",
              file=sys.stderr)
    limits = cell.config["limits"]
    checks = {"unserved_requests": _check(unserved, 0),
              "nothing_checked": _check(0 if tokens else 1, 0),
              "logit_gap": _check(gap, limits["logit_gap"])}
    print(f"checked {len(checked)} requests, {tokens} served tokens",
          file=sys.stderr)
    return run, checks, len(records), unserved


def _graph(cell, seed, seconds, tracer, trace_at, trace_s, peaks, watches,
           t_start):
    from . import graph

    acc, weights, xs, tune_dir = graph.setup(cell, seed)
    setup_s = time.perf_counter() - t_start
    _watch(watches, True)
    calls, t0, t1, kept, traced = graph.window(
        acc, weights, xs, seconds, seed, tracer, trace_at, trace_s)
    _watch(watches, False)
    peak = memory_peak(cell.chips)
    run = Run(cell=cell, kind="graph", window_s=t1 - t0, peaks=peaks,
              calls=calls, traced_calls=traced)
    run.setup_s, run.memory_peak = setup_s, peak
    run.compiles_in_window = watches[0].count
    del acc
    tune_dir.cleanup()
    err, _ = graph.rel_errors(cell, weights, xs, kept)
    checks = {"layer_rel_err": _check(err, cell.config["limits"]
                                      ["layer_rel_err"])}
    print(f"calls {calls}, checked {len(kept)}", file=sys.stderr)
    return run, checks, calls, 0


def print_result(line: Dict[str, Any]) -> None:
    """Each compared number beside its limit as the last lines of stderr,
    then the result as the last line of stdout."""
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)

