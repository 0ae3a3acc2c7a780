"""The program's own spans and named scopes in a traced window.

The program writes spans into the profiler's trace with
``jax.profiler.TraceAnnotation``: ``serve.*`` in the serving loop and
the slot engine, ``graph.*`` in the graph executor.  They are host
events on the thread that ran them, on the clock of the device planes
that ``trace.load`` reads, so a device gap can be laid against what the
program was doing.  Their arguments (a request's ``rid``, a step's
``live`` slots) are event stats, which ``trace.load`` drops; ``load``
here keeps them.

The decode step's ``jax.named_scope`` names are not in the trace: a TPU
op event carries the op's instruction text but no name stack.  They are
read from the compiled program instead (``SlotEngine.step_hlo_text()``),
whose ops carry ``metadata={op_name="jit(step)/.../attention/..."}``:
``hlo_scopes`` maps each op the trace prints to its scope.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import trace as trace_mod

#: span name prefixes the program writes
PROGRAM_PREFIXES = ("serve.", "graph.")
#: idle time under no program span
NO_SPAN = "no span"

#: the decode step's named scopes (``serve/slots.py``, ``models/``)
DECODE_SCOPES = ("cache_gather", "attention", "mlp", "head", "sample",
                 "cache_scatter")
#: spans inside one server loop, in the order the loop runs them
LOOP_SPANS = ("serve.admit", "serve.prefill", "serve.cache_insert",
              "serve.step", "serve.fetch", "serve.emit")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: int          # ns after the profile's start
    end: int
    args: Dict[str, Any] = dataclasses.field(default_factory=dict,
                                             compare=False)

    @property
    def dur(self) -> int:
        return self.end - self.start


def load(path: str, prefixes: Tuple[str, ...] = PROGRAM_PREFIXES
         ) -> List[Span]:
    """The program's spans in a trace file, with their arguments, sorted
    by start; times as ``trace.load`` gives them."""
    from jax.profiler import ProfileData

    out = [Span(ev.name, int(ev.start_ns), int(ev.end_ns), dict(ev.stats))
           for plane in ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith(prefixes)]
    return sorted(out, key=lambda s: (s.start, -s.end))


def spans(trace: trace_mod.Trace, prefix: str) -> List[trace_mod.Event]:
    """Host events named ``prefix...`` on every thread, by start."""
    return sorted((e for events in trace.host for e in events
                   if e.name.startswith(prefix)),
                  key=lambda e: (e.start, -e.end))


def in_window(trace: trace_mod.Trace, events: Iterable[trace_mod.Event]
              ) -> List[trace_mod.Event]:
    lo, hi = trace.window
    return [e for e in events if lo <= e.start < hi]


def self_ns(trace: trace_mod.Trace, prefix: str) -> Dict[str, int]:
    """Total self time of each span name, over the spans starting in the
    window: its duration less that of the ``prefix`` spans nested in it
    on its thread."""
    lo, hi = trace.window
    out: Dict[str, int] = {}
    for events in trace.host:
        mine = [e for e in events if e.name.startswith(prefix)]
        for e, own in trace_mod.self_times(mine):
            if lo <= e.start < hi:
                out[e.name] = out.get(e.name, 0) + own
    return out


def idle_by_span(trace: trace_mod.Trace, n: int = 10
                 ) -> List[Tuple[str, float]]:
    """Idle time of the first chip by the innermost program span that
    covers it, in seconds: the ``n`` names with the most, then
    ``NO_SPAN`` for idle time under none.  An idle interval that spans
    several program spans is split between them."""
    if not trace.devices:
        return []
    idle = trace_mod.gaps(trace.devices[0], trace.window)
    program = [e for p in PROGRAM_PREFIXES for e in spans(trace, p)
               if e.end > e.start]
    by_start = sorted(program, key=lambda e: e.start)
    by_end = sorted(program, key=lambda e: e.end)
    points = sorted({t for a, b in idle for t in (a, b)}
                    | {t for e in program for t in (e.start, e.end)})
    active: Dict[int, trace_mod.Event] = {}
    totals: Dict[str, int] = {}
    si = ei = gi = 0
    for t0, t1 in zip(points, points[1:]):
        while si < len(by_start) and by_start[si].start <= t0:
            active[id(by_start[si])] = by_start[si]
            si += 1
        while ei < len(by_end) and by_end[ei].end <= t0:
            active.pop(id(by_end[ei]), None)
            ei += 1
        while gi < len(idle) and idle[gi][1] <= t0:
            gi += 1
        if gi == len(idle) or not idle[gi][0] <= t0 < idle[gi][1]:
            continue
        inner = max(active.values(), key=lambda e: (e.start, -e.end),
                    default=None)
        name = NO_SPAN if inner is None else inner.name
        totals[name] = totals.get(name, 0) + (t1 - t0)
    named = sorted(((k, v) for k, v in totals.items() if k != NO_SPAN),
                   key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in named] + [
        (NO_SPAN, totals.get(NO_SPAN, 0) / 1e9)]


def _within(outer: List[trace_mod.Event], inner: List[trace_mod.Event]
            ) -> List[List[trace_mod.Event]]:
    """For each of ``outer`` (sorted by start, not overlapping), the
    ``inner`` events that start inside it."""
    starts = [o.start for o in outer]
    out: List[List[trace_mod.Event]] = [[] for _ in outer]
    for e in inner:
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.start < outer[i].end:
            out[i].append(e)
    return out


def host_ms_per_loop(trace: trace_mod.Trace) -> Optional[float]:
    """Mean over the ``serve.loop`` spans starting in the window of the
    loop's duration less its time under ``serve.fetch`` (waiting for
    the step's result) and ``serve.prefill`` (waiting for a prefill), in
    ms: the host's own work per loop, while no decode step is queued."""
    loops = in_window(trace, spans(trace, "serve.loop"))
    if not loops:
        return None
    waits = [e for e in spans(trace, "serve.")
             if e.name in ("serve.fetch", "serve.prefill")]
    inside = _within(loops, waits)
    host = [lp.dur - sum(e.dur for e in ws) for lp, ws in zip(loops, inside)]
    return sum(host) / len(host) / 1e6


def loop_split(trace: trace_mod.Trace) -> Dict[str, float]:
    """Mean ms per ``serve.loop`` (those starting in the window) spent
    under each span of ``LOOP_SPANS``, and the loop's own mean duration
    under ``serve.loop``.  Nested spans count in their own name and in
    their parent's (``serve.fetch`` is inside ``serve.step``)."""
    loops = in_window(trace, spans(trace, "serve.loop"))
    if not loops:
        return {}
    inner = [e for e in spans(trace, "serve.") if e.name in LOOP_SPANS]
    totals = {"serve.loop": sum(lp.dur for lp in loops)}
    for events in _within(loops, inner):
        for e in events:
            totals[e.name] = totals.get(e.name, 0) + e.dur
    return {k: v / len(loops) / 1e6 for k, v in totals.items()}


def mean_ms(trace: trace_mod.Trace, name: str) -> Optional[float]:
    """Mean duration of the spans ``name`` starting in the window, ms."""
    found = [e for e in in_window(trace, spans(trace, name))
             if e.name == name]
    if not found:
        return None
    return sum(e.dur for e in found) / len(found) / 1e6


# ---------------------------------------------------------------------------
# named scopes of a compiled program
# ---------------------------------------------------------------------------

_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+) = ")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([^\s,)}]+)")


def hlo_scopes(text: str, scopes: Iterable[str] = DECODE_SCOPES
               ) -> Dict[str, str]:
    """Op name (as the trace prints it, without ``%``) -> the innermost
    of ``scopes`` in the op's ``op_name`` metadata, for every op of every
    computation of an HLO module's text (a ``while`` body's ops are
    events of their own in the trace).  A fusion takes the scope of its
    fused computation's root op, else its own.  Ops under none of
    ``scopes`` are left out."""
    wanted = set(scopes)
    own: Dict[str, Optional[str]] = {}
    calls: Dict[str, str] = {}
    roots: Dict[str, str] = {}
    comp = None
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            h = _HEADER.match(line)
            if h is not None:
                comp = h.group(1)
            continue
        name = m.group(2)
        if m.group(1) and comp is not None:
            roots[comp] = name
        meta = _OP_NAME.search(line)
        parts = meta.group(1).split("/") if meta else []
        own[name] = next((p for p in reversed(parts) if p in wanted), None)
        c = _CALLS.search(line)
        if c is not None:
            calls[name] = c.group(1)

    def scope(name: str, depth: int = 0) -> Optional[str]:
        callee = calls.get(name)
        if callee in roots and depth < 8:
            found = scope(roots[callee], depth + 1)
            if found is not None:
                return found
        return own.get(name)

    out = {}
    for name in own:
        s = scope(name)
        if s is not None:
            out[name] = s
    return out


@dataclasses.dataclass(frozen=True)
class ProgramSplit:
    runs: int
    total_ns: int                   # device time of the runs
    by_scope: Dict[str, int]        # scope -> self ns of its ops
    other_ops: Dict[str, int]       # op -> self ns, ops under no scope


def program_split(trace: trace_mod.Trace, program: str,
                  scope_of: Dict[str, str]) -> Optional[ProgramSplit]:
    """The device time of the runs of ``program`` starting in the window
    on the first chip, split by the scope of each op's self time."""
    if not trace.devices:
        return None
    lo, hi = trace.window
    dev = trace.devices[0]
    runs = sorted((e for e in dev.modules
                   if trace_mod.program_name(e.name) == program
                   and lo <= e.start < hi), key=lambda e: e.start)
    if not runs:
        return None
    ops = [e for events in _within(runs, dev.ops) for e in events]
    by_scope: Dict[str, int] = {}
    other: Dict[str, int] = {}
    for e, own in trace_mod.self_times(ops):
        op = trace_mod.op_name(e.name).lstrip("%")
        s = scope_of.get(op)
        if s is None:
            other[op] = other.get(op, 0) + own
        else:
            by_scope[s] = by_scope.get(s, 0) + own
    return ProgramSplit(len(runs), sum(e.dur for e in runs), by_scope,
                        other)


def scope_ms(split: ProgramSplit, *scopes: str) -> float:
    """Self time of the ops under ``scopes``, ms per run."""
    return sum(split.by_scope.get(s, 0) for s in scopes) / split.runs / 1e6
