"""Reduce a JAX profiler trace (``*.xplane.pb``) to device metrics.

Layout of a TPU trace as JAX 0.9 writes it: one plane per chip,
``/device:TPU:<n>``, with the lines ``XLA Modules`` (one event per run
of a compiled program, named ``jit_<function>(<fingerprint>)``), ``XLA
Ops`` (one event per HLO op, nested: a ``while`` op spans its body's
ops) and ``Async XLA Ops`` (copies in flight, which overlap compute and
are not counted as busy); a ``/host:CPU`` plane with one line per host
thread; and plane stats ``profile_start_time`` / ``profile_stop_time``
in ns since the epoch.  Event times are ns after ``profile_start_time``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

_FINGERPRINT = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: int          # ns after the profile's start
    end: int

    @property
    def dur(self) -> int:
        return self.end - self.start


@dataclasses.dataclass
class Device:
    name: str
    modules: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Trace:
    """The events of one traced window, clipped to ``window``."""

    window: Interval
    devices: List[Device]
    host: List[List[Event]]           # one list per host thread

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def program_name(module_event_name: str) -> str:
    """``jit_step(7475...)`` -> ``jit_step``."""
    return _FINGERPRINT.sub("", module_event_name)


def op_name(op_event_name: str) -> str:
    """``%copy.118 = bf16[...] copy(...)`` -> ``%copy.118``."""
    return op_event_name.split(" = ", 1)[0].strip()


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"want one .xplane.pb under {trace_dir}, "
                                f"found {files}")
    return files[0]


def load(path: str, window_epoch_ns: Optional[Interval] = None) -> Trace:
    """Read a trace file.  ``window_epoch_ns``: the traced window as the
    host saw it (epoch ns just after tracing started, and just before it
    stopped); by default the profile's own start and stop."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    start = stop = None
    devices: List[Device] = []
    host: List[List[Event]] = []
    for plane in data.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = int(stats["profile_start_time"])
            stop = int(stats["profile_stop_time"])
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(Device(
                plane.name,
                _events(lines.get("XLA Modules")),
                _events(lines.get("XLA Ops"))))
        elif plane.name.startswith("/host:"):
            host.extend(_events(ln) for ln in plane.lines)
    if window_epoch_ns is not None and start is not None:
        window = (window_epoch_ns[0] - start, window_epoch_ns[1] - start)
    elif start is not None:
        window = (0, stop - start)
    else:
        raise ValueError(f"{path}: no profile_start_time in any plane")
    devices.sort(key=lambda d: d.name)
    return Trace(window=window, devices=devices, host=host)


def _events(line) -> List[Event]:
    if line is None:
        return []
    return [Event(ev.name, int(ev.start_ns), int(ev.end_ns))
            for ev in line.events]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def busy(device: Device, window: Interval) -> List[Interval]:
    """Intervals in which an op ran on the device, inside ``window``."""
    return union(clip(((e.start, e.end) for e in device.ops), window))


def busy_ns(trace: Trace) -> float:
    """Busy time averaged over the chips of the trace."""
    if not trace.devices:
        return 0.0
    return sum(sum(b - a for a, b in busy(d, trace.window))
               for d in trace.devices) / len(trace.devices)


def gaps(device: Device, window: Interval) -> List[Interval]:
    """Idle intervals of the device inside ``window``."""
    out, cur = [], window[0]
    for a, b in busy(device, window):
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < window[1]:
        out.append((cur, window[1]))
    return out


# ---------------------------------------------------------------------------
# programs and ops
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ProgramRuns:
    count: int
    total_ns: int
    first_start: int
    last_end: int


def program_runs(trace: Trace, name: str) -> Optional[ProgramRuns]:
    """Runs of one compiled program (by ``program_name``) that start in
    the window, on the first chip.  None when there are none."""
    if not trace.devices:
        return None
    lo, hi = trace.window
    runs = [e for e in trace.devices[0].modules
            if program_name(e.name) == name and lo <= e.start < hi]
    if not runs:
        return None
    return ProgramRuns(len(runs), sum(e.dur for e in runs),
                       min(e.start for e in runs), max(e.end for e in runs))


def program_totals(trace: Trace) -> Dict[str, Tuple[int, int]]:
    """program -> (runs, total ns) on the first chip, inside the window."""
    out: Dict[str, List[int]] = {}
    if not trace.devices:
        return {}
    lo, hi = trace.window
    for e in trace.devices[0].modules:
        if lo <= e.start < hi:
            acc = out.setdefault(program_name(e.name), [0, 0])
            acc[0] += 1
            acc[1] += e.dur
    return {k: (v[0], v[1]) for k, v in out.items()}


def self_times(events: Sequence[Event]) -> List[Tuple[Event, int]]:
    """Each event with its self time: its duration less the part that
    events nested inside it cover (a ``while`` op less its body)."""
    order = sorted(events, key=lambda e: (e.start, -e.end))
    own = {id(e): e.dur for e in order}
    stack: List[Event] = []
    for e in order:
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack and e.end <= stack[-1].end:
            own[id(stack[-1])] -= e.dur
        stack.append(e)
    return [(e, own[id(e)]) for e in order]


def top_ops(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` device ops that took most self time on the first chip,
    named ``<program>/<op>``, in seconds."""
    if not trace.devices:
        return []
    dev = trace.devices[0]
    lo, hi = trace.window
    mods = sorted((e for e in dev.modules), key=lambda e: e.start)
    starts = [m.start for m in mods]
    import bisect

    totals: Dict[str, int] = {}
    ops = [e for e in dev.ops if lo <= e.start < hi]
    for e, own in self_times(ops):
        i = bisect.bisect_right(starts, e.start) - 1
        prog = (program_name(mods[i].name)
                if i >= 0 and mods[i].end >= e.start else "?")
        key = f"{prog}/{op_name(e.name)}"
        totals[key] = totals.get(key, 0) + own
    best = sorted(totals.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in best]


def idle_gaps(trace: Trace, n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of the first chip, each named by the
    innermost host event that covers its midpoint (Python frames, which
    the profiler's Python tracer records as ``$file:line function``,
    before runtime events), in seconds."""
    if not trace.devices:
        return []
    found = sorted(gaps(trace.devices[0], trace.window),
                   key=lambda g: -(g[1] - g[0]))[:n]
    return [(_host_activity(trace, (a + b) // 2), (b - a) / 1e9)
            for a, b in found]


def _host_activity(trace: Trace, t: int) -> str:
    best: Optional[Event] = None
    for events in trace.host:
        for e in events:
            if e.start <= t < e.end and "sleep" not in e.name:
                rank = (not e.name.startswith("$"), e.dur)
                if best is None or rank < (not best.name.startswith("$"),
                                           best.dur):
                    best = e
    return best.name if best is not None else "no host event"
