"""The least time of one graph call on the chip (its algebra FLOPs at
peak, or its least bytes at HBM bandwidth, whichever is longer;
``benchlib.graph.least_time_s``) over the device's busy time per call in
the traced window, which holds whole calls only, in percent.  The count
does not change when a later version fuses or splits the kernels."""
from benchlib import graph, trace


def read(run):
    if (run.kind != "graph" or run.trace is None or run.peaks is None
            or run.traced_calls == 0):
        return None
    busy = trace.busy_ns(run.trace) / 1e9 / run.traced_calls
    if busy <= 0:
        return None
    return 100.0 * graph.least_time_s(run.cell, run.peaks) / busy
