"""Algebra FLOPs of the window's graph calls over the chip's peak times
the window (host clock), in percent: the whole call, dispatch and
gaps included."""
from benchlib import graph


def read(run):
    if run.kind != "graph" or run.peaks is None:
        return None
    return (100.0 * graph.flops(run.cell) * run.calls
            / (run.peaks.flops_bf16 * run.window_s))
