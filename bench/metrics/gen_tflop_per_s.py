"""Algebra FLOPs of every graph call finished in the window, over the
window (host clock).  The FLOPs are counted from the layer's shapes
(``benchlib.counts.layer_graph_flops``), whatever the kernels do."""
from benchlib import graph


def read(run):
    if run.kind != "graph":
        return None
    return graph.flops(run.cell) * run.calls / run.window_s / 1e12
