"""Host time of one graph call, in ms: the mean duration of the
program's ``graph.call`` spans starting in the traced window, from the
call's entry to its return (the window awaits each result after the
call returns).  A program without the span gives nothing."""
from benchlib import spans


def read(run):
    if run.kind != "graph" or run.trace is None:
        return None
    return spans.mean_ms(run.trace, "graph.call")
