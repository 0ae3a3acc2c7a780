"""Share of the traced window in which no op ran on the device: one
less the union of the ops' intervals over the window, in percent."""
from benchlib import trace

KIND = "graph"


def read(run):
    if run.kind != KIND or run.trace is None or not run.trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_ns(run.trace) / run.trace.window_ns)
