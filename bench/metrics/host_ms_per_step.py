"""Host work per server loop, in ms: the mean, over the ``serve.loop``
spans starting in the traced window, of the loop's duration less its
time under ``serve.fetch`` (the host waiting for the decode step's
result) and ``serve.prefill`` (waiting for a prefill).  While the host
does this work the chip has no decode step queued.  Read from the
program's own spans; a program without them gives nothing."""
from benchlib import spans


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    return spans.host_ms_per_loop(run.trace)
