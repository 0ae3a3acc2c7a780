"""Device time of one run of the decode-step program (``jit_step``),
averaged over its runs in the traced window."""
from benchlib import decode_work, trace


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    runs = trace.program_runs(run.trace, decode_work.DECODE_PROGRAM)
    return None if runs is None else runs.total_ns / runs.count / 1e6
