"""Device time of the prefill program in the traced window, per 1000
prompt tokens of the requests whose first token came in that window.

The slot engine jits ``functools.partial(decode.prefill, cfg=...)``,
which JAX names ``_unknown``: on the serving path it is the only program
so named (read by hand from a v5e trace)."""
from benchlib import trace

PREFILL_PROGRAM = "jit__unknown"


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    runs = trace.program_runs(run.trace, PREFILL_PROGRAM)
    ta, tb = run.trace_host
    tokens = sum(r.request.prompt_len for r in run.records
                 if r.first is not None and ta <= r.first < tb)
    if runs is None or tokens == 0:
        return None
    return runs.total_ns / 1e6 / (tokens / 1000.0)
