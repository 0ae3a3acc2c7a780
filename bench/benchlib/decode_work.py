"""The decode work inside a serving run's traced window."""
from __future__ import annotations

import dataclasses
from typing import Optional

from . import trace


@dataclasses.dataclass(frozen=True)
class DecodeWork:
    runs: trace.ProgramRuns         # the decode-step program in the trace
    flops: int                      # model FLOPs of the live tokens
    bytes: int                      # least bytes (the architecture's
                                    # ``decode_needs``)
    tokens: int                     # live slot-steps


#: the slot engine's jitted decode step, as the trace names its program
DECODE_PROGRAM = "jit_step"


def decode_work(run) -> Optional[DecodeWork]:
    """Each served request's decode steps are spread evenly between its
    first and its last token; those whose time falls in the traced window
    count, each attending over its prompt and the tokens before it."""
    if run.kind != "serve" or run.trace is None:
        return None
    runs = trace.program_runs(run.trace, DECODE_PROGRAM)
    if runs is None:
        return None
    ta, tb = run.trace_host
    contexts = []
    for r in run.records:
        if not r.served or r.finished is None or len(r.tokens) < 2:
            continue
        n = len(r.tokens)
        step = (r.finished - r.first) / (n - 1)
        for k in range(1, n):
            if ta <= r.first + k * step < tb:
                contexts.append(r.request.prompt_len + k)
    flops, nbytes = run.cell.architecture().decode_needs(
        run.dims, runs.count, contexts)
    return DecodeWork(runs, flops, nbytes, len(contexts))
