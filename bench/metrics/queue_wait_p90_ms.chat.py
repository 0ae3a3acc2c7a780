"""90th percentile, over every request the window answers for, of the
time from its submission to the server until the scheduler took it off
the queue for its prefill (``RequestFuture.admitted_at``): the queue
part of ``ttft_p90_ms.chat``.  A request never admitted counts as
infinitely slow; a program without the counter gives nothing."""
import math

from benchlib.stats import percentile


def read(run):
    if run.kind != "serve" or not run.records:
        return None
    per = []
    for r in run.records:
        admitted = getattr(r.future, "admitted_at", None)
        per.append(math.inf if admitted is None
                   else admitted - r.future.request.submitted_at)
    p = percentile(per, 90)
    return None if p is None or math.isinf(p) else p * 1e3
