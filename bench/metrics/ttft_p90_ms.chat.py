"""90th percentile, over every request the window answers for, of the
time from when it was due (its place in the open-loop schedule, not
when it was sent) to its first token.  A request never served counts as
infinitely slow.

Per layer, not end to end: on the chip a six-run set of danube.chat
spreads by about 13% (each request first waits for the running decode
step, uniform over ~105 ms, and this is the 4th longest of 33), more
than half of the largest bound allowed."""
import math

from benchlib.stats import percentile


def read(run):
    if run.kind != "serve" or not run.records:
        return None
    per = [r.first - r.due if r.served else math.inf for r in run.records]
    p = percentile(per, 90)
    return None if p is None or math.isinf(p) else p * 1e3
