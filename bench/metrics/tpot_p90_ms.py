"""90th percentile, over every request the window answers for, of the
time from its first to its last token over the tokens after the first.
A request never served counts as infinitely slow."""
import math

from benchlib.stats import percentile


def read(run):
    if run.kind != "serve" or not run.records:
        return None
    per = []
    for r in run.records:
        if r.served and len(r.tokens) > 1:
            per.append((r.finished - r.first) / (len(r.tokens) - 1))
        elif not r.served:
            per.append(math.inf)
    p = percentile(per, 90)
    return None if p is None or math.isinf(p) else p * 1e3
