"""Model FLOPs of the decode steps in the traced window over the chip's
peak times the time from the first decode step's start to the last
one's end, stalls between them included, in percent."""
from benchlib.decode_work import decode_work


def read(run):
    w = decode_work(run)
    if w is None or run.peaks is None or w.tokens == 0:
        return None
    span = (w.runs.last_end - w.runs.first_start) / 1e9
    return 100.0 * w.flops / (run.peaks.flops_bf16 * span)
