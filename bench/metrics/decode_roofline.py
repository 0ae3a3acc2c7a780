"""The least time the decode steps in the traced window could take on
the chip (their model FLOPs at peak, or their least bytes at HBM
bandwidth, whichever is longer; the ``decode_needs`` of the
configuration's architecture, ``bench/architectures/``) over the
decode-step program's measured device time, in percent."""
from benchlib.decode_work import decode_work


def read(run):
    w = decode_work(run)
    if w is None or run.peaks is None or w.tokens == 0:
        return None
    least = max(w.flops / run.peaks.flops_bf16,
                w.bytes / run.peaks.hbm_bytes_per_s)
    return 100.0 * least / (w.runs.total_ns / 1e9)
