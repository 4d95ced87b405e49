"""Least time of the decode steps' needed work (``work.decode_step``) over
their device time, summed over the traced decode executions."""
from .. import work


def read(run):
    pairs = run.with_device_times("decode")
    if not pairs:
        return None
    least = sum(work.decode_step(run.conf, l.rows, l.ctx_sum).least_s(run.peak)
                for l, _ in pairs)
    return 100.0 * least / sum(t for _, t in pairs)
