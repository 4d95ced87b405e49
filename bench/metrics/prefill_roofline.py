"""Least time of the prefills' needed work (``work.prefill_call``, refilled
rows only) over their device time, summed over the traced prefills."""
from .. import work


def read(run):
    pairs = run.with_device_times("prefill")
    if not pairs:
        return None
    least = sum(work.prefill_call(run.conf, l.rows, run.mix.prompt_len)
                .least_s(run.peak) for l, _ in pairs)
    return 100.0 * least / sum(t for _, t in pairs)
