"""Needed operations of the traced refills over their whole steps' time
times the chip's bf16 peak: each from its prefill's start to the next
decode step's start on the device, so the splice and the host's work in
between count as well as the prefill program (which prefill_roofline
reads alone)."""
from .. import work


def read(run):
    pairs = run.with_device_times("prefill", whole_step=True)
    if not pairs:
        return None
    flops = sum(work.prefill_call(run.conf, l.rows, run.mix.prompt_len).flops
                for l, _ in pairs)
    return 100.0 * flops / (sum(t for _, t in pairs) * run.peak["bf16_flop_per_s"])
