"""Needed operations of every step launched in the traced window, over
the window's length times the chip's bf16 peak."""
from .. import work


def read(run):
    if run.trace is None or run.trace.window_s <= 0 or not run.launches:
        return None
    flops = sum(
        work.prefill_call(run.conf, l.rows, run.mix.prompt_len).flops
        if l.kind == "prefill" else
        work.decode_step(run.conf, l.rows, l.ctx_sum).flops
        for l in run.launches)
    return 100.0 * flops / (run.trace.window_s * run.peak["bf16_flop_per_s"])
