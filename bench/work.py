"""Operations and bytes that a step needs, counted from shapes alone.

"Needed" means what any implementation of the step has to do, whatever
the program chooses: every weight read once per call, the valid cache or
state read, the new entries written, the matrix products of the rows
that hold a request, and one output row per prompt.  Weights are counted
in bfloat16 (2 bytes), as the configurations state.  Each family's module
under ``bench/reference/`` counts its own layers (``decode_work``,
``prefill_work``) and says how; the two functions here pick the module by
the configuration's ``reference`` key.
"""
from __future__ import annotations

from typing import NamedTuple

BF16, F32 = 2, 4


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other):
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, peak: dict) -> float:
        """The least time the chip could take: the larger of the compute
        and the memory bound."""
        return max(self.flops / peak["bf16_flop_per_s"],
                   self.bytes / peak["hbm_bytes_per_s"])


def decode_step(cfg: dict, rows: int, ctx_sum: int) -> Work:
    """One decode step for ``rows`` rows that hold a request; ``ctx_sum``
    is the sum over those rows of the positions already in the cache."""
    from . import reference          # the family modules import this one
    return reference.part(cfg["reference"], "decode_work")(cfg, rows, ctx_sum)


def prefill_call(cfg: dict, rows: int, prompt_len: int) -> Work:
    """One prefill of ``rows`` refilled rows of ``prompt_len`` tokens; rows
    that the call computes but no request needs are not counted."""
    from . import reference
    return reference.part(cfg["reference"], "prefill_work")(
        cfg, rows, prompt_len)
