"""What the benchmark sees of one ``ServeEngine.run``, without editing the
engine: every output token's host time, every step launch, and which
request sits in which slot.

- Each ``Request`` gets a ``TimedOutput`` as its ``output`` list.  Its
  ``append`` stamps the host clock and, once the window is armed and its
  close has passed, raises :class:`WindowClosed` instead, which ends the
  engine's loop at the first token after the close.
- The engine's public ``prefill_step`` and ``decode_step`` are wrapped
  in host spans (``bench.prefill``, ``bench.decode``) that the profiler
  records, and each launch is logged with the rows that hold a request.
  No jit is added: the programs are the ones the engine compiled.
- Slots are mirrored from the engine's own policy (a FIFO queue; a freed
  slot is refilled in slot order right after the step that freed it), so
  a request's admission time is its predecessor's last token time, the
  closed loop's send time, and the first token the engine feeds a fresh
  slot (the prefill's argmax, which it never appends) can be read back
  from the decode step's input.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import numpy as np

from repro.serve.engine import Request


class WindowClosed(Exception):
    """Raised from a token append once the measured window has closed."""


@dataclass
class Served:
    """One request as the benchmark tracks it."""
    rid: int
    prompt: np.ndarray
    max_new: int
    in_flight: bool = False               # stands for a request already served
    slot: int = -1
    admitted: Optional[float] = None      # host time of the send
    times: List[float] = field(default_factory=list)
    first_fed: Optional[tuple] = None     # (decode launch index, slot)
    done: bool = False
    tokens: List[int] = field(default_factory=list)


class TimedOutput(list):
    def __init__(self, rec: "Recorder", served: Served):
        super().__init__()
        self._rec, self._served = rec, served

    def append(self, token) -> None:
        now = time.perf_counter()
        if self._rec.close is not None and now >= self._rec.close:
            raise WindowClosed
        super().append(token)
        self._served.times.append(now)
        self._rec.on_token(self._served, now)


@dataclass
class Launch:
    kind: str                 # "prefill" | "decode"
    t: float                  # host time at the launch
    rows: int                 # rows that hold a request
    ctx_sum: int              # decode: cache positions of those rows


class Recorder:
    """Instruments one engine; ``serve`` runs a request list through it."""

    def __init__(self, eng, prompt_len: int):
        self.eng, self.B, self.P = eng, eng.B, prompt_len
        self.spans = False          # wrap launches in profiler spans
        self.close: Optional[float] = None
        self.launches: List[Launch] = []
        self.fed: List[object] = []           # decode inputs after a prefill
        self.prefill_fn, self.decode_fn = eng.prefill_step, eng.decode_step
        eng.prefill_step, eng.decode_step = self._wrap_prefill, self._wrap_decode
        self._reset([])

    # ----------------------------------------------------------- mirror
    def _reset(self, served: List[Served]) -> None:
        self.queue = list(served)
        self.active: List[Optional[Served]] = [None] * self.B
        self.freed_at: List[float] = [0.0] * self.B
        self.fresh: List[Served] = []
        self.start = time.perf_counter()

    def on_token(self, s: Served, now: float) -> None:
        if len(s.times) >= s.max_new:
            s.done = True
            self.active[s.slot] = None
            self.freed_at[s.slot] = now

    def _wrap_prefill(self, params, batch):
        now = time.perf_counter()
        new = []
        for i in range(self.B):             # the engine's refill order
            if self.active[i] is None and self.queue:
                s = self.queue.pop(0)
                s.slot, self.active[i] = i, s
                s.admitted = self.freed_at[i] if self.launches else self.start
                new.append(s)
        self.fresh.extend(new)
        self.launches.append(Launch("prefill", now, len(new), 0))
        if not self.spans:
            return self.prefill_fn(params, batch)
        with jax.profiler.TraceAnnotation("bench.prefill"):
            return self.prefill_fn(params, batch)

    def _wrap_decode(self, params, cache, tok):
        now = time.perf_counter()
        live = [s for s in self.active if s is not None]
        if self.fresh:
            self.fed.append(tok)
            for s in self.fresh:
                s.first_fed = (len(self.fed) - 1, s.slot)
            self.fresh = []
        self.launches.append(Launch(
            "decode", now, len(live),
            sum(self.P + len(s.times) for s in live)))
        if not self.spans:
            return self.decode_fn(params, cache, tok)
        with jax.profiler.TraceAnnotation("bench.decode"):
            return self.decode_fn(params, cache, tok)

    # ------------------------------------------------------------ serve
    def serve(self, served: List[Served], close: Optional[float] = None
              ) -> bool:
        """Run ``served`` through the engine; with ``close``, stop at the
        first token after it.  Returns whether the window closed (False:
        the engine finished every request first)."""
        self._reset(served)
        self.launches, self.fed = [], []
        reqs = []
        for s in served:
            r = Request(rid=s.rid, prompt=s.prompt, max_new_tokens=s.max_new)
            r.output = s.tokens = TimedOutput(self, s)
            reqs.append(r)
        self.close = close
        try:
            self.eng.run(reqs)
        except WindowClosed:
            return True
        finally:
            self.close = None
        return False

    def first_tokens(self, served: List[Served]) -> dict:
        """rid -> the token the engine fed after the request's prefill."""
        fed = jax.device_get(self.fed)
        return {s.rid: int(np.asarray(fed[s.first_fed[0]])[s.first_fed[1], 0])
                for s in served if s.first_fed is not None}
