"""Batched serving engine: prefill + greedy decode with fixed-shape jitted
steps and slot-based continuous batching (finished sequences are replaced
from the request queue without recompiling — the decode step shape never
changes).

Continuous batching is *correct* continuous batching: when a slot frees
mid-decode, the request that takes it over is **re-prefilled** — all slots
refilled in the same step share one batched prefill call — and its rows of
the KV cache, per-slot position vector and last-token vector are spliced
in while the other slots keep decoding undisturbed.  (The per-slot
positions come from the model layer: ``cache['pos']`` is a (B,) vector and
attention masks/RoPE are per-row, so a freshly prefilled slot decodes
exactly as it would in a batch of its own.)

Startup also **warms the mapping-plan cache** (`repro.core.plan`): the
engine pre-solves the COMET block-selection plans for its prefill and
decode kernel shapes through ``PlanCache.warmup``, so the first traced
kernel finds its plan already on disk instead of running a search inside
the trace.

**Tracing.** The engine names its work on the profiler's own clock, so a
trace taken with ``jax.profiler`` puts the host loop beside the device:
the steps are the programs ``jit_prefill_step`` and ``jit_decode_step``;
each iteration of the decode loop is a ``serve.step`` step span holding
``serve.decode`` (token cast, decode launch, argmax and finite-check
dispatch; arg ``rows`` live), ``serve.sync`` (the wait for the tokens),
``serve.emit`` (the per-slot token loop), ``serve.admit`` (slot
assignment; ``rows`` admitted) and, on a refill, ``serve.prefill``
(``rows`` admitted, ``batch``) and ``serve.splice``.  A span costs one
call that returns at once when no profiler records.  ``stats`` counts
``rows_admitted`` and ``compiles`` (executables built while ``run`` is
active, each function named once in a WARNING), and every ``Request``
is stamped on ``time.perf_counter``: ``admitted_s``, ``first_token_s``,
``done_s``.
"""
from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import Mesh

from ..models.model import Model

__all__ = ["Request", "ServeEngine"]

log = logging.getLogger(__name__)

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_serving = threading.local()        # .engine: the engine in run() on this thread
_warned: Set[str] = set()


def _count_compile(event: str, duration_s: float, **kw) -> None:
    """Count an executable built while an engine serves (a compile inside
    ``run`` stalls every slot), naming its function once per process."""
    eng = getattr(_serving, "engine", None)
    if event != COMPILE_EVENT or eng is None:
        return
    eng.stats["compiles"] += 1
    name = kw.get("fun_name", "?")
    if name not in _warned:
        _warned.add(name)
        log.warning("%s compiled while serving", name)


jax.monitoring.register_event_duration_secs_listener(_count_compile)


@dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (prompt_len,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None
    # wall-clock decode budget from slot admission; a request that blows
    # it is force-finished (``timed_out``) so it cannot pin a slot until
    # the engine-global ``max_steps``
    deadline_s: Optional[float] = None
    # filled by the engine:
    output: List[int] = field(default_factory=list)
    done: bool = False
    timed_out: bool = False
    admitted_s: Optional[float] = None      # time.perf_counter() stamps
    first_token_s: Optional[float] = None
    done_s: Optional[float] = None


class ServeEngine:
    """Fixed batch of decode slots; requests stream through them.

    Per-request guards: ``Request.max_new_tokens`` (optionally clamped
    by the engine's ``max_new_cap``) bounds tokens, and
    ``Request.deadline_s`` (default ``default_deadline_s``) bounds wall
    time per slot occupancy — one runaway request degrades to a
    truncated answer instead of holding a decode slot hostage."""

    def __init__(self, model: Model, params, *, batch_size: int,
                 cache_len: int, prompt_len: int,
                 mesh: Optional[Mesh] = None,
                 plan_warmup: bool = True,
                 max_new_cap: Optional[int] = None,
                 default_deadline_s: Optional[float] = None):
        self.model = model
        self.params = params
        self.mesh = mesh
        self.B = batch_size
        self.cache_len = cache_len
        self.prompt_len = prompt_len
        self.max_new_cap = max_new_cap
        self.default_deadline_s = default_deadline_s
        cfg = model.cfg

        # the engine's two compiled steps (public so a caller can lower
        # and inspect the exact programs the engine runs); their names
        # name the device programs, jit_prefill_step and jit_decode_step
        def prefill_step(p, b):
            return model.prefill(p, b, cache_len, mesh)

        def decode_step(p, c, t):
            return model.decode(p, c, t, mesh)

        self.prefill_step = jax.jit(prefill_step)
        self.decode_step = jax.jit(decode_step, donate_argnums=(1,))
        # nonfinite_tokens: tokens taken (by argmax) from a logits row
        # that held a NaN or an inf; argmax still yields an in-vocabulary
        # id there, so only this count shows a broken decode step
        self.stats: Dict[str, float] = {"prefill_calls": 0, "decode_steps": 0,
                                        "tokens_out": 0, "timeouts": 0,
                                        "nonfinite_tokens": 0,
                                        "rows_admitted": 0, "compiles": 0}
        if plan_warmup:
            self.warm_plans()

    # ------------------------------------------------------------- plans
    def plan_shapes(self) -> Dict[str, List]:
        """The kernel shapes this engine's prefill/decode steps can ask
        the autotuner for (``PAPER_KERNEL_SHAPES``-style table): the
        prefill self-attention block (prompt_len x prompt_len), the
        decode block over the full cache (1 x cache_len — the CPU decode
        path uses dense einsums, but a kernelized flash-decoding backend
        asks for exactly this shape, so the plan is pre-solved either
        way), and — for SSD families — the chunk-length sweep for the
        prompt length."""
        cfg = self.model.cfg
        shapes: Dict[str, List] = {}
        if not cfg.has_ssm or cfg.family == "hybrid":
            shapes["attention_blocks"] = [
                (self.prompt_len, self.prompt_len, cfg.hd),   # prefill
                (1, self.cache_len, cfg.hd),                  # decode
            ]
        if cfg.has_ssm:
            shapes["ssd_chunk_len"] = [
                (self.prompt_len, cfg.ssm_headdim, cfg.ssm_state)]
        return shapes

    def warm_plans(self) -> Dict[str, int]:
        """Pre-solve the block-selection plans for this engine's kernel
        shapes in one ``search_many`` sweep and persist them (PlanCache
        disk store), so neither this process nor any later one re-solves
        at trace time.  The sweep runs on threads: a serving process
        holds the accelerator, and a forked pool worker would inherit
        its runtime (the sweep is a handful of jobs anyway)."""
        from ..kernels.autotune import plan_jobs
        from ..core.plan import get_plan_cache

        t0 = time.time()
        stats = get_plan_cache().warmup(plan_jobs(self.plan_shapes()),
                                        executor="thread",
                                        sweep_id="serve-warmup")
        self.stats["plan_warmup_hits"] = stats["hits"]
        self.stats["plan_warmup_solved"] = stats["solved"]
        self.stats["plan_warmup_s"] = time.time() - t0
        return stats

    # ------------------------------------------------------------- serving
    def _pad_prompts(self, rows: Sequence[Optional[Request]]) -> np.ndarray:
        """(B, prompt_len) token rows, right-aligned; ``None`` rows (empty
        or not-being-refilled slots) stay zero."""
        toks = np.zeros((self.B, self.prompt_len), np.int32)
        for i, r in enumerate(rows):
            if r is None:
                continue
            t = r.prompt[-self.prompt_len:]
            toks[i, -len(t):] = t          # right-aligned
        return toks

    def _prefill_batch(self, rows: Sequence[Optional[Request]]):
        """One batched prefill over ``rows`` (None rows carry zeros).
        Returns (last-token vector, cache with per-slot positions)."""
        batch = {"tokens": jnp.asarray(self._pad_prompts(rows))}
        if self.model.cfg.is_encdec:
            Se = max(1, self.prompt_len // self.model.cfg.enc_ratio)
            batch["src_embeds"] = jnp.zeros(
                (self.B, Se, self.model.cfg.d_model), jnp.float32)
        logits, cache = self.prefill_step(self.params, batch)
        self.stats["prefill_calls"] += 1
        last = jnp.argmax(logits[:, -1, :self.model.cfg.vocab_size], -1)
        cache = dict(cache)
        # per-slot decode positions from the start: the merged cache keeps
        # one compiled decode shape whether or not slots ever diverge
        cache["pos"] = jnp.broadcast_to(
            jnp.asarray(cache["pos"], jnp.int32), (self.B,))
        return last, cache

    def _refill_prefill(self, active: Sequence[Optional[Request]],
                        idxs: List[int], cache, last):
        """Prefill the newly refilled slots (one batched call however many
        freed this step) and splice their rows — KV/state cache, position,
        last token — into the live decode state."""
        rows = [r if i in idxs else None for i, r in enumerate(active)]
        with TraceAnnotation("serve.prefill", rows=len(idxs), batch=self.B):
            fresh_last, fresh = self._prefill_batch(rows)
        if cache is None:                  # initial fill: take it wholesale
            return fresh_last, fresh
        with TraceAnnotation("serve.splice"):
            sel = np.zeros(self.B, dtype=bool)
            sel[idxs] = True
            selj = jnp.asarray(sel)

            def splice(old, new):
                # stacked cache leaves are (L, B, ...): batch axis 1
                shape = [1] * old.ndim
                shape[1] = self.B
                return jnp.where(selj.reshape(shape), new, old)

            merged = {"pos": jnp.where(selj, fresh["pos"], cache["pos"])}
            for key in cache:
                if key != "pos":
                    merged[key] = jax.tree.map(splice, cache[key], fresh[key])
            return jnp.where(selj, fresh_last, last), merged

    def _token_budget(self, r: Request) -> int:
        return (r.max_new_tokens if self.max_new_cap is None
                else min(r.max_new_tokens, self.max_new_cap))

    def run(self, requests: List[Request], *, max_steps: int = 10_000
            ) -> List[Request]:
        """Process all requests with continuous slot reuse."""
        prev, _serving.engine = getattr(_serving, "engine", None), self
        try:
            self._run(requests, max_steps)
        finally:
            _serving.engine = prev
        return list(requests)

    def _run(self, requests: List[Request], max_steps: int) -> None:
        queue = list(requests)
        active: List[Optional[Request]] = [None] * self.B

        def refill() -> List[int]:
            free = [i for i, r in enumerate(active) if r is None][:len(queue)]
            with TraceAnnotation("serve.admit", rows=len(free)):
                now = time.perf_counter()
                for i in free:
                    r = active[i] = queue.pop(0)
                    r.admitted_s = now
                self.stats["rows_admitted"] += len(free)
            return free

        last, cache = self._refill_prefill(active, refill(), None, None)

        for _step in range(max_steps):
            live = sum(r is not None and not r.done for r in active)
            if not live and not queue:
                break
            with StepTraceAnnotation("serve.step",
                                     step_num=self.stats["decode_steps"]):
                with TraceAnnotation("serve.decode", rows=live):
                    tok = last[:, None].astype(jnp.int32)
                    logits, cache = self.decode_step(self.params, cache, tok)
                    self.stats["decode_steps"] += 1
                    step = logits[:, -1, :self.model.cfg.vocab_size]
                    last = jnp.argmax(step, -1)
                    finite = jnp.isfinite(step).all(-1)
                with TraceAnnotation("serve.sync"):
                    host, finite = jax.device_get((last, finite))
                with TraceAnnotation("serve.emit"):
                    self._emit(active, host, finite)
                new = refill()
                if new:
                    # the bug this fixes: refilled slots used to inherit the
                    # previous occupant's KV cache and last token
                    last, cache = self._refill_prefill(active, new, cache, last)

    def _emit(self, active: List[Optional[Request]], host, finite) -> None:
        """Append each live slot's token; finish (and free) the slots whose
        request reached its budget, its eos or its deadline."""
        now = time.perf_counter()
        for i, r in enumerate(active):
            if r is None or r.done:
                continue
            r.output.append(int(host[i]))
            if r.first_token_s is None:
                r.first_token_s = now
            self.stats["tokens_out"] += 1
            self.stats["nonfinite_tokens"] += int(not finite[i])
            deadline = (r.deadline_s if r.deadline_s is not None
                        else self.default_deadline_s)
            if deadline is not None and now - r.admitted_s >= deadline:
                # runaway guard: force-finish instead of pinning the
                # slot until the engine-global max_steps
                r.timed_out = True
                self.stats["timeouts"] += 1
            elif not (len(r.output) >= self._token_budget(r)
                      or (r.eos_id is not None and host[i] == r.eos_id)):
                continue
            r.done, r.done_s = True, now
            active[i] = None               # slot freed (continuous batching)
