"""Batched serving engine: prefill + greedy decode with fixed-shape jitted
steps and slot-based continuous batching (finished sequences are replaced
from the request queue without recompiling — the decode step shape never
changes).

Continuous batching is *correct* continuous batching: when a slot frees
mid-decode, the request that takes it over is **re-prefilled** — all slots
refilled in the same step share one batched prefill call — and its rows of
the KV cache, per-slot position vector and last-token vector are written
into the live decode state while the other slots keep decoding
undisturbed.  (The per-slot positions come from the model layer:
``cache['pos']`` is a (B,) vector and attention masks/RoPE are per-row, so
a freshly prefilled slot decodes exactly as it would in a batch of its
own.)  A refill of ``k < B`` slots prefills only those rows, padded with
zero rows to the smallest *row bucket* that holds them (1, 4, 16, 64, …
up to B, with B itself; under a mesh rounded up to a multiple of the
data-parallel size), and one jitted splice (``splice_rows``, donated)
scatters them into the live state in place, so only that state and the
bucket's rows are alive at a refill.  The first fill, and a refill of all
B slots, prefill B rows and take them whole.  Each bucket's prefill and
splice are compiled when the engine is built, so serving compiles
nothing new however many slots a step frees.

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
(``rows`` admitted, ``batch`` the rows prefilled: the bucket) and
``serve.splice``.  A span costs one call that returns at once when no
profiler records.  ``stats`` counts ``rows_admitted``, ``rows_prefilled``
(the rows of every prefill call; ``rows_admitted / rows_prefilled`` is
the share of prefilled rows a request used) and ``compiles`` (executables
built while ``run`` is active, each function named once in a WARNING),
and every ``Request`` is stamped on ``time.perf_counter``:
``admitted_s``, ``first_token_s``, ``done_s``.
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
from ..parallel.sharding import dp_size

__all__ = ["Request", "ServeEngine", "row_buckets"]

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


def row_buckets(batch: int, multiple: int = 1) -> List[int]:
    """The row counts a refill prefills at: 1, 4, 16, 64, … below
    ``batch``, each rounded up to a multiple of ``multiple`` (the
    data-parallel size that shards the batch), then ``batch`` itself."""
    out, n = set(), 1
    while n < batch:
        out.add(-(-n // multiple) * multiple)
        n *= 4
    return sorted(b for b in out if b < batch) + [batch]


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
        # the engine's compiled steps (public so a caller can lower and
        # inspect the exact programs the engine runs); their names name the
        # device programs, jit_prefill_step, jit_decode_step, jit_splice_rows
        def prefill_step(p, b):
            return model.prefill(p, b, cache_len, mesh)

        def decode_step(p, c, t):
            return model.decode(p, c, t, mesh)

        def splice_rows(cache, last, logits, fresh, idx):
            # the fresh rows' first tokens, then each row written at its
            # slot: batch axis 0 of pos and last, axis 1 of every stacked
            # leaf; an index of B (a padding row) writes nothing
            first = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)
            out = {"pos": cache["pos"].at[idx].set(fresh["pos"], mode="drop")}
            for key in cache:
                if key != "pos":
                    out[key] = jax.tree.map(
                        lambda old, new: old.at[:, idx].set(new, mode="drop"),
                        cache[key], fresh[key])
            return last.at[idx].set(first.astype(last.dtype), mode="drop"), out

        self.prefill_step = jax.jit(prefill_step)
        self.decode_step = jax.jit(decode_step, donate_argnums=(1,))
        self.splice_rows = jax.jit(splice_rows, donate_argnums=(0, 1))
        self.buckets = row_buckets(
            batch_size, dp_size(mesh) if mesh is not None else 1)
        # nonfinite_tokens: tokens taken (by argmax) from a logits row
        # that held a NaN or an inf; argmax still yields an in-vocabulary
        # id there, so only this count shows a broken decode step
        self.stats: Dict[str, float] = {"prefill_calls": 0, "decode_steps": 0,
                                        "tokens_out": 0, "timeouts": 0,
                                        "nonfinite_tokens": 0,
                                        "rows_admitted": 0,
                                        "rows_prefilled": 0, "compiles": 0}
        if plan_warmup:
            self.warm_plans()
        # weights given as shapes (to lower the steps for another device)
        # cannot serve, so there is nothing to warm
        if all(isinstance(x, jax.Array) for x in jax.tree.leaves(params)):
            self.warm_refills()

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

    def warm_refills(self) -> None:
        """Compile, ahead of time, the prefill of every row bucket below B
        and the splice of every bucket into the live B-row state, so a
        refill of any size builds nothing while serving.  (A compile ahead
        of time fills ``jax.jit``'s cache for arguments of the same shapes
        and placement.)  The B-row prefill and the decode step compile at
        the first fill, as they always have.  Under a mesh the live state
        is placed by the decode step, so there the splice compiles again
        at the first partial refill."""
        _, live = jax.eval_shape(self.prefill_step, self.params,
                                 self._batch([None] * self.B))
        last = jax.ShapeDtypeStruct((self.B,), jnp.int32)
        for n in self.buckets:
            batch = self._batch([None] * n)
            if n < self.B:
                self.prefill_step.lower(self.params, batch).compile()
            logits, fresh = jax.eval_shape(self.prefill_step, self.params,
                                           batch)
            idx = jax.ShapeDtypeStruct((n,), jnp.int32)
            self.splice_rows.lower(live, last, logits, fresh, idx).compile()

    # ------------------------------------------------------------- serving
    def _batch(self, rows: Sequence[Optional[Request]]
               ) -> Dict[str, jax.Array]:
        """The prefill batch for ``rows`` (``None``: a zero row): their
        prompts right-aligned in (rows, prompt_len) tokens, and for an
        encoder-decoder zero source embeddings."""
        toks = np.zeros((len(rows), self.prompt_len), np.int32)
        for i, r in enumerate(rows):
            if r is not None:
                t = r.prompt[-self.prompt_len:]
                toks[i, -len(t):] = t          # right-aligned
        batch = {"tokens": jnp.asarray(toks)}
        cfg = self.model.cfg
        if cfg.is_encdec:
            Se = max(1, self.prompt_len // cfg.enc_ratio)
            batch["src_embeds"] = jnp.asarray(
                np.zeros((len(rows), Se, cfg.d_model), np.float32))
        return batch

    def bucket(self, k: int) -> int:
        """Rows a refill of ``k`` slots prefills: the least bucket >= k."""
        return next(b for b in self.buckets if b >= k)

    def _refill_prefill(self, active: Sequence[Optional[Request]],
                        idxs: List[int], cache, last):
        """Prefill the newly refilled slots ``idxs`` (one batched call
        however many freed this step) and write their rows — KV/state
        cache, position, last token — into the live decode state.  The
        first fill and a refill of every slot prefill B rows in slot order
        and take them whole; a refill of fewer prefills ``bucket(k)``
        rows, zero rows first and the admitted ones last (so a fault that
        drops a call's back rows lands on served rows, not on padding),
        and splices them in place."""
        k = len(idxs)
        whole = cache is None or k == self.B
        n = self.B if whole else self.bucket(k)
        rows = ([r if i in idxs else None for i, r in enumerate(active)]
                if whole else [None] * (n - k) + [active[i] for i in idxs])
        with TraceAnnotation("serve.prefill", rows=k, batch=n):
            logits, fresh = self.prefill_step(self.params, self._batch(rows))
            self.stats["prefill_calls"] += 1
            self.stats["rows_prefilled"] += n
            if whole:
                return (jnp.argmax(logits[:, -1, :self.model.cfg.vocab_size],
                                   -1), fresh)
        with TraceAnnotation("serve.splice"):
            idx = np.full(n, self.B, np.int32)     # padding rows: dropped
            idx[n - k:] = idxs
            return self.splice_rows(cache, last, logits, fresh,
                                    jnp.asarray(idx))

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
