"""COMET-driven Pallas block-size selection (DESIGN.md §2, kernel-level use).

This is the paper's mapping-space exploration applied to TPU tiles: for
each kernel we build the corresponding compound-op workload, instantiate a
single-core TPU-v5e hardware model, and rank candidate tile shapes with
the **shared search engine** — ``search(candidate_list=...)`` routes the
whole candidate set through the batched evaluator (core/batcheval.py),
the same memory-fit validation + Eq. 1–7 latency model the map-space
search uses, so Pallas block selection and the analytical model cannot
drift apart.  Candidate blocks map onto MappingSpec tile counts
(block -> ceil(dim / block) temporal tiles) and both Eq. 5-7 schedules
are evaluated per block candidate in the same SoA pass.

Every entry point resolves through the :class:`repro.core.plan.PlanCache`
(the ``MappingPlan`` subsystem): the first call per (shape, arch, engine
version) solves and persists a plan to the durable store
(:mod:`repro.core.planstore` — SQLite WAL with LRU/age eviction and
per-plan provenance, degrading to a JSON dir or memory-only under store
faults), so every later call — in this process or any other pointed at
the same ``$REPRO_PLAN_CACHE`` — is a dictionary/row lookup with **no
search at all**.  Store faults never reach the autotuner: a degraded
store costs durability, never a wrong (or missing) block shape.  Serving
engines pre-populate the cache at startup (``ServeEngine`` warmup) and
benchmark hosts can ship their sweeps as plan bundles
(``benchmarks/paper_tables.export_plans``).

VMEM working-set constraints mirror the kernels' actual scratch/BlockSpec
usage (those are layout facts about the kernels, not a cost model) and
pre-filter the candidate set.  All functions degrade to safe
hardware-aligned defaults if no candidate survives.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.hardware import Arch, tpu_v5e
from repro.core.ir import MappingSpec
from repro.core.plan import get_plan_cache
from repro.core.workload import (CompoundOp, flash_attention, gemm_softmax,
                                 ssd_chunk)

__all__ = ["attention_blocks", "gemm_epilogue_blocks", "ssd_chunk_len",
           "VMEM_BUDGET", "PAPER_KERNEL_SHAPES", "plan_jobs",
           "attention_plan_job", "gemm_epilogue_plan_job", "ssd_plan_jobs"]

# The scoped VMEM every kernel asks Mosaic for (CompilerParams
# vmem_limit_bytes) and the bound the candidate filters below keep each
# working-set estimate x2 (Pallas double buffering) within: half of v5e's
# 128 MiB.  Mosaic's default scoped limit is 16 MiB, which refuses blocks
# this filter passes; tests/test_tpu_compile.py compiles every paper shape
# at its chosen blocks against this limit.
VMEM_BUDGET = 64 * 1024 * 1024
_LANE = 128  # MXU/VPU lane alignment

SCHEDULES = ("sequential", "pipelined")

# Compute–collective overlap axis for kernel candidates.  On the
# single-chip kernel arch collectives cost zero, so the axis is inert
# today (ties break to overlap=0.0, the serial point) — it exists so
# multi-chip kernel archs rank double-buffered fused kernels (e.g.
# all-gather-GEMM) against their serial splits through the same plans.
OVERLAPS = (0.0, 1.0)

# The kernel shapes exercised by the paper-table benchmarks and the kernel
# test sweeps — the set a warm plan store must answer without solving
# (benchmarks/search_throughput.py gates this; tests/test_plan.py verifies
# the no-search property with a fresh cache instance).
PAPER_KERNEL_SHAPES: Dict[str, List[Tuple[int, ...]]] = {
    "attention_blocks": [(1024, 1024, 64), (4096, 4096, 128),
                         (1, 32768, 128), (32768, 32768, 128)],
    "gemm_epilogue_blocks": [(512, 4096, 128), (4096, 4096, 4096),
                             (4096, 16384, 4096)],
    "ssd_chunk_len": [(4096, 64, 128)],
}

_KERNEL_ARCH: Optional[Arch] = None

# Per-shape memo of *job descriptions* (compound op + candidate list) —
# the question, never the answer: every call still resolves its blocks
# through the PlanCache, this only avoids rebuilding identical candidate
# sets (and lets the plan layer's fingerprint memos hit by identity).
_JOB_MEMO: Dict[Tuple, object] = {}
_JOB_MEMO_MAX = 1024


def _memo_job(key: Tuple, build):
    hit = _JOB_MEMO.get(key)
    if hit is None and key not in _JOB_MEMO:
        if len(_JOB_MEMO) >= _JOB_MEMO_MAX:
            _JOB_MEMO.clear()
        hit = _JOB_MEMO[key] = build()
    return hit


def _align(x: int, a: int = _LANE) -> int:
    return max(a, (x // a) * a)


def _kernel_arch() -> Arch:
    """Single-chip view of the TPU for per-core block selection (the ICI
    mesh is irrelevant to one kernel invocation).  Memoized by hand — this
    module keeps no functools result caches; block-selection results live
    in the PlanCache alone."""
    global _KERNEL_ARCH
    if _KERNEL_ARCH is None:
        _KERNEL_ARCH = tpu_v5e(mesh=(1, 1))
    return _KERNEL_ARCH


def _candidate_specs(variant: str, tiles: Sequence[Dict[str, int]]
                     ) -> Tuple[MappingSpec, ...]:
    """Candidate MappingSpecs in (schedule, overlap)-major order (all
    sequential/overlap=0 first — the pre-plan-refactor axis layout, kept
    so selection ties break identically), pairs minor.  A tuple: immutable
    sequences are what the plan layer's fingerprint memo may cache by
    identity."""
    return tuple(MappingSpec(variant=variant, schedule=s, overlap=ov, **t)
                 for s in SCHEDULES for ov in OVERLAPS for t in tiles)


def _pair_of(plan, pairs: Sequence[Tuple[int, int]]) -> Tuple[int, int]:
    """Winning (block, block) pair of a candidates-mode plan: the stored
    ``best_index`` walks the (schedule, overlap)-major candidate list with
    the pairs minor, so modulo the pair count recovers the pair regardless
    of which schedule/overlap rung won."""
    return pairs[plan.best_index % len(pairs)]


# ------------------------------------------------------------ attention


def _attention_pairs(sq: int, skv: int, d: int) -> List[Tuple[int, int]]:
    """VMEM-feasible (block_q, block_k) pairs.  Working set per (bq, bk):
    q(bq,d) + k/v(bk,d)*2 + acc(bq,d) f32 + s(bq,bk) f32 (+ double
    buffering handled by budget halving)."""
    cands = (128, 256, 512, 1024)
    pairs = []
    for bq in cands:
        if bq > max(sq, _LANE):
            continue
        for bk in cands:
            if bk > max(skv, _LANE):
                continue
            vmem = (bq * d * 2 + 2 * bk * d * 2 + bq * d * 4 + bq * bk * 4
                    + 2 * bq * _LANE * 4)
            if vmem * 2 > VMEM_BUDGET:
                continue
            pairs.append((bq, bk))
    return pairs


def attention_plan_job(sq: int, skv: int, d: int
                       ) -> Optional[Tuple[CompoundOp, Arch, Dict,
                                           List[Tuple[int, int]]]]:
    """The plan job behind :func:`attention_blocks`: ``(co, arch,
    search_kw, pairs)``, or None when no pair survives the VMEM filter.
    The job triple is what warmup paths feed to ``PlanCache.warmup`` so
    their cache keys match the trace-time lookups exactly."""
    def build():
        pairs = _attention_pairs(sq, skv, d)
        if not pairs:
            return None
        M, N = max(sq, _LANE), max(skv, _LANE)
        co = flash_attention(M, d, N, d)
        tiles = [{"m_tiles": math.ceil(M / bq), "n_tiles": math.ceil(N / bk)}
                 for bq, bk in pairs]
        kw = {"candidate_list": _candidate_specs("fa", tiles)}
        return co, _kernel_arch(), kw, pairs

    return _memo_job(("attn", sq, skv, d), build)


def attention_blocks(sq: int, skv: int, d: int) -> Tuple[int, int]:
    """(block_q, block_k) for the FlashAttention kernel via a PlanCache-
    resolved candidates-mode search on the flash-attention compound op."""
    job = attention_plan_job(sq, skv, d)
    if job is None:
        return (_LANE, _LANE)
    co, arch, kw, pairs = job
    plan = get_plan_cache().resolve(co, arch, **kw)
    return _pair_of(plan, pairs)


# -------------------------------------------------------- gemm epilogues


def _gemm_pairs(m: int, n: int, k: int) -> List[Tuple[int, int]]:
    """VMEM-feasible (block_m, block_k) pairs.  Constraint: acc
    (block_m, N) f32 + B slice (block_k, N) must fit VMEM."""
    pairs = []
    for bm in (128, 256, 512):
        for bk in (128, 256, 512):
            if bk > max(k, _LANE):
                continue
            vmem = bm * n * 4 + bk * n * 2 + bm * bk * 2 + bm * n * 2
            if vmem * 2 > VMEM_BUDGET:
                continue
            pairs.append((bm, bk))
    return pairs


def gemm_epilogue_plan_job(m: int, n: int, k: int
                           ) -> Optional[Tuple[CompoundOp, Arch, Dict,
                                               List[Tuple[int, int]]]]:
    """The plan job behind :func:`gemm_epilogue_blocks` (see
    :func:`attention_plan_job`)."""
    def build():
        pairs = _gemm_pairs(m, n, k)
        if not pairs:
            return None
        M, K = max(m, _LANE), max(k, _LANE)
        co = gemm_softmax(M, n, K)
        tiles = [{"m_tiles": math.ceil(M / bm), "k_tiles": math.ceil(K / bk)}
                 for bm, bk in pairs]
        kw = {"candidate_list": _candidate_specs("fused_dist", tiles)}
        return co, _kernel_arch(), kw, pairs

    return _memo_job(("gemm", m, n, k), build)


def gemm_epilogue_blocks(m: int, n: int, k: int) -> Tuple[int, int]:
    """(block_m, block_k) for the fused GEMM-SM / GEMM-LN kernels via a
    PlanCache-resolved candidates-mode search on gemm_softmax."""
    job = gemm_epilogue_plan_job(m, n, k)
    if job is None:
        return (_LANE, _LANE)
    co, arch, kw, pairs = job
    plan = get_plan_cache().resolve(co, arch, **kw)
    return _pair_of(plan, pairs)


# ------------------------------------------------------------------ ssd


def _ssd_chunk_cands(s: int, p: int, n: int) -> List[int]:
    out = []
    for c in (128, 256, 512):
        if c > max(s, _LANE):
            continue
        vmem = (c * p * 2 * 2 + 2 * c * n * 2 + c * c * 4 + n * p * 4)
        if vmem * 2 > VMEM_BUDGET:
            continue
        out.append(c)
    return out


def ssd_plan_jobs(s: int, p: int, n: int
                  ) -> List[Tuple[CompoundOp, Arch, Dict, int]]:
    """One plan job per candidate chunk length (the chunk length changes
    the compound op's dimensions themselves, so this is a sweep of
    per-chunk workloads rather than a tiling grid)."""
    def build():
        return [(ssd_chunk(S=s, H=1, P=p, Dst=n, C=c), _kernel_arch(),
                 {"candidate_list": (MappingSpec(variant="fused_dist",
                                                 m_tiles=1),)}, c)
                for c in _ssd_chunk_cands(s, p, n)]

    return _memo_job(("ssd", s, p, n), build)


def ssd_chunk_len(s: int, p: int, n: int) -> int:
    """Chunk length for the SSD kernel via the COMET ssd_chunk compound op.

    Larger chunks amortize the state GEMMs but grow the (c, c) intra-chunk
    matrix quadratically; the shared cost model finds the knee.  The
    candidate chunk workloads fan through ``PlanCache.warmup`` as one
    batched sweep (no hand-rolled scalar loop); per-chunk plans persist,
    so warm processes answer from the store."""
    jobs = ssd_plan_jobs(s, p, n)
    if not jobs:
        return 128
    cache = get_plan_cache()
    # threads, not the process pool: this runs inside kernel tracing, in
    # a process that may hold the accelerator
    cache.warmup([(co, arch, kw) for co, arch, kw, _c in jobs],
                 executor="thread")
    best = None
    for co, arch, kw, c in jobs:
        plan = cache.resolve(co, arch, **kw)
        lat = math.ceil(max(s, 1) / c) * plan.latency_s
        if best is None or lat < best[0]:
            best = (lat, c)
    return best[1]


# --------------------------------------------------------------- warmup


def plan_jobs(shapes: Optional[Dict[str, Sequence[Tuple[int, ...]]]] = None
              ) -> List[Tuple[CompoundOp, Arch, Dict]]:
    """All plan jobs for a kernel-shape table (default:
    :data:`PAPER_KERNEL_SHAPES`) — feed to ``PlanCache.warmup`` to
    pre-solve every block selection those shapes will ever ask for."""
    shapes = shapes if shapes is not None else PAPER_KERNEL_SHAPES
    jobs: List[Tuple[CompoundOp, Arch, Dict]] = []
    for sq, skv, d in shapes.get("attention_blocks", ()):
        job = attention_plan_job(sq, skv, d)
        if job is not None:
            jobs.append(job[:3])
    for m, n, k in shapes.get("gemm_epilogue_blocks", ()):
        job = gemm_epilogue_plan_job(m, n, k)
        if job is not None:
            jobs.append(job[:3])
    for s, p, n in shapes.get("ssd_chunk_len", ()):
        jobs.extend(job[:3] for job in ssd_plan_jobs(s, p, n))
    return jobs
