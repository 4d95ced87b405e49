"""Serving driver: batched prefill+decode with the ServeEngine.

Example (CPU, reduced config):
    PYTHONPATH=src python -m repro.launch.serve --arch glm4-9b --smoke \
        --requests 12 --batch 4 --prompt-len 32 --max-new 16

Plan-cache wiring (the MappingPlan subsystem, ``repro.core.plan``):
``--plan-cache DIR`` points the engine's plan store somewhere explicit
(equivalent to ``REPRO_PLAN_CACHE=DIR``); ``--plan-bundle PATH`` imports
a bundle exported by ``benchmarks/paper_tables.export_plans`` before the
engine starts, so startup warmup is pure cache hits; ``--no-plan-warmup``
skips the startup warmup sweep entirely; ``--plan-gc`` runs the store's
garbage collection (age expiry + LRU eviction + vacuum) before startup —
the knob a fleet cron job would use.  The output JSON reports which
store backend actually served the run (``plan_store``): ``sqlite`` on a
healthy host, ``json`` or ``memory`` after degradations (see
``repro.core.planstore``).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh

from repro.configs.registry import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models.config import ModelConfig
from repro.models.model import Model
from repro.serve.engine import Request, ServeEngine

__all__ = ["build", "run", "main"]


def build(cfg: ModelConfig, *, requests: int, batch: int, prompt_len: int,
          cache_len: int, max_new: int, seed: int = 0,
          mesh: Optional[Mesh] = None, plan_warmup: bool = True,
          params=None) -> Tuple[ServeEngine, List[Request]]:
    """The engine and its requests: random weights drawn from ``seed``
    (unless ``params`` is given, so two engines can share one set) and
    ``requests`` prompts of ``prompt_len`` tokens drawn from ``seed``."""
    model = Model(cfg)
    if params is None:
        params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=prompt_len).astype(np.int32),
                    max_new_tokens=max_new)
            for i in range(requests)]
    eng = ServeEngine(model, params, batch_size=batch,
                      cache_len=cache_len, prompt_len=prompt_len,
                      mesh=mesh, plan_warmup=plan_warmup)
    return eng, reqs


def run(eng: ServeEngine, reqs: List[Request]) -> Dict:
    """Serve ``reqs`` to completion; returns the run's counts and its
    host wall time."""
    t0 = time.time()
    done = eng.run(reqs)
    dt = time.time() - t0
    n_tok = sum(len(r.output) for r in done)
    return {
        "requests": len(done),
        "completed": sum(r.done or len(r.output) > 0 for r in done),
        "tokens": n_tok,
        "wall_s": round(dt, 2),
        "tok_per_s": round(n_tok / dt, 1),
        "decode_steps": eng.stats["decode_steps"],
        "nonfinite_tokens": eng.stats["nonfinite_tokens"],
        "prefill_calls": eng.stats["prefill_calls"],
        "plan_warmup_solved": eng.stats.get("plan_warmup_solved", 0),
        "plan_warmup_hits": eng.stats.get("plan_warmup_hits", 0),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--mesh", choices=["none", "host"], default="none")
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="mapping-plan store directory "
                         "(default: $REPRO_PLAN_CACHE or ~/.cache/repro-plans)")
    ap.add_argument("--plan-bundle", default=None, metavar="PATH",
                    help="import a plan bundle (paper_tables.export_plans) "
                         "into the store before starting the engine")
    ap.add_argument("--no-plan-warmup", action="store_true",
                    help="skip the startup plan-warmup sweep")
    ap.add_argument("--plan-gc", action="store_true",
                    help="garbage-collect the plan store (age expiry + "
                         "LRU eviction + vacuum) before starting")
    args = ap.parse_args()

    if args.plan_cache:
        os.environ["REPRO_PLAN_CACHE"] = args.plan_cache
    enable_compile_cache()
    from repro.core.plan import get_plan_cache
    imported = 0
    gc_out = None
    if args.plan_gc:
        gc_out = get_plan_cache().gc()
    if args.plan_bundle:
        imported = get_plan_cache().import_bundle(args.plan_bundle)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    eng, reqs = build(cfg, requests=args.requests, batch=args.batch,
                      prompt_len=args.prompt_len, cache_len=args.cache_len,
                      max_new=args.max_new,
                      mesh=make_host_mesh() if args.mesh == "host" else None,
                      plan_warmup=not args.no_plan_warmup)
    out = run(eng, reqs)
    store = get_plan_cache().store_stats()["store"]
    out.update({
        "plan_bundle_imported": imported,
        "plan_store": store.get("backend"),
        "plan_store_plans": store.get("plans", 0),
        "plan_gc": gc_out,
    })
    print(json.dumps(out))


if __name__ == "__main__":
    main()
