"""End-to-end training driver.

Wires together: model zoo (--arch, reduced or full config), synthetic data
pipeline, sharded TrainState (ZeRO-1), jitted train step (optional µbatch
accumulation, COMET-planned loss collectives, int8 grad compression),
async checkpointing with keep-k retention, exact restart from the latest
checkpoint, and straggler monitoring.

Example (CPU, reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.model import Model
from repro.parallel.sharding import (batch_sharding, param_shardings,
                                     zero1_shardings)
from repro.train.checkpoint import AsyncCheckpointer, latest_step, \
    restore_checkpoint
from repro.train.data import SyntheticLM
from repro.train.elastic import StragglerMonitor
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import TrainState, make_train_step

__all__ = ["train_loop", "main"]


def warmup_kernel_plans(model: Model, seq: int) -> Dict[str, int]:
    """Pre-solve the COMET block-selection plans the training step's
    kernels will ask for (attention blocks at the training sequence
    length, SSD chunk lengths) through the shared PlanCache, so tracing
    the first step hits the store instead of searching."""
    from repro.core.plan import get_plan_cache
    from repro.kernels.autotune import plan_jobs

    cfg = model.cfg
    shapes: Dict[str, Any] = {}
    if not cfg.has_ssm or cfg.family == "hybrid":
        shapes["attention_blocks"] = [(seq, seq, cfg.hd)]
    if cfg.has_ssm:
        shapes["ssd_chunk_len"] = [(seq, cfg.ssm_headdim, cfg.ssm_state)]
    return get_plan_cache().warmup(plan_jobs(shapes),
                                   sweep_id="train-warmup")


def train_loop(model: Model, *, steps: int, batch: int, seq: int,
               mesh=None, opt_cfg: Optional[OptConfig] = None,
               microbatches: int = 1, use_planner_loss: bool = False,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
               keep: int = 3, seed: int = 0,
               log_every: int = 10,
               warmup_plans: bool = False) -> Dict[str, Any]:
    cfg = model.cfg
    opt_cfg = opt_cfg or OptConfig(total_steps=steps)
    if warmup_plans:
        from repro.core.plan import get_plan_cache
        ws = warmup_kernel_plans(model, seq)
        store = get_plan_cache().store_stats()["store"]
        print(f"[train] plan warmup: {ws['solved']} solved, "
              f"{ws['hits']} already cached "
              f"(store: {store.get('backend')}, "
              f"{store.get('plans', 0)} plans)")
    data = SyntheticLM(cfg.vocab_size, seq, batch, seed=seed,
                       encdec=cfg.is_encdec, d_model=cfg.d_model,
                       enc_ratio=cfg.enc_ratio)

    params = model.init(jax.random.PRNGKey(seed))
    opt = init_opt_state(params, compression=opt_cfg.grad_compression)
    state = TrainState(params, opt)

    if mesh is not None:
        ax, ab = model.param_axes(), model.abstract_params()
        psh = param_shardings(ax, ab, mesh)
        zsh = zero1_shardings(ax, ab, mesh)
        state = TrainState(
            params=jax.device_put(state.params, psh),
            opt=state.opt._replace(
                m=jax.device_put(state.opt.m, zsh),
                v=jax.device_put(state.opt.v, zsh),
                err=(jax.device_put(state.opt.err, zsh)
                     if state.opt.err is not None else None)))

    start_step = 0
    ckptr = None
    if ckpt_dir:
        ckptr = AsyncCheckpointer(ckpt_dir, keep=keep)
        if latest_step(ckpt_dir) is not None:
            state, start_step, extra = restore_checkpoint(ckpt_dir, state)
            print(f"[train] restored step {start_step} from {ckpt_dir}")

    step_fn = jax.jit(
        make_train_step(model, opt_cfg, mesh, microbatches=microbatches,
                        use_planner_loss=use_planner_loss),
        donate_argnums=(0,))

    mon = StragglerMonitor()
    losses = []
    t_start = time.time()
    for step in range(start_step, steps):
        b = data.batch(step)
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        if mesh is not None:
            jb = {k: jax.device_put(v, batch_sharding(mesh, batch, v.ndim))
                  for k, v in jb.items()}
        mon.start()
        state, metrics = step_fn(state, jb)
        loss = float(metrics["loss"])
        straggler = mon.stop(step)
        losses.append(loss)
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step {step:5d} loss {loss:8.4f} "
                  f"gnorm {float(metrics['grad_norm']):8.3f} "
                  f"lr {float(metrics['lr']):.2e}"
                  + (" STRAGGLER" if straggler else ""), flush=True)
        if ckptr and (step + 1) % ckpt_every == 0:
            ckptr.save(step + 1, state)
    if ckptr:
        ckptr.save(steps, state)
        ckptr.wait()
        if ckptr.errors:
            raise RuntimeError(f"checkpoint errors: {ckptr.errors}")
    wall = time.time() - t_start
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "wall_s": wall, "straggler_events": mon.events,
            "steps_done": steps - start_step}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--planner-loss", action="store_true")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", choices=["none", "host", "production",
                                       "production-multi"], default="none")
    ap.add_argument("--plan-cache", default=None, metavar="DIR",
                    help="mapping-plan store directory "
                         "(default: $REPRO_PLAN_CACHE or ~/.cache/repro-plans)")
    ap.add_argument("--warmup-plans", action="store_true",
                    help="pre-solve kernel block-selection plans at startup")
    args = ap.parse_args()

    if args.plan_cache:
        os.environ["REPRO_PLAN_CACHE"] = args.plan_cache
    enable_compile_cache()
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = Model(cfg)
    mesh = None
    if args.mesh == "host":
        mesh = make_host_mesh()
    elif args.mesh.startswith("production"):
        mesh = make_production_mesh(multi_pod=args.mesh.endswith("multi"))
    out = train_loop(
        model, steps=args.steps, batch=args.batch, seq=args.seq, mesh=mesh,
        opt_cfg=OptConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(1, args.steps // 10),
                          grad_compression=args.grad_compression),
        microbatches=args.microbatches, use_planner_loss=args.planner_loss,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        warmup_plans=args.warmup_plans)
    print(json.dumps({"final_loss": out["final_loss"],
                      "wall_s": round(out["wall_s"], 1),
                      "steps": out["steps_done"]}))


if __name__ == "__main__":
    main()
