"""CLI: ``python -m repro.calibrate [--backend=cpu|synthetic] ...``.

Runs one calibration pass (sweep -> fit -> gate -> persist) and prints a
summary.  ``--backend=cpu`` times real ``jax.lax`` collectives on the
forced 8-virtual-device CPU mesh; ``--backend=synthetic`` generates
timings from the reference preset's own NoC constants (optionally
jittered) so the whole loop runs without touching jax — the CI fit gate.

The persisted ``calibrated_noc.json`` lands in the plan-store root
(``$REPRO_PLAN_CACHE`` / ``~/.cache/repro-plans``, or ``--store``).
Re-running with matching provenance reuses it: ``fits_solved: 0``, file
untouched, bit-identical store.  Exit status: 0 on a passing gate,
1 when the fitted model misses its own sweep by more than
``--gate-median``, 2 when the sweep degrades to a degenerate fit.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from repro.core import hardware

from .driver import calibrate_once
from .harness import (SweepConfig, _replace_mesh, jax_measure_fn,
                      synthetic_measure_fn)

PRESETS = {"edge": hardware.edge, "cloud": hardware.cloud,
           "tpu_v5e": hardware.tpu_v5e, "tileflow_like": hardware.tileflow_like}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.calibrate",
        description="Measure collectives, fit NoCParams, persist with "
                    "provenance")
    ap.add_argument("--backend", choices=("cpu", "synthetic"),
                    default="cpu",
                    help="cpu: time real jax.lax collectives on the forced "
                         "8-virtual-device mesh; synthetic: analytic "
                         "generator from the reference preset (no jax)")
    ap.add_argument("--arch", choices=sorted(PRESETS), default="tpu_v5e",
                    help="preset whose cluster NoC seeds the fit's "
                         "reference (channel width, enqueue split)")
    ap.add_argument("--store", default=None,
                    help="store root (default: plan-store resolution)")
    ap.add_argument("--force", action="store_true",
                    help="re-sweep even when a matching calibration exists")
    ap.add_argument("--min-bytes", type=int, default=None)
    ap.add_argument("--max-bytes", type=int, default=None)
    ap.add_argument("--sizes", type=int, default=None,
                    help="log-spaced sizes per collective type")
    ap.add_argument("--iters", type=int, default=None)
    ap.add_argument("--warmup", type=int, default=None)
    ap.add_argument("--jitter", type=float, default=0.0,
                    help="synthetic backend: multiplicative noise bound")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gate-median", type=float, default=0.6,
                    help="max median |relative error| of the fitted model "
                         "on its own sweep")
    ap.add_argument("--json", action="store_true",
                    help="print the summary as one JSON document")
    args = ap.parse_args(argv)

    cfg_kwargs = {k: v for k, v in
                  (("min_bytes", args.min_bytes),
                   ("max_bytes", args.max_bytes),
                   ("n_sizes", args.sizes),
                   ("iters", args.iters),
                   ("warmup", args.warmup)) if v is not None}
    config = SweepConfig(**cfg_kwargs) if cfg_kwargs else None

    reference = PRESETS[args.arch]().cluster_noc
    if args.backend == "cpu":
        # Nothing above imports jax, so the CPU platform and its forced
        # device count are set before the backend starts: on a host with
        # an accelerator the sweep would otherwise time the accelerator
        # and persist it under provenance backend "cpu".
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("XLA_FLAGS",
                              "--xla_force_host_platform_device_count=8")
        import jax
        if jax.devices()[0].platform != "cpu":
            ap.error("--backend=cpu: jax was started on "
                     f"{jax.devices()[0].platform} before this CLI ran")
        n = len(jax.devices())
        reference = _replace_mesh(reference, (1, n))
        measure_fn = jax_measure_fn()
        participants = n
        jax_version = jax.__version__
    else:
        reference = _replace_mesh(reference, (1, 8))
        measure_fn = synthetic_measure_fn(reference, jitter=args.jitter,
                                          seed=args.seed)
        participants = [2, 4, 8]
        jax_version = "synthetic"

    summary = calibrate_once(
        measure_fn, reference, participants,
        backend=args.backend, jax_version=jax_version,
        store=args.store, force=args.force, config=config,
        gate_median=args.gate_median)

    if args.json:
        print(json.dumps(summary, indent=2, sort_keys=True))
    else:
        p = summary["params"]
        print(f"backend={summary['backend']} reused={summary['reused']} "
              f"fits_solved={summary['fits_solved']}")
        print(f"points={summary['n_points']} "
              f"dropped={summary.get('n_dropped', 0)} "
              f"degenerate={summary['degenerate']}")
        print(f"fitted: channel_bandwidth={p['channel_bandwidth']:.4g} B/s  "
              f"t_router={p['t_router']:.4g} s  t_enq={p['t_enq']:.4g} s")
        print(f"rel err: median={summary['median_rel_err']:.3f} "
              f"max={summary['max_rel_err']:.3f} "
              f"(gate median<={summary['gate_median']}) "
              f"-> {'OK' if summary['gate_ok'] else 'FAIL'}")
        print(f"store: {summary['path']}")

    if summary["degenerate"]:
        return 2
    return 0 if summary["gate_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
