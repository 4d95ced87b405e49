import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the very first two lines: jax locks the device count on first
# init.  512 virtual host devices realize the 2x16x16 production mesh.

# Multi-pod dry-run (deliverable e).
#
# For every (architecture × input-shape × mesh) cell:
#     jax.jit(step).lower(*abstract_inputs).compile()
# must succeed on the single-pod 16×16 mesh AND the 2×16×16 multi-pod mesh.
# We record memory_analysis(), cost_analysis() and the parsed collective
# traffic into artifacts/dryrun/<arch>__<shape>__<mesh>.json for §Dry-run /
# §Roofline.
#
# Usage:
#     python -m repro.launch.dryrun --arch glm4-9b --shape train_4k --mesh single
#     python -m repro.launch.dryrun --all [--mesh both] [--skip-existing]

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _mesh(kind: str):
    from repro.launch.mesh import make_production_mesh
    return make_production_mesh(multi_pod=(kind == "multi"))


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             *, opt_flags: Optional[Dict[str, Any]] = None,
             tag: str = "", calibrated: Optional[str] = None,
             overlap: float = 1.0) -> Dict[str, Any]:
    """Lower + compile one cell; returns the artifact dict.

    ``calibrated`` points at a ``repro.calibrate`` store (``True`` for
    the default plan-store root): when a valid calibration loads, the
    roofline's collective term is charged at the measured-and-fitted
    channel bandwidth instead of the datasheet link constant, and the
    artifact records which calibration was applied.

    ``overlap`` is the achievable compute-collective overlap factor for
    the overlap-adjusted roofline bound (the serial bound is always
    reported alongside; see ``roofline_terms``)."""
    import jax.numpy as jnp
    from repro.analysis import (parse_collectives, reconcile_cell,
                                roofline_terms, trace_counts)
    from repro.configs.registry import SHAPES, get_config
    from repro.launch import specs as S
    from repro.models.model import Model
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import make_train_step

    t0 = time.time()
    mesh = _mesh(mesh_kind)
    cfg = get_config(arch)
    STEP_FLAGS = ("planner_loss", "microbatches")
    for k, v in (opt_flags or {}).items():
        if k not in STEP_FLAGS:
            cfg = cfg.with_(**{k: v})
    shape = SHAPES[shape_name]
    model = Model(cfg)
    rec: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag,
        "kind": shape.kind, "devices": mesh.devices.size,
        "params": model.n_params(), "active_params": cfg.n_active_params(),
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
    }

    with mesh:
        if shape.kind == "train":
            state_ab, state_sh = S.state_specs(model, mesh)
            batch_ab = S.batch_specs(cfg, shape, mesh)
            step = make_train_step(
                model, OptConfig(), mesh,
                microbatches=int((opt_flags or {}).get("microbatches", 1)),
                use_planner_loss=(opt_flags or {}).get("planner_loss", False))
            fn = jax.jit(step, donate_argnums=(0,))
            lowered = fn.lower(state_ab, batch_ab)
        elif shape.kind == "prefill":
            params_ab, _ = S.state_specs(model, mesh, with_opt=False)
            batch_ab = S.batch_specs(cfg, shape, mesh, with_labels=False)
            fn = jax.jit(lambda p, b: model.prefill(p, b, shape.seq_len, mesh))
            lowered = fn.lower(params_ab, batch_ab)
        else:  # decode
            params_ab, _ = S.state_specs(model, mesh, with_opt=False)
            cache_ab, _ = S.cache_specs(model, shape, mesh)
            tok = jax.ShapeDtypeStruct(
                (shape.global_batch, 1), jnp.int32,
                sharding=S.batch_sharding(mesh, shape.global_batch, 2))
            fn = jax.jit(lambda p, c, t: model.decode(p, c, t, mesh),
                         donate_argnums=(1,))
            lowered = fn.lower(params_ab, cache_ab, tok)

        compiled = lowered.compile()

    # --- structural FLOPs + explicit collectives from one jaxpr walk
    # (scan-aware; repro.analysis.jaxpr — the launch/*_analysis shims are
    # deprecated)
    jaxpr_trace = None
    try:
        if shape.kind == "train":
            jaxpr_trace = trace_counts(step, state_ab, batch_ab)
        elif shape.kind == "prefill":
            jaxpr_trace = trace_counts(lambda p, b: model.prefill(
                p, b, shape.seq_len, mesh), params_ab, batch_ab)
        else:
            jaxpr_trace = trace_counts(
                lambda p, c, t: model.decode(p, c, t, mesh),
                params_ab, cache_ab, tok)
        sf = jaxpr_trace.flops
        rec["structural_flops_global"] = sf
        rec["structural_flops_per_device"] = sf / mesh.devices.size
        if jaxpr_trace.findings:
            rec["jaxpr_findings"] = list(jaxpr_trace.findings)
    except Exception as e:  # noqa: BLE001
        rec["structural_flops_error"] = repr(e)

    rec["lower_compile_s"] = round(time.time() - t0, 1)
    ca = compiled.cost_analysis() or {}
    rec["cost_analysis"] = {k: float(v) for k, v in ca.items()
                            if isinstance(v, (int, float))
                            and k in ("flops", "bytes accessed",
                                      "optimal_seconds", "utilization")}
    ma = compiled.memory_analysis()
    if ma is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(ma, attr, None)
            if v is not None:
                rec.setdefault("memory_analysis", {})[attr] = int(v)
    hlo = compiled.as_text()
    stats = parse_collectives(hlo)
    rec["collectives"] = stats.to_dict()
    rec["hlo_bytes"] = len(hlo)
    flops_raw = rec["cost_analysis"].get("flops", 0.0)
    bytes_acc = rec["cost_analysis"].get("bytes accessed", 0.0)
    # corrected per-device quantities (see EXPERIMENTS §Roofline "sources"):
    #  - compute: structural jaxpr FLOPs / devices (exact for scans)
    #  - memory: single-pass HBM traffic estimate from memory_analysis
    #    (args+outputs+temps each touched once)
    #  - collective: HLO wire bytes with in-loop ops × layer trip count
    flops_pd = rec.get("structural_flops_per_device", flops_raw)
    mem_traffic = 0.0
    if "memory_analysis" in rec:
        ma_ = rec["memory_analysis"]
        mem_traffic = (ma_.get("argument_size_in_bytes", 0)
                       + ma_.get("output_size_in_bytes", 0)
                       + ma_.get("temp_size_in_bytes", 0))
    wire_pd_hlo = stats.wire_bytes_scaled(cfg.n_layers)
    # --- jaxpr-vs-HLO reconciliation (repro.analysis.reconcile): compare
    # the walker's explicit collectives + the declared GSPMD schedule
    # against the HLO text parse; the roofline charges the RECONCILED
    # volumes (never undercharging), with disagreements surfaced as
    # findings in the artifact.
    schedule = None
    if shape.kind == "train":
        try:
            from repro.parallel.collective_planner import (
                train_collective_schedule)
            schedule = train_collective_schedule(
                cfg, mesh, shape.global_batch, shape.seq_len,
                microbatches=int((opt_flags or {}).get("microbatches", 1)),
                planner_loss=bool(
                    (opt_flags or {}).get("planner_loss", False)))
        except Exception as e:  # noqa: BLE001 — declaration gap, not fatal
            rec["schedule_error"] = repr(e)
    recon = reconcile_cell(jaxpr_trace, stats, schedule=schedule,
                           loop_trip=cfg.n_layers)
    rec["reconcile"] = recon.to_dict()
    wire_pd = recon.total_reconciled_wire
    rec["mem_traffic_per_device"] = mem_traffic
    rec["collective_wire_per_device"] = wire_pd
    rec["collective_wire_hlo_per_device"] = wire_pd_hlo
    link_bw = None
    if calibrated:
        from repro.calibrate import calibration_path, load_calibration
        cal_path = (calibration_path() if calibrated is True
                    else calibration_path(calibrated))
        cal = load_calibration(cal_path)
        if cal is not None:
            link_bw = cal.params.channel_bandwidth
            rec["calibration"] = {
                "path": str(cal_path),
                "backend": cal.provenance.get("backend"),
                "channel_bandwidth": link_bw,
                "median_rel_err": cal.median_rel_err,
            }
        else:
            rec["calibration"] = {"path": str(cal_path), "loaded": False}
    rec["roofline"] = roofline_terms(flops_pd, mem_traffic, wire_pd,
                                     link_bw=link_bw, overlap=overlap)
    rec["roofline_raw_hlo"] = roofline_terms(flops_raw, bytes_acc,
                                             stats.total_wire_bytes)
    # MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE); decode: D = batch
    # tokens per step
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        factor = 6.0
    elif shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        factor = 2.0
    else:
        tokens = shape.global_batch
        factor = 2.0
    model_flops_global = factor * cfg.n_active_params() * tokens
    rec["model_flops_global"] = model_flops_global
    rec["model_flops_per_device"] = model_flops_global / mesh.devices.size
    if rec.get("structural_flops_per_device"):
        rec["useful_flop_ratio"] = (rec["model_flops_per_device"]
                                    / rec["structural_flops_per_device"])

    # --- static contract verdict (repro.analysis.contracts): trace the
    # sharded softmax path on THIS cell's mesh and audit its collective
    # schedule against the planner's declaration — the roofline numbers
    # above are only trustworthy if the cost model and the traced program
    # agree on what goes over the wire.
    from repro.analysis.contracts import sharded_contract_checks
    try:
        dp = 1
        for ax in ("pod", "data"):
            if ax in mesh.axis_names:
                dp *= int(mesh.shape[ax])
        p_model = int(mesh.shape.get("model", 1))
        cchecks = sharded_contract_checks(
            mesh, batch=2 * dp, seq=16, d_model=64, vocab_p=128 * p_model)
        rec["contracts"] = {
            "checked": len(cchecks),
            "failures": [c.to_dict() for c in cchecks if not c.ok],
            "ok": all(c.ok for c in cchecks),
        }
    except Exception as e:  # noqa: BLE001 — verdict must not sink the cell
        rec["contracts"] = {"error": repr(e), "ok": False}
    return rec


def artifact_path(arch: str, shape: str, mesh_kind: str, tag: str = "") -> str:
    os.makedirs(ARTIFACT_DIR, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    return os.path.join(ARTIFACT_DIR,
                        f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--tag", default="")
    ap.add_argument("--calibrated", nargs="?", const=True, default=None,
                    metavar="STORE",
                    help="charge the roofline's collective term at the "
                         "calibrated channel bandwidth from STORE (default: "
                         "the plan-store root) instead of the datasheet "
                         "link constant")
    ap.add_argument("--overlap", type=float, default=1.0,
                    help="achievable compute-collective overlap for the "
                         "overlap-adjusted roofline bound (default 1.0; "
                         "the serial bound is always reported too)")
    ap.add_argument("--opt", default="",
                    help="comma k=v model-config overrides (hillclimb)")
    args = ap.parse_args()
    if not 0.0 <= args.overlap <= 1.0:
        ap.error("--overlap must lie in [0, 1]")

    opt_flags: Dict[str, Any] = {}
    for kv in filter(None, args.opt.split(",")):
        k, v = kv.split("=")
        if v.lower() in ("true", "false"):
            opt_flags[k] = v.lower() == "true"
        elif v.lstrip("-").isdigit():
            opt_flags[k] = int(v)
        else:
            try:
                opt_flags[k] = float(v)
            except ValueError:
                opt_flags[k] = v

    from repro.configs.registry import all_cells
    cells = all_cells() if args.all else [(args.arch, args.shape)]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    failures = []
    for arch, shape in cells:
        for mk in meshes:
            path = artifact_path(arch, shape, mk, args.tag)
            if args.skip_existing and os.path.exists(path):
                print(f"skip {path}")
                continue
            try:
                rec = run_cell(arch, shape, mk, opt_flags=opt_flags,
                               tag=args.tag, calibrated=args.calibrated,
                               overlap=args.overlap)
                with open(path, "w") as f:
                    json.dump(rec, f, indent=1)
                r = rec["roofline"]
                c = rec.get("contracts", {})
                verdict = ("contracts=ok" if c.get("ok")
                           else f"contracts=FAIL({len(c.get('failures', []))}"
                                f"{' ' + c['error'] if 'error' in c else ''})")
                nrf = len(rec.get("reconcile", {}).get("findings", []))
                verdict += (" recon=clean" if nrf == 0
                            else f" recon={nrf} findings")
                print(f"OK  {arch:22s} {shape:12s} {mk:6s} "
                      f"compile={rec['lower_compile_s']:7.1f}s "
                      f"bottleneck={r['bottleneck']:10s} "
                      f"t=({r['t_compute_s']:.3e},{r['t_memory_s']:.3e},"
                      f"{r['t_collective_s']:.3e})s "
                      f"serial={r['bound_serial_s']:.3e}s "
                      f"ov{r['overlap']:g}={r['bound_overlap_s']:.3e}s"
                      f"({r['bottleneck_overlap']}) {verdict}", flush=True)
            except Exception as e:  # noqa: BLE001 — sweep must continue
                failures.append((arch, shape, mk, repr(e)))
                print(f"FAIL {arch} {shape} {mk}: {e}", flush=True)
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} failures:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
