"""On-chip smoke check: the COMET compound-op kernels and the serve path
on a TPU, through the entry points a user calls.

    python chip_smoke.py             # one chip: kernel phase + serve phase
    python chip_smoke.py --chips 4   # 2x2 host: the multi-chip phase only

One process; it starts no child.  It refuses to run anywhere but a TPU:
on any other platform, or outside a checkout of this repository, it exits
non-zero without printing its last line.

* Kernel phase: every ``PAPER_KERNEL_SHAPES`` entry of FlashAttention,
  GEMM-Softmax, GEMM-LayerNorm and the SSD scan, compiled for the chip
  (``interpret=False``) at the blocks its mapping plan resolves, against
  its ``kernels/ref.py`` oracle computed in f32 at ``highest`` matmul
  precision.
* Serve phase: phi4-mini-3.8b at its published widths with random
  weights drawn from ``--seed``, served through ``launch/serve.py`` twice
  on the same weights and prompts: the default XLA attention path, and
  ``use_kernels=True`` so the Pallas FlashAttention compiles into the
  prefill.
* ``--chips 4``: ``allgather_gemm`` under ``jax.shard_map`` over the four
  chips against ``allgather_gemm_reference``, and the collective
  calibration sweep over the 4-chip mesh, fitted and gated as
  ``python -m repro.calibrate`` gates it.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Every kernel is held to  max|kernel - ref| <= REL_TOL * scale, where
# scale bounds the output's magnitude (see each phase).  Two bf16
# roundings separate kernel from oracle: the kernel's bf16 output (2^-8
# of the scale) and at most one bf16 pass of an f32 MXU operand (2^-8 of
# the scale); 2^-6 is twice their sum.  A wrong block index, mask, scale
# or carried state is off by O(scale).
REL_TOL = 2.0 ** -6

# Serve phase: the XLA and Pallas prefills round each of the 32 layers'
# attention output to bf16 at different points, and XLA runs f32 matmuls
# on the TPU as one bf16 pass; these 2^-8-sized differences compound
# through the residual stream to a few 1e-3 to 1e-2 of the last-position
# logits.  A wrong causal mask, softmax scale or GQA head map differs by
# O(1).  Measured as ||xla - pallas||_2 / ||xla||_2 over the batch.
LOGITS_REL_TOL = 5e-2

SERVE_ARCH = "phi4-mini-3.8b"
SERVE = dict(requests=8, batch=4, prompt_len=512, cache_len=1024,
             max_new=16)


def _line(**kw) -> None:
    print(" ".join(f"{k}={v}" for k, v in kw.items()), flush=True)


def _check(name: str, err: float, tol: float, **info) -> bool:
    ok = bool(err <= tol)
    _line(check=name, max_err=f"{err:.3e}", tol=f"{tol:.3e}",
          ok=ok, **info)
    return ok


# ------------------------------------------------------------ kernel phase


def attention_error(out, q, k, v, rows):
    """(error, tolerance) of the worst causal-attention output row among
    the query-row blocks ``rows`` = [(first row, count)], each row held to
    REL_TOL of its own max|ref|: row i averages about i keys, so with
    random v its size falls like i^-1/2 (about 0.01 at i = 32768), and one
    scale for the whole output would pass a row of zeros.  A NaN row is
    the worst."""
    import jax.numpy as jnp

    from repro.kernels import ref

    f32 = jnp.float32
    sq, skv = q.shape[2], k.shape[2]
    worst = []                           # (err / tol, err, tol) per block
    for i0, n in rows:
        # attention_ref aligns the rows to the end of the keys it is given
        want = ref.attention_ref(
            q[:, :, i0:i0 + n].astype(f32),
            k[:, :, :skv - sq + i0 + n].astype(f32),
            v[:, :, :skv - sq + i0 + n].astype(f32), causal=True)
        e = jnp.max(jnp.abs(out[:, :, i0:i0 + n].astype(f32) - want),
                    axis=-1).ravel()
        tol = REL_TOL * jnp.max(jnp.abs(want), axis=-1).ravel()
        ratio = jnp.nan_to_num(e / tol, nan=jnp.inf)
        i = int(jnp.argmax(ratio))
        worst.append((float(ratio[i]), float(e[i]), float(tol[i])))
    return max(worst)[1:]


def kernel_phase(seed: int) -> bool:
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.autotune import (PAPER_KERNEL_SHAPES, attention_blocks,
                                        gemm_epilogue_blocks, ssd_chunk_len)
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.kernels.gemm_layernorm import gemm_layernorm
    from repro.kernels.gemm_softmax import gemm_softmax
    from repro.kernels.ssd import ssd_scan_fwd

    key = jax.random.PRNGKey(seed)
    f32 = jnp.float32
    bf16 = jnp.bfloat16

    def normal(shape, scale=1.0, dtype=bf16):
        nonlocal key
        key, sub = jax.random.split(key)
        return (jax.random.normal(sub, shape, f32) * scale).astype(dtype)

    def err(out, want):
        return float(jnp.max(jnp.abs(out.astype(f32) - want.astype(f32))))

    ok = True
    hi = jax.default_matmul_precision("highest")

    # FlashAttention, GQA 2 query heads on 1 kv head
    for sq, skv, d in PAPER_KERNEL_SHAPES["attention_blocks"]:
        q = normal((1, 2, sq, d))
        k = normal((1, 1, skv, d))
        v = normal((1, 1, skv, d))
        fa = jax.jit(lambda q, k, v: flash_attention_fwd(
            q, k, v, causal=True, interpret=False))
        out = fa(q, k, v)
        if sq * skv <= 4096 * 4096:
            rows = [(0, sq)]
        else:
            # the full S x S reference would not fit: check query-row
            # blocks at the start, middle and end against the oracle on
            # the causal prefix they see
            r = 256
            rows = [(0, r), (sq // 2 - r // 2, r), (sq - r, r)]
        with hi:
            e, tol = attention_error(out, q, k, v, rows)
        ok &= _check(f"flash_attention{(sq, skv, d)}", e, tol,
                     blocks=attention_blocks(sq, skv, d),
                     rows_checked=sum(n for _, n in rows),
                     tol_per="row")

    # GEMM-Softmax / GEMM-LayerNorm; b scaled so a @ b has unit variance.
    # Softmax rows peak at max|ref|; LayerNorm outputs are bounded by
    # max|ref| too.
    for m, n, kk in PAPER_KERNEL_SHAPES["gemm_epilogue_blocks"]:
        a = normal((m, kk))
        b = normal((kk, n), kk ** -0.5)
        gamma = normal((n,), 0.1) + 1.0
        beta = normal((n,), 0.1)
        blocks = gemm_epilogue_blocks(m, n, kk)
        out = jax.jit(lambda a, b: gemm_softmax(a, b, interpret=False))(a, b)
        with hi:
            want = ref.gemm_softmax_ref(a.astype(f32), b.astype(f32))
        ok &= _check(f"gemm_softmax{(m, n, kk)}", err(out, want),
                     REL_TOL * float(jnp.max(want)), blocks=blocks)
        out = jax.jit(lambda a, b, g, bt: gemm_layernorm(
            a, b, g, bt, interpret=False))(a, b, gamma, beta)
        with hi:
            want = ref.gemm_layernorm_ref(a.astype(f32), b.astype(f32),
                                          gamma.astype(f32),
                                          beta.astype(f32))
        ok &= _check(f"gemm_layernorm{(m, n, kk)}", err(out, want),
                     REL_TOL * float(jnp.max(jnp.abs(want))), blocks=blocks)

    # SSD scan against the naive recurrence; dA in the Mamba-2 range of
    # A * dt (negative, small).  max|ref| is the scale.
    for s, p, n in PAPER_KERNEL_SHAPES["ssd_chunk_len"]:
        bh = 4
        xdt = normal((bh, s, p), 0.1)
        key, sub = jax.random.split(key)
        dA = -jax.random.uniform(sub, (bh, s), f32, 1e-3, 0.1)
        B = normal((bh, s, n), n ** -0.5)
        C = normal((bh, s, n), n ** -0.5)
        out = jax.jit(lambda *xs: ssd_scan_fwd(*xs, interpret=False))(
            xdt, dA, B, C)
        with hi:
            want = ref.ssd_ref(xdt.astype(f32), dA, B.astype(f32),
                               C.astype(f32))
        ok &= _check(f"ssd_scan{(s, p, n)}", err(out, want),
                     REL_TOL * float(jnp.max(jnp.abs(want))),
                     chunk=ssd_chunk_len(s, p, n))
    return ok


# ------------------------------------------------------------- serve phase


def serve_phase(seed: int) -> bool:
    import jax
    import numpy as np

    from repro.configs.registry import get_config
    from repro.launch import serve

    cfg = get_config(SERVE_ARCH)
    dev = jax.devices()[0]
    _line(serve=SERVE_ARCH, n_layers=cfg.n_layers, d_model=cfg.d_model,
          vocab=cfg.vocab_size, **SERVE)
    ok = True
    params = None
    logits = {}
    for path, use_kernels in (("xla", False), ("pallas", True)):
        eng, reqs = serve.build(cfg.with_(use_kernels=use_kernels),
                                seed=seed, params=params, **SERVE)
        if params is None:
            params = eng.params
            n_params = sum(x.size for x in jax.tree.leaves(params))
            n_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
            _line(params=n_params, param_gib=f"{n_bytes / 2**30:.2f}")
        batch = {"tokens": jax.numpy.asarray(
            np.stack([r.prompt for r in reqs[:SERVE["batch"]]]))}
        t0 = time.time()
        compiled = eng.prefill_step.lower(params, batch).compile()
        compile_s = time.time() - t0
        has_kernel = "tpu_custom_call" in compiled.as_text()
        out, _ = compiled(params, batch)
        logits[path] = np.asarray(out[:, -1, :cfg.vocab_size], np.float32)
        summary = serve.run(eng, reqs)
        done = [r for r in reqs if r.done and len(r.output) == SERVE[
            "max_new"] and all(0 <= t < cfg.vocab_size for t in r.output)]
        # every decode step's logits, counted by the engine: argmax over a
        # NaN row still gives an in-vocabulary token
        nonfinite = summary["nonfinite_tokens"]
        _line(path=path, prefill_compile_s=f"{compile_s:.1f}",
              wall_s_with_compile=summary["wall_s"],
              serve_tok_s=summary["serve_tok_s"],
              ttft_p50_ms=summary["ttft_p50_ms"], compiles=summary["compiles"],
              completed=f"{len(done)}/{len(reqs)}",
              tokens=summary["tokens"], nonfinite_tokens=nonfinite,
              tpu_custom_call=has_kernel,
              prefill_logits_finite=bool(np.isfinite(logits[path]).all()),
              peak_bytes_in_use=(dev.memory_stats() or {}).get(
                  "peak_bytes_in_use"))
        ok &= (len(done) == len(reqs) and nonfinite == 0
               and bool(np.isfinite(logits[path]).all())
               and has_kernel == use_kernels)
        del eng, compiled, out
    diff = logits["pallas"] - logits["xla"]
    rel = float(np.linalg.norm(diff) / np.linalg.norm(logits["xla"]))
    ok &= _check("prefill_logits_xla_vs_pallas", rel, LOGITS_REL_TOL,
                 max_abs_diff=f"{float(np.abs(diff).max()):.3e}",
                 argmax_agree=int((logits["xla"].argmax(-1)
                                   == logits["pallas"].argmax(-1)).sum()))
    return ok


# --------------------------------------------------------- multi-chip phase


def multichip_phase(seed: int) -> bool:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.calibrate.harness import jax_measure_fn
    from repro.kernels.allgather_gemm import (allgather_gemm,
                                              allgather_gemm_reference)

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("x",))
    ok = True

    # all-gather-GEMM: X (M, K) sharded over K, W replicated; the output
    # is a K-long bf16 dot, so max|ref| is the scale (REL_TOL as above).
    M, K, N = 256, 4096, 512
    key = jax.random.PRNGKey(seed)
    kx, kw = jax.random.split(key)
    x = jax.device_put(jax.random.normal(kx, (M, K)).astype(jnp.bfloat16),
                       NamedSharding(mesh, P(None, "x")))
    w = jax.device_put((jax.random.normal(kw, (K, N)) * K ** -0.5
                        ).astype(jnp.bfloat16), NamedSharding(mesh, P()))

    def on_mesh(body):
        return jax.jit(jax.shard_map(body, mesh=mesh,
                                     in_specs=(P(None, "x"), P()),
                                     out_specs=P(None, None),
                                     check_vma=False))

    want = on_mesh(lambda x, w: allgather_gemm_reference(
        x, w, axis_name="x"))(x, w).astype(jnp.float32)
    scale = float(jnp.max(jnp.abs(want)))
    for buffers in (1, 2):
        out = on_mesh(lambda x, w: allgather_gemm(
            x, w, axis_name="x", buffers=buffers, interpret=False))(x, w)
        e = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
        ok &= _check(f"allgather_gemm(M={M},K={K},N={N},chips={len(devs)},"
                     f"buffers={buffers})", e, REL_TOL * scale)

    # collective calibration over the 4-chip mesh; the fit lands in
    # chiprun_out/ for reading
    ok &= calibration_check(
        jax_measure_fn(Mesh(np.array(devs), ("cal",))), len(devs),
        backend=devs[0].platform, jax_version=jax.__version__,
        store=str(ROOT / "chiprun_out" / "calibration"))
    return ok


def calibration_check(measure_fn, participants: int, *, backend: str,
                      jax_version: str, store: str) -> bool:
    """Sweep all four collective types with ``measure_fn``, fit, and gate
    as ``python -m repro.calibrate`` does (non-degenerate fit, median
    error at the CLI's default gate).  The sweep drops a point that
    raises or times as NaN and still fits the rest, so this also fails
    on any such drop, and unless every type keeps at least half its
    sizes: a type whose collectives all failed would otherwise vanish
    from a fit that passes."""
    from repro.calibrate.driver import calibrate_once
    from repro.calibrate.harness import (CALIBRATED_TYPES, SweepConfig,
                                         _replace_mesh)
    from repro.core.hardware import tpu_v5e

    reference = _replace_mesh(tpu_v5e().cluster_noc, (1, participants))
    summary = calibrate_once(measure_fn, reference, participants,
                             backend=backend, jax_version=jax_version,
                             store=store, force=True)
    p = summary["params"]
    by_type = {t: summary["points_by_type"].get(t, 0)
               for t in CALIBRATED_TYPES}
    failed = {c: n for c, n in summary["dropped"].items()
              if c in ("error", "not-finite")}
    _line(calibration_points=summary["n_points"],
          points_by_type=json.dumps(by_type, separators=(",", ":")),
          dropped=json.dumps(summary["dropped"], separators=(",", ":")),
          channel_bandwidth=f"{p['channel_bandwidth']:.4g}",
          t_router=f"{p['t_router']:.4g}", t_enq=f"{p['t_enq']:.4g}")
    measured = (not failed
                and min(by_type.values()) >= SweepConfig().n_sizes // 2)
    ok = bool(summary["gate_ok"] and measured)
    _line(check="calibration_fit", ok=ok, gate_ok=summary["gate_ok"],
          median_rel_err=f"{summary['median_rel_err']:.3e}",
          gate_median=summary["gate_median"],
          max_rel_err=f"{summary['max_rel_err']:.3e}",
          degenerate=summary["degenerate"],
          failed_points=sum(failed.values()))
    return ok


# -------------------------------------------------------------------- main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phase on a 2x2 host")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every random input, weight and prompt")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}; run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax
    devs = jax.devices()
    d = devs[0]
    _line(platform=d.platform, device_kind=repr(d.device_kind),
          count=len(devs), jax=jax.__version__)
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {d.platform}",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"found {len(devs)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    _line(compile_cache=enable_compile_cache())

    if args.chips == 4:
        phases = [("multichip", multichip_phase)]
    else:
        phases = [("kernels", kernel_phase), ("serve", serve_phase)]
    ok = True
    for name, phase in phases:
        t0 = time.time()
        passed = phase(args.seed)
        _line(phase=name, ok=passed, seconds=f"{time.time() - t0:.1f}")
        ok &= passed
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
