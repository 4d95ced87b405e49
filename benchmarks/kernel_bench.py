"""Kernel micro-benchmarks: wall-time of the Pallas kernels plus the
autotuned blocks for the paper shapes, so the autotuner's choices are
visible.  The kernels pick their own mode: compiled on a TPU, interpret
mode elsewhere — off the TPU the time is the Pallas interpreter's, not a
device time, and every line names the platform it ran on."""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.kernels.autotune import (attention_blocks, gemm_epilogue_blocks,
                                    ssd_chunk_len)


def _time(fn, *args, iters: int = 3) -> float:
    fn(*args)  # compile/warm
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run_all() -> Dict:
    rng = np.random.default_rng(0)
    out = {"platform": jax.devices()[0].platform}
    print(f"kernel_bench,platform={out['platform']}")

    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    us = _time(lambda a, b, c: ops.flash_attention(a, b, c, True, None, None,
                                                   128, 128), q, k, v)
    bq, bk = attention_blocks(4096, 4096, 128)
    print(f"pallas_flash_attention,{us:.0f},autotuned_blocks=({bq}x{bk})@4k")
    out["fa_us"] = us

    a = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(128, 512)) / 10, jnp.float32)
    us = _time(lambda x, y: ops.gemm_softmax(x, y, block_m=128, block_k=128),
               a, b)
    bm, bkk = gemm_epilogue_blocks(4096, 4096, 4096)
    print(f"pallas_gemm_softmax,{us:.0f},autotuned_blocks=({bm}x{bkk})@4k3")
    out["gemm_sm_us"] = us

    g = jnp.asarray(rng.normal(size=(512,)), jnp.float32)
    be = jnp.asarray(rng.normal(size=(512,)), jnp.float32)
    us = _time(lambda x, y: ops.gemm_layernorm(x, y, g, be, block_m=128,
                                               block_k=128), a, b)
    print(f"pallas_gemm_layernorm,{us:.0f},fused_epilogue")
    out["gemm_ln_us"] = us

    BH, S, P, N = 4, 256, 32, 64
    xdt = jnp.asarray(rng.normal(size=(BH, S, P)), jnp.float32)
    dA = -jnp.abs(jnp.asarray(rng.normal(size=(BH, S)), jnp.float32)) * 0.1
    Bm = jnp.asarray(rng.normal(size=(BH, S, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(BH, S, N)), jnp.float32)
    us = _time(lambda *xs: ops.ssd_scan(*xs, 64), xdt, dA, Bm, Cm)
    ck = ssd_chunk_len(4096, 64, 128)
    print(f"pallas_ssd_scan,{us:.0f},autotuned_chunk={ck}@4k")
    out["ssd_us"] = us
    return out


if __name__ == "__main__":
    run_all()
