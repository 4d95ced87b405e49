"""Fused GEMM→LayerNorm / GEMM→RMSNorm Pallas kernel (the paper's GEMM-LN
compound op, Fused-GEMM-distLN dataflow on one TPU core).

Y = LayerNorm(A @ B) * gamma + beta (or RMSNorm variant).  Same structure
as the GEMM-SM kernel: K streams through VMEM accumulating in f32 scratch,
the normalization epilogue (the paper's Op2..Op8 SIMD chain — more
elementary ops than softmax, hence the larger fusion win) runs on the VPU
at the final K step.  The intermediate C never reaches HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import VMEM_BUDGET, gemm_epilogue_blocks

__all__ = ["gemm_layernorm", "gemm_rmsnorm"]


def _kernel(a_ref, b_ref, g_ref, beta_ref, o_ref, acc, *, eps: float,
            rms: bool):
    ki = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    acc[...] += jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _epilogue():
        c = acc[...]
        g = g_ref[...].astype(jnp.float32)             # (1, N)
        if rms:
            ms = jnp.mean(c * c, axis=1, keepdims=True)     # Op5 var (rms)
            y = c * jax.lax.rsqrt(ms + eps)                 # Op6/7
            o_ref[...] = (y * g).astype(o_ref.dtype)        # Op8 affine
        else:
            mu = jnp.mean(c, axis=1, keepdims=True)         # Op2 mean
            d = c - mu                                      # Op3 sub
            var = jnp.mean(d * d, axis=1, keepdims=True)    # Op4/5 sq+var
            y = d * jax.lax.rsqrt(var + eps)                # Op6/7
            bt = beta_ref[...].astype(jnp.float32)
            o_ref[...] = (y * g + bt).astype(o_ref.dtype)   # Op8 affine


def _fused_gemm_norm(a, b, gamma, beta, *, eps, rms, block_m, block_k,
                     interpret):
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 and gamma.shape == (N,)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bm_d, bk_d = gemm_epilogue_blocks(M, N, K)
    block_m = min(block_m or bm_d, M)
    block_k = min(block_k or bk_d, K)

    pm = (-M) % block_m
    pk = (-K) % block_k
    ap = jnp.pad(a, ((0, pm), (0, pk))) if (pm or pk) else a
    bp = jnp.pad(b, ((0, pk), (0, 0))) if pk else b
    Mp, Kp = M + pm, K + pk
    g2 = gamma.reshape(1, N)
    beta2 = (beta if beta is not None else jnp.zeros_like(gamma)).reshape(1, N)

    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps, rms=rms),
        grid=(Mp // block_m, Kp // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ki: (mi, ki)),
            pl.BlockSpec((block_k, N), lambda mi, ki: (ki, 0)),
            pl.BlockSpec((1, N), lambda mi, ki: (0, 0)),
            pl.BlockSpec((1, N), lambda mi, ki: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, N), lambda mi, ki: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(ap, bp, g2, beta2)
    return out[:M] if pm else out


def gemm_layernorm(a: jax.Array, b: jax.Array, gamma: jax.Array,
                   beta: jax.Array, *, eps: float = 1e-6,
                   block_m: Optional[int] = None,
                   block_k: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """LayerNorm(a @ b) * gamma + beta;  a: (M, K), b: (K, N)."""
    return _fused_gemm_norm(a, b, gamma, beta, eps=eps, rms=False,
                            block_m=block_m, block_k=block_k,
                            interpret=interpret)


def gemm_rmsnorm(a: jax.Array, b: jax.Array, gamma: jax.Array, *,
                 eps: float = 1e-6,
                 block_m: Optional[int] = None,
                 block_k: Optional[int] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """RMSNorm(a @ b) * gamma;  a: (M, K), b: (K, N)."""
    return _fused_gemm_norm(a, b, gamma, None, eps=eps, rms=True,
                            block_m=block_m, block_k=block_k,
                            interpret=interpret)
