"""Fused GEMM→Softmax Pallas kernel (the paper's GEMM-SM compound op,
Fused-GEMM-distSM dataflow adapted to one TPU core).

C = softmax(A @ B, axis=-1).  The K contraction streams through VMEM in
block_k tiles accumulating into a VMEM f32 scratch (the OB-level K loop of
the COMET mapping); the softmax epilogue runs on the VPU at the final K
step while the full N row is still VMEM-resident — the intermediate C
tensor never touches HBM, which is precisely the fusion the paper costs.

Requires block_m * N * 4B to fit VMEM (validated by autotune).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import VMEM_BUDGET, gemm_epilogue_blocks

__all__ = ["gemm_softmax"]


def _kernel(a_ref, b_ref, o_ref, acc):
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
        m = jnp.max(c, axis=1, keepdims=True)          # Op3 rowmax
        e = jnp.exp(c - m)                             # Op4/Op5 sub+exp
        s = jnp.sum(e, axis=1, keepdims=True)          # Op6 rowsum
        o_ref[...] = (e / s).astype(o_ref.dtype)       # Op7 div


def gemm_softmax(a: jax.Array, b: jax.Array, *,
                 block_m: Optional[int] = None,
                 block_k: Optional[int] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """softmax(a @ b, axis=-1); a: (M, K), b: (K, N)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
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

    out = pl.pallas_call(
        _kernel,
        grid=(Mp // block_m, Kp // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda mi, ki: (mi, ki)),
            pl.BlockSpec((block_k, N), lambda mi, ki: (ki, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, N), lambda mi, ki: (mi, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(ap, bp)
    return out[:M] if pm else out
