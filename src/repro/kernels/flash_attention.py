"""FlashAttention Pallas TPU kernel (the paper's §V-D2 'FA' dataflow).

TPU-native adaptation of the FlashAttention compound-op dataflow studied by
COMET: Q rows stay resident in VMEM (block_q tile), K^T/V stream through
VMEM in block_k tiles (the GB-level temporal N loop of the mapping tree),
online softmax runs on the VPU, and both GEMMs hit the MXU.  The extra
non-GEMM work (running-max merge, accumulator rescale) is exactly the
paper's observed SIMD-latency increase for FA.

Block sizes default to the COMET-autotuned values (kernels/autotune.py).

Grid: (batch*q_heads, q_blocks, kv_blocks), kv innermost (sequential /
'arbitrary' dimension semantics so the scratch carry is legal on TPU).
GQA is handled in the K/V index_map (q head -> kv head).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import VMEM_BUDGET, attention_blocks

__all__ = ["flash_attention_fwd", "flash_attention"]

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, acc, m_scr, l_scr, *,
               scale: float, causal: bool, window: Optional[int],
               block_q: int, block_k: int, sq: int, skv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    # token positions (q aligned to the END of the kv axis, decode-friendly)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0) \
        + (skv - sq)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)

    def _body():
        q = q_ref[0].astype(jnp.float32)               # (bq, d)
        k = k_ref[0].astype(jnp.float32)               # (bk, d)
        v = v_ref[0].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = k_pos < skv                            # mask padded keys
        if causal:
            mask &= q_pos >= k_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...][:, :1]                     # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)      # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)                # rescale factor
        p = jnp.exp(s - m_new)                         # (bq, bk)
        l_new = alpha[:, 0] * l_scr[...][:, 0] + jnp.sum(p, axis=1)
        acc[...] = acc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new[:, None], l_scr.shape)

    if causal:
        # skip blocks strictly above the diagonal
        first_q = qi * block_q + (skv - sq)
        last_k = ki * block_k
        pl.when(last_k <= first_q + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...][:, :1]
        l = jnp.where(l == 0.0, 1.0, l)                # fully-masked rows -> 0
        o_ref[0] = (acc[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        window: Optional[int] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """Pallas forward.  q: (B, Hq, Sq, D); k/v: (B, Hkv, Skv, D)."""
    B, Hq, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bq_d, bk_d = attention_blocks(Sq, Skv, D)
    block_q = block_q or bq_d
    block_k = block_k or bk_d
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)

    # pad sequence dims to block multiples
    pq = (-Sq) % block_q
    pk = (-Skv) % block_k
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0))) if pk else v
    Sqp, Skp = Sq + pq, Skv + pk

    qr = qp.reshape(B * Hq, Sqp, D)
    kr = kp.reshape(B * Hkv, Skp, D)
    vr = vp.reshape(B * Hkv, Skp, D)

    def kv_map(bh, qi, ki):
        b = bh // Hq
        h = bh % Hq
        return (b * Hkv + h // group, ki, 0)

    grid = (B * Hq, Sqp // block_q, Skp // block_k)
    out = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q, block_k=block_k,
                          sq=Sq, skv=Skv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), kv_map),
            pl.BlockSpec((1, block_k, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sqp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(qr, kr, vr)
    out = out.reshape(B, Hq, Sqp, D)
    return out[:, :, :Sq] if pq else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=True, scale=None, window=None,
                    block_q=None, block_k=None, interpret=None):
    """FlashAttention with a recompute-based backward (custom_vjp): the
    forward is the Pallas kernel; the backward recomputes attention with the
    jnp reference formula (FlashAttention-style recomputation instead of
    storing the S/P matrices)."""
    return flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                               window=window, block_q=block_q,
                               block_k=block_k, interpret=interpret)


def _fa_fwd(q, k, v, causal, scale, window, block_q, block_k, interpret):
    out = flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                              window=window, block_q=block_q,
                              block_k=block_k, interpret=interpret)
    return out, (q, k, v)


def _fa_bwd(causal, scale, window, block_q, block_k, interpret, res, g):
    from .ref import attention_ref
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal,
                                         scale=scale, window=window),
        q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
