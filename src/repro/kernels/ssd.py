"""Mamba-2 SSD (state-space duality) chunk-scan Pallas kernel.

The SSD chunked algorithm is itself a compound operation (three GEMMs +
decay-mask SIMD ops per chunk — see core/workload.py::ssd_chunk), so COMET
models its dataflow and picks the chunk length.  TPU adaptation: the chunk
is the VMEM-resident tile; intra-chunk terms use the MXU; the inter-chunk
state (N × P, f32) is carried in VMEM scratch across the sequential chunk
grid dimension.

y_t = C_t · h_t,   h_t = exp(dA_t) · h_{t-1} + B_t ⊗ xdt_t

Inputs (per flattened batch*heads row):
  xdt (BH, S, P)  dA (BH, S)  B (BH, S, N)  C (BH, S, N)

The within-chunk cumulative log decay is computed outside the kernel and
handed in twice, as a column (BH, S, 1) and as rows (BH, nc, 1, chunk):
both block shapes meet Mosaic's tiling rule (the last two block dims are
multiples of (8, 128) or equal the array's), and the kernel needs no
in-VMEM cumsum or (c, 1) -> (1, c) relayout.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import VMEM_BUDGET, ssd_chunk_len

__all__ = ["ssd_scan_fwd", "ssd_scan"]


def _ssd_kernel(xdt_ref, cs_col_ref, cs_row_ref, b_ref, c_ref, y_ref, h_scr,
                *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = xdt_ref[0].astype(jnp.float32)                 # (c, P)
    csr = cs_col_ref[0]                                # (c, 1) cumsum of dA
    csc = cs_row_ref[0, 0]                             # (1, c) same, as a row
    bmat = b_ref[0].astype(jnp.float32)                # (c, N)
    cmat = c_ref[0].astype(jnp.float32)                # (c, N)
    # chunk total cs[c-1] as a (1, 1) reduction: a lane slice of csc keeps
    # its lane offset, which Mosaic cannot broadcast down the (c, 1) column
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    total = jnp.sum(jnp.where(last, csc, 0.0), axis=1, keepdims=True)

    # intra-chunk: (C B^T * L) @ X with L[i,j] = exp(cs_i - cs_j) for i>=j
    logl = csr - csc                                   # (c, c)
    i_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    j_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmask = i_idx >= j_idx
    cb = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    cb = jnp.where(lmask, cb * jnp.exp(logl), 0.0)
    y_intra = jax.lax.dot_general(cb, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    # inter-chunk: C_t · h_prev decayed to position t
    h = h_scr[...]                                     # (N, P)
    y_inter = jax.lax.dot_general(cmat, h, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(csr)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = exp(total) h + (B * exp(total - cs))^T @ X
    decay_in = jnp.exp(total - csr)                    # (c, 1)
    h_scr[...] = jnp.exp(total) * h + jax.lax.dot_general(
        bmat * decay_in, x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def ssd_scan_fwd(xdt: jax.Array, dA: jax.Array, B: jax.Array, C: jax.Array,
                 *, chunk: Optional[int] = None,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Pallas forward. Shapes: xdt (BH,S,P), dA (BH,S), B/C (BH,S,N)."""
    BH, S, P = xdt.shape
    N = B.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    chunk = min(chunk or ssd_chunk_len(S, P, N), S)
    pad = (-S) % chunk
    if pad:
        xdt = jnp.pad(xdt, ((0, 0), (0, pad), (0, 0)))
        dA = jnp.pad(dA, ((0, 0), (0, pad)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nc = Sp // chunk
    cs = jnp.cumsum(dA.astype(jnp.float32).reshape(BH, nc, chunk), axis=-1)
    cs_col = cs.reshape(BH, Sp, 1)
    cs_row = cs.reshape(BH, nc, 1, chunk)

    out = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=chunk),
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sp, P), xdt.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
    )(xdt, cs_col, cs_row, B, C)
    return out[:, :S] if pad else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ssd_scan(xdt, dA, B, C, chunk=None, interpret=None):
    """SSD chunk scan with recompute-based backward (custom_vjp over the
    chunked jnp reference)."""
    return ssd_scan_fwd(xdt, dA, B, C, chunk=chunk, interpret=interpret)


def _ssd_fwd(xdt, dA, B, C, chunk, interpret):
    return ssd_scan_fwd(xdt, dA, B, C, chunk=chunk, interpret=interpret), \
        (xdt, dA, B, C)


def _ssd_bwd(chunk, interpret, res, g):
    from .ref import ssd_chunked_ref
    xdt, dA, B, C = res
    ck = chunk or 64
    # pad to chunk multiple for the reference
    S = xdt.shape[1]
    pad = (-S) % ck
    if pad:
        def f(x_, d_, b_, c_):
            xp = jnp.pad(x_, ((0, 0), (0, pad), (0, 0)))
            dp = jnp.pad(d_, ((0, 0), (0, pad)))
            bp = jnp.pad(b_, ((0, 0), (0, pad), (0, 0)))
            cp = jnp.pad(c_, ((0, 0), (0, pad), (0, 0)))
            return ssd_chunked_ref(xp, dp, bp, cp, chunk=ck)[:, :S]
    else:
        def f(x_, d_, b_, c_):
            return ssd_chunked_ref(x_, d_, b_, c_, chunk=ck)
    _, vjp = jax.vjp(f, xdt, dA, B, C)
    return vjp(g)


ssd_scan.defvjp(_ssd_fwd, _ssd_bwd)
