"""Fused all-gather-then-GEMM Pallas kernel with double-buffered gather.

C = all_gather(X, axis=K) @ W.  The gathered operand never materializes
in VMEM as a whole: the kernel streams it chunk-by-chunk (one chunk per
source shard) through a two-slot VMEM buffer with explicit async DMA —
the copy of gather chunk *i+1* is in flight while the MXU contracts
chunk *i*, which is exactly the compute–collective overlap the cost
model's ``overlap`` factor charges (``core/cost.py``): the chunk
transfer time (Eq. 1 MemLat) hides under the dependency-adjacent GEMM,
and only the per-chunk enqueue/issue cost (Eq. 3) stays exposed.

Two layers:

* :func:`streamed_gemm` — the Pallas kernel proper.  X lives in
  HBM/ANY; each K chunk of X and W is DMA'd into a ``buffers``-slot VMEM
  scratch and accumulated into an f32 VMEM accumulator.  ``buffers=2``
  (default) prefetches chunk *i+1* during the chunk-*i* matmul;
  ``buffers=1`` serializes copy → compute per chunk — the unoverlapped
  baseline the microbenchmark (``benchmarks/overlap_bench.py``) compares
  against to measure the *achieved* hidden fraction on real hardware.
* :func:`allgather_gemm` — the shard_map entry point: gathers the
  K-sharded activation with ``jax.lax.all_gather`` and streams the
  result through the kernel.  On a multi-chip TPU mesh the gather chunks
  arrive per-shard over ICI (ring all-gather), so the chunked DMA stream
  models the per-step shard arrival; the remote-DMA ring fusion
  (``make_async_remote_copy``) is the real-mesh follow-up noted in
  ROADMAP.md.

Correctness oracle: :func:`allgather_gemm_reference`
(``shard_map(all_gather) + dot``), pinned by ``tests/test_kernels.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .autotune import VMEM_BUDGET

# has_side_effects only guards the DMA-only kernel body against DCE; the
# output here data-depends on every copy.
_COMPILER_PARAMS = pltpu.CompilerParams(has_side_effects=True,
                                        vmem_limit_bytes=VMEM_BUDGET)

# Static VMEM budget envelope: the (M, K, N, chunks) configurations the
# tests and the overlap microbenchmark drive the kernel with.  The
# ``vmem-budget`` lint (analysis/lint.py) evaluates the scratch shapes
# below against these (both buffer counts) at the arch GB capacity, so
# growing a config here without headroom fails CI statically.
BUDGET_SHAPES = (
    (256, 4096, 512, 8),   # overlap_bench.measure_hidden_fraction scale
    (128, 1024, 256, 8),   # overlap_bench.measure_double_buffer
    (128, 512, 256, 4),    # test_kernels streamed-GEMM cases (largest)
    (256, 4096, 512, 4),   # chip_smoke.py --chips 4 (one chunk per chip)
)
__all__ = ["streamed_gemm", "allgather_gemm", "allgather_gemm_reference"]


def _kernel(x_hbm, w_hbm, o_ref, x_buf, w_buf, acc, x_sem, w_sem, *,
            n_chunks: int, kc: int, nbuf: int):
    """Accumulate sum_c X[:, c*kc:(c+1)*kc] @ W[c*kc:(c+1)*kc, :] with the
    chunk DMA stream double-buffered against the MXU when ``nbuf == 2``."""

    def x_copy(slot, c):
        return pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(c * kc, kc)], x_buf.at[slot], x_sem.at[slot])

    def w_copy(slot, c):
        return pltpu.make_async_copy(
            w_hbm.at[pl.ds(c * kc, kc), :], w_buf.at[slot], w_sem.at[slot])

    acc[...] = jnp.zeros_like(acc)

    if nbuf == 2:
        # warm-up: start the first gather chunk before entering the loop
        x_copy(0, 0).start()
        w_copy(0, 0).start()

        def body(c, carry):
            slot = jax.lax.rem(c, 2)
            nxt = 1 - slot

            # gather chunk c+1 overlaps the matmul on chunk c
            @pl.when(c + 1 < n_chunks)
            def _prefetch():
                x_copy(nxt, c + 1).start()
                w_copy(nxt, c + 1).start()

            x_copy(slot, c).wait()
            w_copy(slot, c).wait()
            acc[...] += jnp.dot(x_buf[slot], w_buf[slot],
                                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, n_chunks, body, None)
    else:
        # single-buffered baseline: copy chunk c, wait, compute — the
        # fully exposed (serial) charging of the same chunk stream
        def body(c, carry):
            x_copy(0, c).start()
            w_copy(0, c).start()
            x_copy(0, c).wait()
            w_copy(0, c).wait()
            acc[...] += jnp.dot(x_buf[0], w_buf[0],
                                preferred_element_type=jnp.float32)
            return carry

        jax.lax.fori_loop(0, n_chunks, body, None)

    o_ref[...] = acc[...].astype(o_ref.dtype)


def streamed_gemm(x: jax.Array, w: jax.Array, *, chunks: int,
                  buffers: int = 2,
                  interpret: Optional[bool] = None) -> jax.Array:
    """x @ w with the K contraction streamed in ``chunks`` DMA chunks
    (one per gather shard); ``buffers=2`` double-buffers the stream.

    Requires ``K % chunks == 0`` (the all-gather entry always satisfies
    this: K = participants x local shard).  Working set: ``buffers`` X
    and W chunk slots plus the (M, N) f32 accumulator must fit VMEM —
    callers pick chunk counts accordingly (``analysis/lint.py`` budgets
    the scratch shapes below statically).
    """
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    if K % chunks != 0:
        raise ValueError(f"chunks={chunks} must divide K={K}")
    if buffers not in (1, 2):
        raise ValueError(f"buffers must be 1 or 2, got {buffers}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    kc = K // chunks

    return pl.pallas_call(
        functools.partial(_kernel, n_chunks=chunks, kc=kc, nbuf=buffers),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((buffers, M, kc), x.dtype),
            pltpu.VMEM((buffers, kc, N), w.dtype),
            pltpu.VMEM((M, N), jnp.float32),
            pltpu.SemaphoreType.DMA((buffers,)),
            pltpu.SemaphoreType.DMA((buffers,)),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(x, w)


def allgather_gemm(x_shard: jax.Array, w: jax.Array, *, axis_name: str,
                   buffers: int = 2,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Fused all-gather-then-GEMM under ``shard_map``: gather the
    K-sharded activation ``x_shard`` (M, K/P) over ``axis_name`` and
    contract the gathered (M, K) against the replicated ``w`` (K, N),
    streaming one chunk per source shard through the double-buffered
    kernel.  Numerically matches :func:`allgather_gemm_reference` up to
    f32 accumulation order."""
    p = jax.lax.psum(1, axis_name)
    xg = jax.lax.all_gather(x_shard, axis_name, axis=1, tiled=True)
    return streamed_gemm(xg, w, chunks=p, buffers=buffers,
                         interpret=interpret)


def allgather_gemm_reference(x_shard: jax.Array, w: jax.Array, *,
                             axis_name: str) -> jax.Array:
    """Unfused oracle: materialize the all-gather, then one dot."""
    xg = jax.lax.all_gather(x_shard, axis_name, axis=1, tiled=True)
    return jnp.dot(xg, w, preferred_element_type=jnp.float32).astype(
        x_shard.dtype)
