"""Per-kernel allclose tests vs the pure-jnp oracles: shape/dtype sweeps in
interpret mode (Pallas kernel body executed on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def _arr(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


# ------------------------------------------------------------ flash attn

@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D", [
    (1, 4, 4, 128, 128, 64),      # MHA square
    (2, 8, 2, 128, 256, 64),      # GQA, kv longer
    (1, 4, 1, 64, 192, 32),       # MQA, ragged seq (padding path)
    (1, 2, 2, 100, 100, 128),     # non-multiple of block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, dtype, causal):
    q = _arr((B, Hq, Sq, D), dtype)
    k = _arr((B, Hkv, Skv, D), dtype)
    v = _arr((B, Hkv, Skv, D), dtype)
    out = ops.flash_attention(q, k, v, causal, None, None, 128, 128, True)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_flash_attention_window():
    q = _arr((1, 4, 256, 64))
    k = _arr((1, 4, 256, 64))
    v = _arr((1, 4, 256, 64))
    out = ops.flash_attention(q, k, v, True, None, 64, 128, 128, True)
    want = ref.attention_ref(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_flash_attention_grads_match_ref():
    q = _arr((1, 2, 128, 32))
    k = _arr((1, 2, 128, 32))
    v = _arr((1, 2, 128, 32))
    f_kernel = lambda *xs: ops.flash_attention(*xs, True, None, None, 128,
                                               128, True).sum()
    f_ref = lambda *xs: ref.attention_ref(*xs, causal=True).sum()
    gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# --------------------------------------------------------- gemm epilogues

@pytest.mark.parametrize("M,K,N,bm,bk", [
    (128, 64, 256, 128, 64),
    (200, 96, 256, 128, 32),      # padding path
    (64, 128, 512, 64, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gemm_softmax_sweep(M, K, N, bm, bk, dtype):
    a = _arr((M, K), dtype)
    b = _arr((K, N), dtype, scale=0.1)
    out = ops.gemm_softmax(a, b, block_m=bm, block_k=bk, interpret=True)
    want = ref.gemm_softmax_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype])


@pytest.mark.parametrize("M,K,N", [(128, 64, 256), (96, 100, 128)])
def test_gemm_layernorm_and_rmsnorm(M, K, N):
    a = _arr((M, K))
    b = _arr((K, N), scale=0.2)
    g = _arr((N,))
    be = _arr((N,))
    out = ops.gemm_layernorm(a, b, g, be, block_m=64, block_k=32,
                             interpret=True)
    want = ref.gemm_layernorm_ref(a, b, g, be)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)
    out = ops.gemm_rmsnorm(a, b, g, block_m=64, block_k=32, interpret=True)
    want = ref.gemm_rmsnorm_ref(a, b, g)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-4)


# ------------------------------------------------------------------- SSD

@pytest.mark.parametrize("BH,S,P,N,chunk", [
    (2, 128, 16, 32, 64),
    (4, 256, 32, 64, 128),
    (1, 200, 16, 32, 64),         # padding path
])
def test_ssd_kernel_sweep(BH, S, P, N, chunk):
    xdt = _arr((BH, S, P))
    dA = -jnp.abs(_arr((BH, S))) * 0.1
    B = _arr((BH, S, N))
    C = _arr((BH, S, N))
    out = ops.ssd_scan(xdt, dA, B, C, chunk, True)
    want = ref.ssd_ref(xdt, dA, B, C)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-3, rtol=2e-3)


def test_ssd_chunked_ref_equals_naive():
    xdt = _arr((2, 256, 16))
    dA = -jnp.abs(_arr((2, 256))) * 0.2
    B = _arr((2, 256, 32))
    C = _arr((2, 256, 32))
    np.testing.assert_allclose(
        np.asarray(ref.ssd_chunked_ref(xdt, dA, B, C, chunk=32)),
        np.asarray(ref.ssd_ref(xdt, dA, B, C)), atol=2e-3, rtol=2e-3)


# -------------------------------------------------------------- autotune

def test_autotune_blocks_fit_vmem():
    from repro.kernels.autotune import (VMEM_BUDGET, attention_blocks,
                                        gemm_epilogue_blocks, ssd_chunk_len)
    for sq, skv, d in [(1024, 1024, 64), (32768, 32768, 128), (1, 32768, 128)]:
        bq, bk = attention_blocks(sq, skv, d)
        ws = (bq * d * 2 + 2 * bk * d * 2 + bq * d * 4 + bq * bk * 4
              + 2 * bq * 128 * 4)
        assert ws * 2 <= VMEM_BUDGET
    # single-pass fused epilogue targets N <= 16384 (the paper's largest);
    # larger N needs the two-pass/distSM mapping, not this kernel.
    for m, n, k in [(512, 4096, 128), (4096, 16384, 4096)]:
        bm, bk = gemm_epilogue_blocks(m, n, k)
        assert (bm * n * 4 + bk * n * 2 + bm * bk * 2 + bm * n * 2) * 2 \
            <= VMEM_BUDGET
    assert ssd_chunk_len(4096, 64, 128) in (128, 256, 512)


# ------------------------------------------------- fused all-gather GEMM

@pytest.mark.parametrize("M,K,N,chunks", [
    (64, 256, 128, 8),
    (128, 512, 256, 4),
    (8, 128, 128, 2),             # tiny M (gather-dominated shape)
])
@pytest.mark.parametrize("buffers", [1, 2])
def test_streamed_gemm_matches_dot(M, K, N, chunks, buffers):
    from repro.kernels import streamed_gemm
    x = _arr((M, K))
    w = _arr((K, N), scale=0.2)
    out = streamed_gemm(x, w, chunks=chunks, buffers=buffers, interpret=True)
    want = jnp.dot(x, w, preferred_element_type=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-4,
                               rtol=2e-5)


def test_streamed_gemm_validates_chunking():
    from repro.kernels import streamed_gemm
    x, w = _arr((16, 100)), _arr((100, 128))
    with pytest.raises(ValueError, match="must divide"):
        streamed_gemm(x, w, chunks=3, interpret=True)
    with pytest.raises(ValueError, match="buffers"):
        streamed_gemm(_arr((16, 128)), _arr((128, 128)), chunks=2, buffers=3,
                      interpret=True)


@pytest.mark.slow
def test_allgather_gemm_matches_reference_on_mesh():
    """Fused double-buffered all-gather-GEMM == shard_map(all_gather)+dot
    on an 8-virtual-device mesh (subprocess: XLA_FLAGS must predate jax)."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.kernels.allgather_gemm import (allgather_gemm,
                                                  allgather_gemm_reference)
        mesh = Mesh(np.array(jax.devices()), ("x",))
        rng = np.random.default_rng(1)
        X = jnp.asarray(rng.standard_normal((64, 512)), jnp.float32)
        W = jnp.asarray(rng.standard_normal((512, 128)), jnp.float32)
        for nbuf in (1, 2):
            fused = jax.shard_map(
                lambda x, w: allgather_gemm(x, w, axis_name="x",
                                            buffers=nbuf),
                mesh=mesh, in_specs=(P(None, "x"), P()),
                out_specs=P(None, None), check_vma=False)
            ref = jax.shard_map(
                lambda x, w: allgather_gemm_reference(x, w, axis_name="x"),
                mesh=mesh, in_specs=(P(None, "x"), P()),
                out_specs=P(None, None), check_vma=False)
            err = float(jnp.abs(fused(X, W) - ref(X, W)).max())
            assert err < 1e-3, (nbuf, err)
            print("AG_GEMM_OK", nbuf, err)
        print("ALL_AG_GEMM_OK")
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(repo, "src")
    env.pop("XLA_FLAGS", None)
    try:
        r = subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=600)
    except (OSError, PermissionError) as e:
        pytest.skip(f"sandbox cannot spawn the 8-device subprocess: {e!r}")
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-3000:]}"
    assert "ALL_AG_GEMM_OK" in r.stdout
