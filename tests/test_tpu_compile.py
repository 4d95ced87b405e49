"""Ahead-of-time compiles for a TPU v5e that is described, not attached:
every main-path Pallas kernel, and the serve engine's prefill and decode
steps at published widths.

Each kernel case lowers a kernel with ``interpret=False`` at the blocks
its mapping plan resolves and compiles it with the chip's own compiler,
so a block the compiler refuses (tiling alignment, scoped VMEM) fails
here and not on the chip; the step cases also check that the program
fits one chip's memory.  Nothing runs: this says nothing about results or
times.  The topology is described inside a fixture, never at import, so
every test worker collects the same tests; where it cannot be described
the fixture skips.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels.allgather_gemm import (BUDGET_SHAPES, allgather_gemm,
                                          streamed_gemm)
from repro.kernels.autotune import PAPER_KERNEL_SHAPES
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.gemm_layernorm import gemm_layernorm
from repro.kernels.gemm_softmax import gemm_softmax
from repro.kernels.ssd import ssd_scan_fwd

# the serve path's prefill self-attention (prompt_len 512, head_dim 128)
SERVE_PREFILL_ATTENTION = (512, 512, 128)


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out entirely."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # compiled, not interpreted
    return compiled


@pytest.mark.parametrize("sq,skv,d", PAPER_KERNEL_SHAPES["attention_blocks"]
                         + [SERVE_PREFILL_ATTENTION])
def test_flash_attention_compiles(one_chip, sq, skv, d):
    q = jax.ShapeDtypeStruct((1, 2, sq, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 1, skv, d), jnp.bfloat16, sharding=one_chip)
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True,
                                                 interpret=False), q, kv, kv)


@pytest.mark.parametrize("kernel", ["softmax", "layernorm"])
@pytest.mark.parametrize("m,n,k", PAPER_KERNEL_SHAPES["gemm_epilogue_blocks"])
def test_gemm_epilogue_compiles(one_chip, kernel, m, n, k):
    def s(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    if kernel == "softmax":
        _compile(lambda a, b: gemm_softmax(a, b, interpret=False),
                 s(m, k), s(k, n))
    else:
        _compile(lambda a, b, g, bt: gemm_layernorm(a, b, g, bt,
                                                    interpret=False),
                 s(m, k), s(k, n), s(n), s(n))


def _compile_ssd(one_chip, s, p, n, chunk=None):
    def sh(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    bh = 4
    _compile(lambda *xs: ssd_scan_fwd(*xs, chunk=chunk, interpret=False),
             sh((bh, s, p)), sh((bh, s), jnp.float32), sh((bh, s, n)),
             sh((bh, s, n)))


@pytest.mark.parametrize("s,p,n", PAPER_KERNEL_SHAPES["ssd_chunk_len"])
def test_ssd_scan_compiles(one_chip, s, p, n):
    _compile_ssd(one_chip, s, p, n)


@pytest.mark.parametrize("chunk", [128, 256])
def test_ssd_scan_compiles_at_chunk(one_chip, chunk):
    """The chunk lengths the autotuner may pick besides 512, at the paper
    shape."""
    (s, p, n), = PAPER_KERNEL_SHAPES["ssd_chunk_len"]
    _compile_ssd(one_chip, s, p, n, chunk)


@pytest.mark.parametrize("m,k,n,chunks", BUDGET_SHAPES)
def test_streamed_gemm_compiles(one_chip, m, k, n, chunks):
    x = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip)
    _compile(lambda x, w: streamed_gemm(x, w, chunks=chunks,
                                        interpret=False), x, w)


@pytest.mark.parametrize("buffers", [1, 2])
def test_allgather_gemm_compiles_on_four_chips(topo, buffers):
    """The ``chip_smoke.py --chips 4`` program: the kernel under
    ``jax.shard_map`` over the 2x2 host, with its all-gather."""
    mesh = Mesh(np.array(topo.devices), ("x",))
    x = jax.ShapeDtypeStruct((256, 4096), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P(None, "x")))
    w = jax.ShapeDtypeStruct((4096, 512), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P()))
    fn = jax.shard_map(
        lambda x, w: allgather_gemm(x, w, axis_name="x", buffers=buffers,
                                    interpret=False),
        mesh=mesh, in_specs=(P(None, "x"), P()), out_specs=P(None, None),
        check_vma=False)
    assert "all-gather" in _compile(fn, x, w).as_text()


# the chip_smoke.py serve shape: phi4-mini-3.8b at its published widths
SERVE_ARCH, SERVE_BATCH, SERVE_PROMPT, SERVE_CACHE = (
    "phi4-mini-3.8b", 4, 512, 1024)
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def serve_shapes(one_chip):
    """Abstract full-width weights and the prefill/decode inputs, on the
    described chip."""
    from repro.configs.registry import get_config
    from repro.models.model import Model

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    cfg = get_config(SERVE_ARCH)
    params = on_chip(Model(cfg).abstract_params())
    tokens = on_chip(jax.ShapeDtypeStruct((SERVE_BATCH, SERVE_PROMPT),
                                          jnp.int32))
    return cfg, params, {"tokens": tokens}


def _engine(cfg, params):
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine
    return ServeEngine(Model(cfg), params, batch_size=SERVE_BATCH,
                       cache_len=SERVE_CACHE, prompt_len=SERVE_PROMPT,
                       plan_warmup=False)


def _fits_one_chip(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < V5E_HBM_BYTES


@pytest.mark.parametrize("use_kernels", [False, True])
def test_serve_prefill_compiles(serve_shapes, monkeypatch, use_kernels):
    """The engine's prefill step at published widths fits one chip; with
    ``use_kernels`` the Pallas FlashAttention is compiled into it."""
    cfg, params, batch = serve_shapes
    # the kernels pick interpret mode from the default backend, which is
    # the CPU in this process: steer them to the chip's path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = _engine(cfg.with_(use_kernels=use_kernels), params)
    compiled = eng.prefill_step.lower(params, batch).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == use_kernels
    assert _fits_one_chip(compiled)


def test_serve_decode_compiles(serve_shapes, one_chip):
    cfg, params, batch = serve_shapes
    eng = _engine(cfg, params)
    _, cache = jax.eval_shape(eng.prefill_step, params, batch)
    cache = dict(cache, pos=jax.ShapeDtypeStruct((SERVE_BATCH,), jnp.int32))
    cache = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=one_chip), cache)
    tok = jax.ShapeDtypeStruct((SERVE_BATCH, 1), jnp.int32, sharding=one_chip)
    assert _fits_one_chip(eng.decode_step.lower(params, cache, tok).compile())
