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


def _on(sharding, tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


def _fits_one_chip(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < V5E_HBM_BYTES


def _updates_cache_in_place(compiled, cache):
    """The decode step writes into the donated cache: its output aliases
    the cache, and its temporaries hold less than half of it (a layer scan
    that takes the stacked cache as ``xs`` and gives it back as ``ys``
    holds a second whole cache, and more)."""
    ma = compiled.memory_analysis()
    size = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    return (ma.alias_size_in_bytes >= size
            and ma.temp_size_in_bytes < size // 2)


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
    compiled = eng.decode_step.lower(params, cache, tok).compile()
    assert _fits_one_chip(compiled)
    assert _updates_cache_in_place(compiled, cache)


# the mamba2.chat benchmark cell: 128 slots of 256-token prompts; a refill
# of a few slots prefills the 4-row bucket
REFILL_ARCH, REFILL_BATCH, REFILL_PROMPT, REFILL_CACHE, REFILL_ROWS = (
    "mamba2-130m", 128, 256, 1280, 4)


def test_refill_prefills_a_bucket_and_splices_in_place(one_chip):
    """At published widths, the 4-row refill prefill fits one chip, and
    the splice that writes its rows into the 128-slot state (2.4 GB of
    f32 SSD state) aliases its output to the donated state: no second
    copy of the state is allocated at a refill."""
    from repro.configs.registry import get_config
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine

    cfg = get_config(REFILL_ARCH)
    params = _on(one_chip, Model(cfg).abstract_params())
    eng = ServeEngine(Model(cfg), params, batch_size=REFILL_BATCH,
                      cache_len=REFILL_CACHE, prompt_len=REFILL_PROMPT,
                      plan_warmup=False)
    assert eng.bucket(2) == REFILL_ROWS

    def tokens(n):
        return {"tokens": jax.ShapeDtypeStruct((n, REFILL_PROMPT), jnp.int32,
                                               sharding=one_chip)}

    bucket = tokens(REFILL_ROWS)
    assert _fits_one_chip(eng.prefill_step.lower(params, bucket).compile())
    logits, fresh = _on(one_chip, jax.eval_shape(eng.prefill_step, params,
                                                 bucket))
    _, live = _on(one_chip, jax.eval_shape(eng.prefill_step, params,
                                           tokens(REFILL_BATCH)))
    last = jax.ShapeDtypeStruct((REFILL_BATCH,), jnp.int32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((REFILL_ROWS,), jnp.int32, sharding=one_chip)
    ma = eng.splice_rows.lower(live, last, logits, fresh,
                               idx).compile().memory_analysis()
    state = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(live))
    assert state > 2 * 10**9
    assert ma.alias_size_in_bytes >= state + REFILL_BATCH * 4
    assert ma.output_size_in_bytes - ma.alias_size_in_bytes < state // 100
    assert ma.temp_size_in_bytes < state // 100


def test_decode_updates_the_ssm_state_in_place(one_chip):
    """At the mamba2.chat cell's 128 slots, the decode step reads and
    writes the 2.4 GB of f32 SSD state in place, layer by layer."""
    from repro.configs.registry import get_config
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine

    cfg = get_config(REFILL_ARCH)
    params = _on(one_chip, Model(cfg).abstract_params())
    eng = ServeEngine(Model(cfg), params, batch_size=REFILL_BATCH,
                      cache_len=REFILL_CACHE, prompt_len=REFILL_PROMPT,
                      plan_warmup=False)
    cache = _on(one_chip, jax.eval_shape(
        lambda: Model(cfg).init_cache(REFILL_BATCH, REFILL_CACHE)))
    tok = jax.ShapeDtypeStruct((REFILL_BATCH, 1), jnp.int32,
                               sharding=one_chip)
    assert _updates_cache_in_place(
        eng.decode_step.lower(params, cache, tok).compile(), cache)


# the deepseek-v3.chat cell: one chip's EP32 share of DeepSeek-V3 (8.66 GB
# of bf16 weights) at 64 slots of 256-token prompts, a 1280-position cache
EP32_ARCH, EP32_BATCH, EP32_PROMPT, EP32_CACHE = (
    "deepseek-v3-ep32", 64, 256, 1280)


def test_expert_share_fills_and_decodes_on_one_chip(one_chip):
    """At published widths, the first fill of all 64 slots (the MLA
    prefill decompresses K and V for 16384 tokens) and the decode step
    of the 7-layer chip share each fit one chip's memory."""
    from repro.configs.registry import get_config
    from repro.models.model import Model
    from repro.serve.engine import ServeEngine

    cfg = get_config(EP32_ARCH)
    params = _on(one_chip, Model(cfg).abstract_params())
    eng = ServeEngine(Model(cfg), params, batch_size=EP32_BATCH,
                      cache_len=EP32_CACHE, prompt_len=EP32_PROMPT,
                      plan_warmup=False)
    batch = {"tokens": jax.ShapeDtypeStruct((EP32_BATCH, EP32_PROMPT),
                                            jnp.int32, sharding=one_chip)}
    assert _fits_one_chip(eng.prefill_step.lower(params, batch).compile())
    _, cache = _on(one_chip, jax.eval_shape(eng.prefill_step, params, batch))
    tok = jax.ShapeDtypeStruct((EP32_BATCH, 1), jnp.int32, sharding=one_chip)
    compiled = eng.decode_step.lower(params, cache, tok).compile()
    assert _fits_one_chip(compiled)
    assert _updates_cache_in_place(compiled, cache)
