"""End-to-end behaviour tests: training driver (with checkpoint restart),
serving engine (continuous batching), and decode/prefill consistency for
the stateful families."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import Model


def test_train_loop_end_to_end(tmp_path):
    from repro.launch.train import train_loop
    from repro.train.optimizer import OptConfig
    model = Model(get_smoke_config("phi4-mini-3.8b"))
    out = train_loop(model, steps=12, batch=4, seq=48,
                     opt_cfg=OptConfig(lr=2e-3, total_steps=12,
                                       warmup_steps=2),
                     ckpt_dir=str(tmp_path / "ck"), ckpt_every=5,
                     log_every=100)
    assert out["steps_done"] == 12
    assert np.isfinite(out["final_loss"])
    # restart continues from the checkpoint
    out2 = train_loop(model, steps=14, batch=4, seq=48,
                      ckpt_dir=str(tmp_path / "ck"), ckpt_every=5,
                      log_every=100)
    assert out2["steps_done"] == 2      # 12 -> 14 only


def test_serve_engine_continuous_batching():
    from repro.serve.engine import Request, ServeEngine
    cfg = get_smoke_config("glm4-9b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=5)
        for i in range(7)]                      # 7 requests through 3 slots
    eng = ServeEngine(model, params, batch_size=3, cache_len=48,
                      prompt_len=16, plan_warmup=False)
    done = eng.run(reqs)
    assert all(len(r.output) == 5 for r in done)
    assert eng.stats["tokens_out"] == 35
    # slots are reused, but refilled slots ARE re-prefilled (batched per
    # step): 7 requests through 3 slots in same-length waves = 3 prefills
    assert eng.stats["prefill_calls"] == 3


@pytest.mark.parametrize("poison", [False, True])
def test_serve_engine_counts_nonfinite_tokens(poison):
    """argmax over a NaN logits row still returns an in-vocabulary id, so
    the engine counts every token taken from a non-finite row."""
    from repro.serve.engine import Request, ServeEngine
    cfg = get_smoke_config("glm4-9b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if poison:
        params = jax.tree.map(lambda x: x * jnp.nan if jnp.issubdtype(
            x.dtype, jnp.floating) else x, params)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, size=12).astype(np.int32), max_new_tokens=3)
        for i in range(2)]
    eng = ServeEngine(model, params, batch_size=2, cache_len=32,
                      prompt_len=16, plan_warmup=False)
    done = eng.run(reqs)
    assert all(len(r.output) == 3 for r in done)
    assert eng.stats["tokens_out"] == 6
    assert eng.stats["nonfinite_tokens"] == (6 if poison else 0)


def test_serve_engine_refill_matches_serial_decoding():
    """Regression for the continuous-batching bug: slots refilled
    mid-decode used to inherit the previous occupant's KV cache and last
    token (never prefilled).  With the queue exceeding batch_size, every
    request's output must match decoding it alone through a 1-slot
    engine."""
    from repro.serve.engine import Request, ServeEngine
    cfg = get_smoke_config("glm4-9b")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, size=12).astype(np.int32)
               for _ in range(7)]
    # staggered lengths so slots free at different steps (un-batched
    # refills as well as the same-step batched case)
    new_tokens = [5, 3, 4, 6, 2, 5, 3]
    reqs = [Request(rid=i, prompt=p.copy(), max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, new_tokens))]
    eng = ServeEngine(model, params, batch_size=3, cache_len=48,
                      prompt_len=16, plan_warmup=False)
    eng.run(reqs)

    one = ServeEngine(model, params, batch_size=1, cache_len=48,
                      prompt_len=16, plan_warmup=False)
    for i, (p, n) in enumerate(zip(prompts, new_tokens)):
        ref = Request(rid=100 + i, prompt=p.copy(), max_new_tokens=n)
        one.run([ref])
        assert reqs[i].output == ref.output, f"request {i} diverged"


def test_ssm_decode_equals_prefill_continuation():
    """Mamba-2: decoding one token after prefill == full-seq forward."""
    cfg = get_smoke_config("mamba2-130m")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 24
    toks = jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab_size,
                       jnp.int32)
    full = model.logits(params, {"tokens": toks}).astype(jnp.float32)
    _, cache = model.prefill(params, {"tokens": toks[:, :S - 1]},
                             cache_len=S + 2)
    lg, _ = model.decode(params, cache, toks[:, S - 1:S])
    np.testing.assert_allclose(np.asarray(lg[:, 0].astype(jnp.float32)),
                               np.asarray(full[:, S - 1]), atol=3e-2,
                               rtol=3e-2)


def test_hybrid_window_ring_buffer():
    """Hymba: decode beyond the window uses the ring buffer correctly —
    prediction must match the teacher-forced forward at every step."""
    cfg = get_smoke_config("hymba-1.5b").with_(window=8)
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 20
    toks = jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab_size,
                       jnp.int32)
    full = model.logits(params, {"tokens": toks}).astype(jnp.float32)
    _, cache = model.prefill(params, {"tokens": toks[:, :12]}, cache_len=24)
    for t in range(12, 16):
        lg, cache = model.decode(params, cache, toks[:, t:t + 1])
        np.testing.assert_allclose(
            np.asarray(lg[:, 0].astype(jnp.float32)),
            np.asarray(full[:, t]), atol=5e-2, rtol=5e-2)


def test_mla_absorbed_decode_matches_forward():
    """DeepSeek MLA: the absorbed (latent-space) decode must agree with the
    decompressed training attention.  f32 so the check is exact (the two
    paths contract in different orders; bf16 noise is checked loosely by
    the per-arch smoke test instead)."""
    cfg = get_smoke_config("deepseek-v3-671b").with_(dtype="float32")
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    toks = jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab_size,
                       jnp.int32)
    full = model.logits(params, {"tokens": toks}).astype(jnp.float32)
    _, cache = model.prefill(params, {"tokens": toks[:, :S - 1]},
                             cache_len=S + 2)
    lg, _ = model.decode(params, cache, toks[:, S - 1:S])
    np.testing.assert_allclose(np.asarray(lg[:, 0].astype(jnp.float32)),
                               np.asarray(full[:, S - 1]), atol=1e-4,
                               rtol=1e-4)
