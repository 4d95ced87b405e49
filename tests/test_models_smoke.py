"""Per-architecture smoke tests: reduced config, one forward/train step on
CPU, asserting output shapes + no NaNs; plus prefill/decode consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, cells_for, get_config, get_smoke_config
from repro.models import Model

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32):
    b = {"tokens": jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab_size,
                               jnp.int32),
         "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.is_encdec:
        b["src_embeds"] = jnp.ones((B, max(1, S // cfg.enc_ratio),
                                    cfg.d_model), jnp.float32)
    return b


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg)
    logits = model.logits(params, batch)
    assert logits.shape == (2, 32, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())
    # one real train step
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.train_step import TrainState, make_train_step
    step = make_train_step(model, OptConfig(lr=1e-3, total_steps=10))
    state = TrainState(params, init_opt_state(params))
    state, metrics = step(state, batch)
    assert bool(jnp.isfinite(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_prefill_decode_matches_forward(arch):
    """Greedy decode after prefill must equal the teacher-forced forward
    logits at the same positions (causal consistency)."""
    cfg = get_smoke_config(arch)
    model = Model(cfg)
    params = model.init(KEY)
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    full = model.logits(params, batch).astype(jnp.float32)

    pre_batch = {k: (v[:, :S - 2] if k != "src_embeds" else v)
                 for k, v in batch.items() if k != "labels"}
    logits_p, cache = model.prefill(params, pre_batch, cache_len=S + 4)
    np.testing.assert_allclose(np.asarray(logits_p[:, -1].astype(jnp.float32)),
                               np.asarray(full[:, S - 3]), atol=6e-2,
                               rtol=6e-2)
    # decode the next token with the true continuation
    lg, cache = model.decode(params, cache, batch["tokens"][:, S - 2:S - 1])
    np.testing.assert_allclose(np.asarray(lg[:, 0].astype(jnp.float32)),
                               np.asarray(full[:, S - 2]), atol=6e-2,
                               rtol=6e-2)


# dense GQA, MoE GQA, the SSD, MLA with a leading dense stack, and a hybrid
# whose window of 4 wraps its ring within the steps decoded
STAGGERED_FAMILIES = {"glm4-9b": {}, "qwen3-moe-30b-a3b": {},
                      "mamba2-130m": {}, "deepseek-v3-671b": {},
                      "hymba-1.5b": {"window": 4}}


@pytest.mark.parametrize("arch", list(STAGGERED_FAMILIES))
def test_decode_at_staggered_row_positions_matches_forward(arch):
    """Rows prefilled to different depths, spliced into one batch, then
    decoded for several steps: each row's logits equal the teacher-forced
    forward at that row's own position, so each layer's write lands at its
    own row and ring slot of the stacked cache and reads back only there."""
    cfg = get_smoke_config(arch).with_(dtype="float32",
                                       **STAGGERED_FAMILIES[arch])
    model = Model(cfg)
    params = model.init(KEY)
    depths, steps, T, cache_len = (5, 9, 7), 4, 16, 16
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (len(depths), T)), jnp.int32)
    full = np.asarray(model.logits(params, {"tokens": toks}))
    rows = [model.prefill(params, {"tokens": toks[r:r + 1, :s]}, cache_len)[1]
            for r, s in enumerate(depths)]
    cache = {k: (jnp.concatenate([c[k] for c in rows]) if k == "pos" else
                 jax.tree.map(lambda *a: jnp.concatenate(a, axis=1),
                              *[c[k] for c in rows]))
             for k in rows[0]}
    # the forward's chunked SSD takes bf16 inputs (ssm._ssd_scan) where
    # decode's recurrence stays f32: 2e-2 of gap there, against 0.65 and
    # more for a state or K/V row written to another row or ring slot
    tol = 5e-2 if cfg.has_ssm else 2e-3
    at = np.asarray(depths)
    for _ in range(steps):
        lg, cache = model.decode(params, cache, toks[np.arange(len(at)),
                                                     at][:, None])
        want = full[np.arange(len(at)), at]
        np.testing.assert_allclose(np.asarray(lg[:, 0]), want, atol=tol,
                                   rtol=tol)
        at = at + 1
    np.testing.assert_array_equal(np.asarray(cache["pos"]), at)


def test_full_configs_match_assignment():
    """The full configs carry the exact dims from the assignment table."""
    spec = {
        "chameleon-34b": (48, 8192, 64, 8, 22016, 65536),
        "phi4-mini-3.8b": (32, 3072, 24, 8, 8192, 200064),
        "minitron-4b": (32, 3072, 24, 8, 9216, 256000),
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
        "seamless-m4t-medium": (12, 1024, 16, 16, 4096, 256206),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
    }
    for arch, (L, d, H, kv, ff, V) in spec.items():
        c = get_config(arch)
        assert (c.n_layers, c.d_model, c.n_heads, c.n_kv_heads,
                c.d_ff, c.vocab_size) == (L, d, H, kv, ff, V), arch
    ds = get_config("deepseek-v3-671b")
    assert (ds.n_layers, ds.d_model, ds.n_heads, ds.n_experts, ds.top_k,
            ds.moe_d_ff, ds.vocab_size) == (61, 7168, 128, 256, 8, 2048, 129280)
    qw = get_config("qwen3-moe-30b-a3b")
    assert (qw.n_layers, qw.d_model, qw.n_experts, qw.top_k, qw.moe_d_ff,
            qw.vocab_size) == (48, 2048, 128, 8, 768, 151936)
    mb = get_config("mamba2-130m")
    assert (mb.n_layers, mb.d_model, mb.ssm_state, mb.vocab_size) == \
        (24, 768, 128, 50280)


def test_cells_follow_brief():
    """long_500k only for sub-quadratic archs; all archs have 3 base cells."""
    for a in ARCH_IDS:
        cells = cells_for(a)
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(cells)
        if a in ("mamba2-130m", "hymba-1.5b"):
            assert "long_500k" in cells
        else:
            assert "long_500k" not in cells
    total = sum(len(cells_for(a)) for a in ARCH_IDS)
    assert total == 32   # 40-cell table minus 8 noted long_500k skips


def test_moe_routing_conservation():
    """Top-k gates are normalized and dispatch preserves token mass for
    tokens under capacity."""
    cfg = get_smoke_config("qwen3-moe-30b-a3b")
    model = Model(cfg)
    params = model.init(KEY)
    from repro.models.moe import moe_apply, router_weights
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 8, cfg.d_model),
                          jnp.bfloat16)
    layer0 = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    gates, idx = router_weights(cfg, layer0, x.reshape(8, cfg.d_model))
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-3)
    assert int(idx.max()) < cfg.n_experts
    out = moe_apply(cfg, layer0, x)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())


@pytest.mark.parametrize("arch", ["glm4-9b", "hymba-1.5b", "mamba2-130m"])
def test_pallas_kernel_path_in_model(arch):
    """use_kernels=True routes attention/SSD through the Pallas kernels
    (interpret mode on CPU) and must match the reference path closely."""
    cfg = get_smoke_config(arch).with_(dtype="float32", window=None)
    model_ref = Model(cfg)
    model_k = Model(cfg.with_(use_kernels=True))
    params = model_ref.init(KEY)
    B, S = 1, 128   # S >= kernel block size
    toks = jnp.asarray(np.arange(B * S).reshape(B, S) % cfg.vocab_size,
                       jnp.int32)
    ref = model_ref.logits(params, {"tokens": toks}).astype(jnp.float32)
    out = model_k.logits(params, {"tokens": toks}).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-3, rtol=5e-3)
