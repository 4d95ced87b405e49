"""DeepSeek-V3 at a chip's expert-parallel share, at smoke widths on the CPU
in float32: the program against the benchmark's float32 reference
(``bench/reference/deepseek_v3.py``) and a transcription of the published
router, YaRN against the plain rope it leaves alone, routing that does not
depend on the batch, and the shares of an expert layer against the uncut
layer."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import deepseek_v3 as ref
from bench.reference import mm_f32
from repro.models import moe as moe_mod
from repro.models.attention import _mla_scale
from repro.models.config import ModelConfig
from repro.models.layers import rope_cos_sin
from repro.models.model import Model

# the published router counts and rope settings at smoke widths; 8 of the
# 256 experts held, as on a chip of EP32
SMOKE = {
    "name": "deepseek-v3-smoke", "reference": "deepseek_v3",
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "q_lora_rank": 32, "kv_lora_rank": 16,
    "moe_intermediate_size": 16, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "vocab_size": 512,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "published": {"n_routed_experts": 256},
}


def _cfg(conf=SMOKE, **kw) -> ModelConfig:
    return ModelConfig(name=conf["name"], family="moe", attn_type="mla",
                       dtype="float32", **ref.program_fields(conf)).with_(**kw)


def _params(cfg, seed=0, bias=0.05):
    """Weights of ``cfg`` with a nonzero correction bias in every MoE
    layer (the load-balancing state a deployment would load)."""
    params = Model(cfg).init(jax.random.PRNGKey(seed))
    L = cfg.n_layers - cfg.first_dense_layers
    params["layers"]["moe"]["router_bias"] = bias * jax.random.normal(
        jax.random.PRNGKey(seed + 1), (L, cfg.n_experts))
    return params


def _ref_logits(conf, params, toks):
    with jax.default_matmul_precision("highest"):
        h = ref.hidden(conf, params, toks, mm_f32)
        return np.asarray(mm_f32(h, ref.head(conf, params)))


def test_prefill_then_decode_agrees_with_the_reference():
    """The chip-share program (8 of 256 experts held, router over all 256
    with a nonzero correction bias, YaRN) through prefill and then the
    latent cache gives the reference's logits at every position."""
    cfg = _cfg()
    assert (cfg.n_experts, cfg.experts_held, cfg.rope_factor) == (256, 8, 40)
    model, params = Model(cfg), _params(cfg)
    B, S, P = 2, 14, 9
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S), 0, cfg.vocab_size)
    want = _ref_logits(SMOKE, params, toks)[..., :cfg.vocab_size]
    logits, cache = model.prefill(params, {"tokens": toks[:, :P]}, cache_len=S)
    got = [logits[:, 0]]
    for t in range(P, S):
        logits, cache = model.decode(params, cache, toks[:, t:t + 1])
        got.append(logits[:, 0])
    got = np.stack([np.asarray(g) for g in got], 1)[..., :cfg.vocab_size]
    np.testing.assert_allclose(got, want[:, P - 1:], atol=2e-4, rtol=2e-4)
    # the held experts were routed to, so their part is in the comparison
    x = jax.random.normal(jax.random.PRNGKey(2), (64, cfg.d_model))
    layer0 = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    _, idx = moe_mod.router_weights(cfg, layer0, x)
    assert 0 < int((idx < cfg.experts_held).sum()) < idx.size


def _noaux_tc(logits, bias, n_group, topk_group, top_k, scaling):
    """DeepSeek-V3's ``Gate.forward`` (its inference code), row by row in
    numpy: sigmoid scores, biased for the choice; groups scored by their
    best two; the best groups kept; top-k in them; unbiased scores of the
    chosen, normalised and scaled."""
    gates, idx = [], []
    for row in logits:
        scores = 1 / (1 + np.exp(-row.astype(np.float64)))
        biased = scores + bias
        groups = biased.reshape(n_group, -1)
        group_scores = np.sort(groups, axis=-1)[:, -2:].sum(-1)
        kept = np.argsort(-group_scores, kind="stable")[:topk_group]
        mask = np.ones(n_group, bool)
        mask[kept] = False
        masked = np.where(mask[:, None], -np.inf, groups).reshape(-1)
        chosen = np.argsort(-masked, kind="stable")[:top_k]
        w = scores[chosen]
        gates.append(w / w.sum() * scaling)
        idx.append(chosen)
    return np.array(gates), np.array(idx)


def test_group_limited_router_is_the_published_noaux_tc():
    cfg = _cfg()
    rng = np.random.default_rng(0)
    T, d, E = 40, cfg.d_model, cfg.n_experts
    p = {"router": jnp.asarray(rng.normal(size=(d, E)) / math.sqrt(d), jnp.float32),
         "router_bias": jnp.asarray(rng.normal(size=E) * 0.05, jnp.float32)}
    x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        logits = np.asarray(x @ p["router"])
        gates, idx = moe_mod.router_weights(cfg, p, x)
        rgates, ridx = ref.route(p, x, 8, 4, 8, 2.5, mm_f32)
    want_g, want_i = _noaux_tc(logits, np.asarray(p["router_bias"]), 8, 4, 8, 2.5)
    for got_g, got_i in ((gates, idx), (rgates, ridx)):
        order = np.argsort(np.asarray(got_i), -1)
        np.testing.assert_array_equal(np.take_along_axis(np.asarray(got_i), order, -1),
                                      np.sort(want_i, -1))
        np.testing.assert_allclose(
            np.take_along_axis(np.asarray(got_g), order, -1),
            np.take_along_axis(want_g, np.argsort(want_i, -1), -1), rtol=1e-5)
    # every token's experts lie in at most topk_group groups, and the
    # gates add up to the routed scaling
    assert (np.array([len(set(r // 32)) for r in np.asarray(idx)]) <= 4).all()
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-5)


def test_yarn_frequencies_and_temperature_are_the_published_ones():
    """At the published rope settings YaRN keeps the first 10 of the 32
    frequencies, divides those from 23 on by 40 and ramps between, and
    multiplies the softmax scale by (0.1 ln 40 + 1)^2."""
    cfg = ModelConfig(attn_type="mla", head_dim=128, rope_head_dim=64,
                      rope_factor=40.0)
    cos, sin = rope_cos_sin(jnp.ones((1,), jnp.int32), 64, cfg)
    freqs = np.arctan2(np.asarray(sin[0]), np.asarray(cos[0]))
    base = 1.0 / 10000.0 ** (np.arange(32) / 32)
    conf = dict(SMOKE, qk_rope_head_dim=64, qk_nope_head_dim=128)
    np.testing.assert_allclose(freqs, ref.yarn_freqs(conf), rtol=1e-5)
    np.testing.assert_allclose(freqs[:11], base[:11], rtol=1e-5)
    np.testing.assert_allclose(freqs[23:], base[23:] / 40, rtol=1e-5)
    assert (freqs[11:23] < base[11:23]).all() and (freqs[11:23] > base[11:23] / 40).all()
    mscale2 = (0.1 * math.log(40) + 1) ** 2
    assert _mla_scale(cfg) == pytest.approx(mscale2 / math.sqrt(192))
    assert _mla_scale(cfg) == pytest.approx(ref.softmax_scale(conf))


def test_without_scaling_the_rope_and_scale_are_bit_identical():
    """A configuration without YaRN (every other family, and MLA with
    ``rope_factor`` 1) computes exactly what it computed before YaRN
    was written: the plain frequencies and 1/sqrt(width)."""
    pos = jnp.arange(300, dtype=jnp.int32)
    half = 64
    freqs = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[..., None] * freqs
    plain = ModelConfig(attn_type="mla", head_dim=128, rope_head_dim=64)
    for got in (rope_cos_sin(pos, 128, ModelConfig()),
                rope_cos_sin(pos, 128, plain)):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(jnp.cos(ang)))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(jnp.sin(ang)))
    assert _mla_scale(plain) == 1.0 / math.sqrt(192)


def test_a_rows_logits_do_not_depend_on_what_shares_its_batch():
    """Dropless routing: a prompt prefilled beside seven others gives the
    logits it gives alone, however many of the batch's tokens pick the
    same experts (capacity-bounded dispatch dropped some of them)."""
    cfg = _cfg(n_experts_held=0)              # every expert held
    model, params = Model(cfg), _params(cfg, seed=3)
    toks = jax.random.randint(jax.random.PRNGKey(7), (8, 16), 0, cfg.vocab_size)
    batch, _ = model.prefill(params, {"tokens": toks}, cache_len=16)
    for r in (0, 5):
        alone, _ = model.prefill(params, {"tokens": toks[r:r + 1]}, cache_len=16)
        np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(batch[r]),
                                   atol=1e-5, rtol=1e-5)
    # one expert taking most of a batch's tokens keeps every one of them
    layer0 = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    skewed = dict(layer0, router_bias=jnp.zeros(cfg.n_experts).at[3].set(10.0))
    x = jax.random.normal(jax.random.PRNGKey(8), (4, 64, cfg.d_model))
    y = moe_mod.moe_apply(cfg, skewed, x)
    for r in range(4):
        np.testing.assert_allclose(
            np.asarray(moe_mod.moe_apply(cfg, skewed, x[r:r + 1])[0]),
            np.asarray(y[r]), atol=1e-5, rtol=1e-5)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """The 32 shares of 8 experts each, with the shared expert counted
    once, give what the uncut layer of 256 experts gives, in the program
    and in the reference; share 0 is what the chip-share layer computes."""
    uncut = _cfg(n_experts_held=0)
    params = _params(uncut, seed=4)
    p = jax.tree.map(lambda a: a[0], params["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, uncut.d_model))
    x2d = x.reshape(-1, uncut.d_model)
    with jax.default_matmul_precision("highest"):
        whole = moe_mod.moe_apply(uncut, p, x).reshape(x2d.shape)
        gates, idx = moe_mod.router_weights(uncut, p, x2d)
        shares = [moe_mod.moe_local(x2d, gates, idx, p["wi"][e:e + 8],
                                    p["wg"][e:e + 8], p["wo"][e:e + 8], e)
                  for e in range(0, 256, 8)]
        shared = moe_mod._shared_ffn(p, x2d)
        np.testing.assert_allclose(np.asarray(sum(shares) + shared),
                                   np.asarray(whole), atol=1e-5, rtol=1e-5)
        # the reference's layer with all 256 experts is the same sum
        rw = ref.moe(p, x2d, route_dims=(8, 4, 8, 2.5), mm=mm_f32)
        np.testing.assert_allclose(np.asarray(rw), np.asarray(whole),
                                   atol=1e-4, rtol=1e-4)
        # the chip's share: its first 8 experts, plus the shared expert
        held = _cfg()
        p0 = dict(p, wi=p["wi"][:8], wg=p["wg"][:8], wo=p["wo"][:8])
        np.testing.assert_allclose(
            np.asarray(moe_mod.moe_apply(held, p0, x).reshape(x2d.shape)),
            np.asarray(shares[0] + shared), atol=1e-5, rtol=1e-5)


def test_tokens_in_blocks_give_what_one_block_gives(monkeypatch):
    """Past ``TOKEN_BLOCK`` tokens the held experts run block by block (a
    last block padded); the result is the one-block result, with 8 held
    (every held expert over every token) and with all 256 held (the
    grouped form)."""
    for held in (8, 0):
        cfg = _cfg(n_experts_held=held)
        p = jax.tree.map(lambda a: a[0], _params(cfg, seed=6)["layers"]["moe"])
        x2d = jax.random.normal(jax.random.PRNGKey(10), (50, cfg.d_model))
        gates, idx = moe_mod.router_weights(cfg, p, x2d)
        one = moe_mod.moe_local(x2d, gates, idx, p["wi"], p["wg"], p["wo"], 0)
        with monkeypatch.context() as m:
            m.setattr(moe_mod, "TOKEN_BLOCK", 16)
            blocks = moe_mod.moe_local(x2d, gates, idx, p["wi"], p["wg"],
                                       p["wo"], 0)
        np.testing.assert_allclose(np.asarray(blocks), np.asarray(one),
                                   atol=1e-6, rtol=1e-6)
        assert float(jnp.abs(one).max()) > 0


def test_a_yarn_file_with_other_settings_is_refused():
    """The program holds DeepSeek-V3's YaRN settings as constants and
    takes only the factor from a file; a file with another original
    length or ramp is refused instead of run with DeepSeek's ramp."""
    assert ref.program_fields(SMOKE)["rope_factor"] == 40
    for key, value in (("original_max_position_embeddings", 8192),
                       ("beta_fast", 16), ("mscale_all_dim", 0.707)):
        conf = dict(SMOKE, rope_scaling=dict(SMOKE["rope_scaling"], **{key: value}))
        with pytest.raises(ValueError, match="YaRN constants"):
            ref.program_fields(conf)


@pytest.mark.parametrize("held", [4, 8, 0])
def test_expert_work_is_at_most_the_assignments(held):
    """The held experts' matmuls run over no more rows than a token's
    assignments to them can be, min(top_k, held) a token: with at most
    top_k held every held expert runs every token, with more (all 256
    here) the assignments go through a grouped matmul, 8 rows a token
    where every expert over every token would be 256."""
    from repro.analysis.jaxpr import structural_flops
    cfg = _cfg(n_experts_held=held)
    Eh, d, f, k, T = cfg.experts_held, cfg.d_model, cfg.moe_d_ff, cfg.top_k, 40
    f32 = jnp.float32
    args = (jax.ShapeDtypeStruct((T, d), f32), jax.ShapeDtypeStruct((T, k), f32),
            jax.ShapeDtypeStruct((T, k), jnp.int32),
            jax.ShapeDtypeStruct((Eh, d, f), f32), jax.ShapeDtypeStruct((Eh, d, f), f32),
            jax.ShapeDtypeStruct((Eh, f, d), f32))
    flops = structural_flops(lambda *a: moe_mod.moe_local(*a, 0), *args)
    assert flops == 3 * 2 * T * min(k, Eh) * d * f
