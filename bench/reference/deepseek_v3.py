"""DeepSeek-V3 reference (arXiv:2412.19437, the published ``config.json``
and its inference code): multi-head latent attention with YaRN, leading
dense SwiGLU layers, then mixture-of-experts layers with the sigmoid
``noaux_tc`` router and a shared expert; final RMSNorm, untied output
matrix.  Each layer is ``x + MLA(norm1(x))``, then ``x + FFN(norm2(x))``.

MLA, decompressed, with causal attention over one sequence:

    c_q = RMSNorm(x W_dq),  [q_nope, q_rope] = c_q W_uq          (per head)
    [c_kv, k_rope] = x W_dkv,  c_kv = RMSNorm(c_kv)              (k_rope shared)
    k_nope = c_kv W_uk,  v = c_kv W_uv
    s = (q_nope . k_nope + rope(q_rope) . rope(k_rope)) * mscale^2 / sqrt(nope + rope)

with YaRN's frequencies (``rope_scaling``: the slow dims divided by
``factor``, a linear ramp between ``beta_fast`` and ``beta_slow`` turns
over ``original_max_position_embeddings``) and its temperature
``mscale = 0.1 mscale_all_dim ln(factor) + 1``.

The router, over all ``published["n_routed_experts"]`` experts: sigmoid
scores, a load-balancing bias added for the choice only (a
``router_bias`` beside the layer's weights where the caller holds one),
each group of experts scored by the sum of its best two, the best
``topk_group`` of ``n_group`` groups kept, the best
``num_experts_per_tok`` experts among them chosen, their (unbiased) scores
normalised and multiplied by ``routed_scaling_factor``.  The layer holds
the file's ``n_routed_experts``, the first of them (one chip's
expert-parallel share): it adds only those experts' part, and the shared
expert's.

The file's ``departures`` say where this follows the program rather than
the published model.  Work is counted with the latent cache (``c_kv`` and
``k_rope``, 576 values a position a layer) in bfloat16, decode in the
absorbed form over the valid cache, and routed experts at their expected
share of a token: ``num_experts_per_tok`` × held ÷ published
(``decode_work``, ``prefill_work``, ``moe_decode_work``).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import F32, rms_norm
from ..work import BF16, F32 as F32_BYTES, Work

HIGHEST = jax.lax.Precision.HIGHEST


def _check(cfg: dict) -> None:
    rs = cfg["rope_scaling"]
    if (cfg["scoring_func"], cfg["topk_method"], cfg["norm_topk_prob"],
            rs["type"]) != ("sigmoid", "noaux_tc", True, "yarn") \
            or rs["mscale"] != rs["mscale_all_dim"]:
        raise ValueError("deepseek_v3 reference: only the published sigmoid "
                         "noaux_tc router and YaRN with mscale == "
                         "mscale_all_dim are written down here")


def yarn_freqs(cfg: dict) -> np.ndarray:
    """The (rope dims / 2,) inverse frequencies under YaRN."""
    rs, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], cfg["rope_theta"]
    orig = rs["original_max_position_embeddings"]

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(rs["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low),
                   0, 1)
    freqs = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    smooth = 1 - ramp
    return (freqs / rs["factor"] * (1 - smooth) + freqs * smooth).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    mscale = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
    return mscale * mscale / math.sqrt(cfg["qk_nope_head_dim"]
                                       + cfg["qk_rope_head_dim"])


def _rope(x: jax.Array, freqs: jax.Array) -> jax.Array:
    """x (T, ..., D): rotate the two halves of the last dim by position."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freqs[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def _mla(p, x, freqs, *, dims, mm):
    H, dn, dr, dv, kvr, eps, scale = dims
    T = x.shape[0]
    cq = rms_norm(mm(x, p["wdq"]), p["q_norm"], eps)
    q = mm(cq, p["wuq"]).reshape(T, H, dn + dr)
    kv = mm(x, p["wdkv"])
    ckv = rms_norm(kv[:, :kvr], p["kv_norm"], eps)
    k_rope = _rope(kv[:, kvr:], freqs)                       # (T, dr)
    k_nope = mm(ckv, p["wuk"]).reshape(T, H, dn)
    v = mm(ckv, p["wuv"]).reshape(T, H, dv)
    s = (jnp.einsum("thd,shd->hts", q[..., :dn], k_nope, precision=HIGHEST)
         + jnp.einsum("thd,sd->hts", _rope(q[..., dn:], freqs), k_rope,
                      precision=HIGHEST)) * scale
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v, precision=HIGHEST)
    return mm(o.reshape(T, H * dv), p["wo"])


def _swiglu(h, wg, wi, wo, mm):
    return mm(jax.nn.silu(mm(h, wg)) * mm(h, wi), wo)


def route(p, h, n_group, topk_group, top_k, scaling, mm):
    """(gates (T, top_k), experts (T, top_k)) over all the router's
    experts, as ``noaux_tc`` chooses them."""
    scores = jax.nn.sigmoid(mm(h, p["router"]))
    choice = scores + p["router_bias"] if "router_bias" in p else scores
    T, E = choice.shape
    grouped = choice.reshape(T, n_group, E // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)
    best = jax.lax.top_k(group_score, topk_group)[1]
    keep = jnp.zeros((T, n_group), bool).at[jnp.arange(T)[:, None], best].set(True)
    choice = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)
    experts = jax.lax.top_k(choice, top_k)[1]
    gates = jnp.take_along_axis(scores, experts, 1)
    return gates / gates.sum(-1, keepdims=True) * scaling, experts


def moe(p, h, *, route_dims, mm):
    """The held experts' part of the layer's routed output, plus the
    shared expert's."""
    gates, experts = route(p, h, *route_dims, mm)
    out = _swiglu(h, p["shared_wg"], p["shared_wi"], p["shared_wo"], mm)
    for e in range(p["wi"].shape[0]):
        g = jnp.where(experts == e, gates, 0.0).sum(-1)          # (T,)
        out = out + g[:, None] * _swiglu(h, p["wg"][e], p["wi"][e],
                                         p["wo"][e], mm)
    return out


@partial(jax.jit, static_argnames=("dims", "route_dims", "mm"))
def _layer(layers, i, x, freqs, *, dims, route_dims, mm):
    p = jax.tree.map(lambda a: a[i], layers)
    eps = dims[5]
    x = x + _mla(p["attn"], rms_norm(x, p["norm1"]["scale"], eps), freqs,
                 dims=dims, mm=mm)
    h = rms_norm(x, p["norm2"]["scale"], eps)
    if route_dims is None:
        return x + _swiglu(h, p["mlp"]["wg"], p["mlp"]["wi"], p["mlp"]["wo"], mm)
    return x + moe(p["moe"], h, route_dims=route_dims, mm=mm)


def _dims(cfg: dict):
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"],
            float(cfg["rms_norm_eps"]), softmax_scale(cfg))


def hidden(cfg: dict, params, tokens, mm) -> jax.Array:
    """tokens (N, T) -> (N, T, d); one sequence at a time, so the (H, T, T)
    scores of one layer are the largest temporary."""
    _check(cfg)
    dims = _dims(cfg)
    route_dims = (cfg["n_group"], cfg["topk_group"], cfg["num_experts_per_tok"],
                  float(cfg["routed_scaling_factor"]))
    freqs = jnp.asarray(yarn_freqs(cfg))
    n_dense = cfg["first_k_dense_replace"]
    out = []
    for row in jnp.asarray(tokens):
        x = jnp.take(params["embedding"], row, axis=0).astype(F32)
        for i in range(n_dense):
            x = _layer(params["dense_layers"], i, x, freqs, dims=dims,
                       route_dims=None, mm=mm)
        for i in range(cfg["num_hidden_layers"] - n_dense):
            x = _layer(params["layers"], i, x, freqs, dims=dims,
                       route_dims=route_dims, mm=mm)
        out.append(rms_norm(x, params["final_norm"]["scale"], dims[5]))
    return jnp.stack(out)


def head(cfg: dict, params) -> jax.Array:
    if cfg["tie_word_embeddings"]:
        return params["embedding"].T
    return params["unembed"]


def _sizes(cfg: dict):
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                     cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = L - n_dense
    mla = (d * qr + qr * H * (dn + dr) + d * (kvr + dr) + kvr * H * dn
           + kvr * H * dv + H * dv * d)
    mla_norms = qr + kvr
    dense_ffn = 3 * d * cfg["intermediate_size"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    E = cfg["published"]["n_routed_experts"]
    held = cfg["n_routed_experts"]
    shared = cfg["n_shared_experts"] * expert
    routed = cfg["num_experts_per_tok"] * held / E   # expected experts a token
    V = cfg["vocab_size"]
    bf16_params = (L * (mla + mla_norms + 2 * d) + n_dense * dense_ffn
                   + n_moe * (held * expert + shared) + d + d * V)
    weights = bf16_params * BF16 + n_moe * d * E * F32_BYTES
    latent = L * (kvr + dr) * BF16                  # cache bytes of a position
    # matrix products of one token (multiply-adds), the head excluded
    token_macs = (L * mla + n_dense * dense_ffn
                  + n_moe * (d * E + routed * expert + shared))
    return d, L, H, dn, dr, dv, kvr, V, weights, latent, token_macs


def decode_work(cfg: dict, rows: int, ctx_sum: int) -> Work:
    """Absorbed MLA over the valid latent cache: per key and head, the
    latent and rope scores and the latent context."""
    d, L, H, dn, dr, dv, kvr, V, weights, latent, macs = _sizes(cfg)
    keys = ctx_sum + rows                       # the new key is attended
    flops = rows * 2 * (macs + d * V) + L * 2 * H * (2 * kvr + dr) * keys
    nbytes = (weights + rows * d * BF16 + latent * ctx_sum + latent * rows)
    return Work(flops, nbytes)


def prefill_work(cfg: dict, rows: int, prompt_len: int) -> Work:
    """Decompressed MLA over the causal lower triangle of the scores."""
    S = prompt_len
    d, L, H, dn, dr, dv, kvr, V, weights, latent, macs = _sizes(cfg)
    attn = L * 2 * H * (dn + dr + dv) * S * (S + 1) // 2
    flops = rows * (2 * macs * S + attn + 2 * d * V)
    nbytes = weights + rows * S * (d * BF16 + latent)
    return Work(flops, nbytes)


def moe_decode_work(cfg: dict, rows: int) -> Work:
    """The MoE layers of one decode step (the program's ``moe`` scope):
    the router in float32, the held experts at their expected share of a
    token, the shared expert; each layer's weights read once and its
    rows read and written."""
    d, E = cfg["hidden_size"], cfg["published"]["n_routed_experts"]
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    held = cfg["n_routed_experts"]
    shared = cfg["n_shared_experts"] * expert
    routed = cfg["num_experts_per_tok"] * held / E
    flops = n_moe * rows * 2 * (d * E + routed * expert + shared)
    nbytes = n_moe * ((held * expert + shared) * BF16 + d * E * F32_BYTES
                      + 2 * rows * d * BF16)
    return Work(flops, nbytes)


def program_fields(conf: dict) -> dict:
    """The file's numbers as ``ModelConfig`` fields.  Of ``rope_scaling``
    the program takes only ``factor``; the rest it holds as DeepSeek-V3's
    published constants, so a file that differs there is refused."""
    from repro.models import layers
    rs = conf["rope_scaling"]
    held = (layers.YARN_ORIGINAL_LEN, layers.YARN_BETA_FAST,
            layers.YARN_BETA_SLOW, layers.YARN_MSCALE)
    if (rs["original_max_position_embeddings"], rs["beta_fast"],
            rs["beta_slow"], rs["mscale_all_dim"]) != held:
        raise ValueError(f"deepseek_v3: rope_scaling {rs} differs from the "
                         f"program's YaRN constants {held}")
    return {"d_model": conf["hidden_size"],
            "n_layers": conf["num_hidden_layers"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "head_dim": conf["qk_nope_head_dim"],
            "d_ff": conf["intermediate_size"],
            "vocab_size": conf["vocab_size"],
            "q_lora_rank": conf["q_lora_rank"],
            "kv_lora_rank": conf["kv_lora_rank"],
            "rope_head_dim": conf["qk_rope_head_dim"],
            "v_head_dim": conf["v_head_dim"],
            "n_experts": conf["published"]["n_routed_experts"],
            "n_experts_held": conf["n_routed_experts"],
            "n_shared_experts": conf["n_shared_experts"],
            "top_k": conf["num_experts_per_tok"],
            "moe_d_ff": conf["moe_intermediate_size"],
            "first_dense_layers": conf["first_k_dense_replace"],
            "router_type": conf["scoring_func"],
            "n_group": conf["n_group"],
            "topk_group": conf["topk_group"],
            "routed_scaling": conf["routed_scaling_factor"],
            "rope_theta": conf["rope_theta"],
            "rope_factor": rs["factor"],
            "norm_eps": conf["rms_norm_eps"],
            "tie_embeddings": conf["tie_word_embeddings"]}
