"""Dense decoder reference (Phi-3 layout): RMSNorm, grouped-query
attention with rotate-half RoPE over the whole head, SwiGLU MLP, final
RMSNorm, untied or tied output matrix.

Keys read from the configuration file: ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``rope_theta``, ``rms_norm_eps``, ``tie_word_embeddings``.  The file's
``departures`` say where this follows the program rather than the
published model (full-width RoPE, no LongRoPE, untied output).

Work is counted with the KV cache in bfloat16 and causal attention over
the lower triangle of the scores (``decode_work``, ``prefill_work``).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import F32, rms_norm
from ..work import BF16, Work


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x (T, H, D): rotate the two halves of each head by position."""
    T, _, D = x.shape
    half = D // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


@partial(jax.jit, static_argnames=("dims", "mm"))
def _layer(layers, i, x, *, dims, mm):
    H, Hkv, theta, eps = dims
    p = jax.tree.map(lambda a: a[i], layers)
    T, d = x.shape
    hd = p["attn"]["wq"].shape[-1] // H
    h = rms_norm(x, p["norm1"]["scale"], eps)
    q = _rope(mm(h, p["attn"]["wq"]).reshape(T, H, hd), theta)
    k = _rope(mm(h, p["attn"]["wk"]).reshape(T, Hkv, hd), theta)
    v = mm(h, p["attn"]["wv"]).reshape(T, Hkv, hd)
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(v, H // Hkv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v,
                   precision=jax.lax.Precision.HIGHEST)
    x = x + mm(o.reshape(T, H * hd), p["attn"]["wo"])
    h = rms_norm(x, p["norm2"]["scale"], eps)
    g = mm(h, p["mlp"]["wg"])
    return x + mm(jax.nn.silu(g) * mm(h, p["mlp"]["wi"]), p["mlp"]["wo"])


def hidden(cfg: dict, params, tokens, mm) -> jax.Array:
    """tokens (N, T) -> (N, T, d); one sequence at a time, so the (H, T, T)
    scores of one layer are the largest temporary."""
    dims = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]))
    out = []
    for row in jnp.asarray(tokens):
        x = jnp.take(params["embedding"], row, axis=0).astype(F32)
        for i in range(cfg["num_hidden_layers"]):
            x = _layer(params["layers"], i, x, dims=dims, mm=mm)
        out.append(rms_norm(x, params["final_norm"]["scale"], dims[3]))
    return jnp.stack(out)


def head(cfg: dict, params) -> jax.Array:
    if cfg["tie_word_embeddings"]:
        return params["embedding"].T
    return params["unembed"]


def _sizes(cfg: dict):
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // H
    ff, V = cfg["intermediate_size"], cfg["vocab_size"]
    mat = L * (2 * d * H * hd + 2 * d * Hkv * hd + 3 * d * ff)
    norms = L * 2 * d + d
    kv_token = L * 2 * Hkv * hd * BF16            # cache bytes of one position
    return d, L, H, hd, V, mat, norms, kv_token


def decode_work(cfg: dict, rows: int, ctx_sum: int) -> Work:
    d, L, H, hd, V, mat, norms, kv_token = _sizes(cfg)
    keys = ctx_sum + rows                       # the new key is attended
    flops = rows * 2 * (mat + d * V) + L * 4 * H * hd * keys
    weights = (mat + norms + d * V) * BF16
    nbytes = (weights + rows * d * BF16 + kv_token * ctx_sum
              + kv_token * rows)
    return Work(flops, nbytes)


def prefill_work(cfg: dict, rows: int, prompt_len: int) -> Work:
    S = prompt_len
    d, L, H, hd, V, mat, norms, kv_token = _sizes(cfg)
    flops = rows * (2 * mat * S + L * 4 * H * hd * S * (S + 1) // 2
                    + 2 * d * V)
    weights = (mat + norms + d * V) * BF16
    nbytes = weights + rows * S * (d * BF16 + kv_token)
    return Work(flops, nbytes)


def program_fields(conf: dict) -> dict:
    return {"d_model": conf["hidden_size"],
            "n_layers": conf["num_hidden_layers"],
            "n_heads": conf["num_attention_heads"],
            "n_kv_heads": conf["num_key_value_heads"],
            "d_ff": conf["intermediate_size"],
            "vocab_size": conf["vocab_size"],
            "rope_theta": conf["rope_theta"],
            "norm_eps": conf["rms_norm_eps"],
            "tie_embeddings": conf["tie_word_embeddings"]}
