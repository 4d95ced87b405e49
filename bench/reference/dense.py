"""Dense decoder reference (Phi-3 layout): RMSNorm, grouped-query
attention with rotate-half RoPE over the whole head, SwiGLU MLP, final
RMSNorm, untied or tied output matrix.

Keys read from the configuration file: ``hidden_size``,
``num_hidden_layers``, ``num_attention_heads``, ``num_key_value_heads``,
``rope_theta``, ``rms_norm_eps``, ``tie_word_embeddings``.  The file's
``departures`` say where this follows the program rather than the
published model (full-width RoPE, no LongRoPE, untied output).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from . import F32, rms_norm


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
