"""Mamba-2 reference: RMSNorm, in-projection to (z, x, B, C, dt), causal
depthwise convolution with SiLU, the selective state-space recurrence
token by token (no chunking), the gated RMSNorm (norm after the gate),
out-projection, final RMSNorm and the tied output matrix.

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,   y_t = C_t h_t + D x_t

Keys read from the configuration file: ``d_model``, ``n_layer``,
``norm_epsilon``, ``tie_embeddings`` and, in ``ssm_cfg``, ``d_state``,
``d_conv``, ``expand``, ``headdim`` and ``ngroups``.

Work is counted with the recurrent state in float32, and the prefill's
scan as the chunked SSD algorithm at the configuration's published
``chunk_size``, not at any chunk the program picks, so a change of chunk
cannot move the yardstick (``decode_work``, ``prefill_work``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import F32, rms_norm
from ..work import BF16, F32 as F32_BYTES, Work


def _layer_one(layers, i, x, dims, mm):
    d_model, ns, K, expand, hp, ng, eps = dims
    p = jax.tree.map(lambda a: a[i], layers)["ssm"]
    norm1 = jax.tree.map(lambda a: a[i], layers)["norm1"]["scale"]
    T = x.shape[0]
    di = expand * d_model
    nh = di // hp
    proj = mm(rms_norm(x, norm1, eps), p["in_proj"])
    z, xin = proj[:, :di], proj[:, di:2 * di]
    bc = proj[:, 2 * di:2 * di + 2 * ng * ns]
    dt_raw = proj[:, 2 * di + 2 * ng * ns:]
    xbc = jnp.concatenate([xin, bc], -1)
    w = p["conv_w"].astype(F32)                              # (K, channels)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    conv = sum(padded[j:j + T] * w[j] for j in range(K))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(F32))
    xs = xbc[:, :di].reshape(T, nh, hp)
    Bm = jnp.repeat(xbc[:, di:di + ng * ns].reshape(T, ng, ns), nh // ng, 1)
    Cm = jnp.repeat(xbc[:, di + ng * ns:].reshape(T, ng, ns), nh // ng, 1)
    dt = jax.nn.softplus(dt_raw + p["dt_bias"].astype(F32))     # (T, nh)
    A = -jnp.exp(p["A_log"].astype(F32))                      # (nh,)

    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + (dt_t[:, None, None] * b_t[:, :, None]) * x_t[:, None, :])
        return h, jnp.sum(c_t[:, :, None] * h, axis=1)       # (nh, hp)

    h0 = jnp.zeros((nh, ns, hp), F32)
    _, y = jax.lax.scan(step, h0, (xs, Bm, Cm, dt))
    y = y + p["D"].astype(F32)[None, :, None] * xs
    y = y.reshape(T, di) * jax.nn.silu(z)
    y = rms_norm(y, p["norm_scale"], eps)
    return x + mm(y, p["out_proj"])


@partial(jax.jit, static_argnames=("dims", "mm"))
def _layer(layers, i, x, *, dims, mm):
    return jax.vmap(lambda r: _layer_one(layers, i, r, dims, mm))(x)


def hidden(cfg: dict, params, tokens, mm) -> jax.Array:
    """tokens (N, T) -> (N, T, d); the rows share each layer's call."""
    s = cfg["ssm_cfg"]
    eps = float(cfg["norm_epsilon"])
    dims = (cfg["d_model"], s["d_state"], s["d_conv"], s["expand"],
            s["headdim"], s["ngroups"], eps)
    x = jnp.take(params["embedding"], jnp.asarray(tokens), axis=0).astype(F32)
    for i in range(cfg["n_layer"]):
        x = _layer(params["layers"], i, x, dims=dims, mm=mm)
    return rms_norm(x, params["final_norm"]["scale"], eps)


def head(cfg: dict, params) -> jax.Array:
    if cfg["tie_embeddings"]:
        return params["embedding"].T
    return params["unembed"]


def _sizes(cfg: dict):
    s = cfg["ssm_cfg"]
    d, L, V = cfg["d_model"], cfg["n_layer"], cfg["vocab_size"]
    ns, K, hp, ng = s["d_state"], s["d_conv"], s["headdim"], s["ngroups"]
    di = s["expand"] * d
    nh = di // hp
    cd = di + 2 * ng * ns
    mat = L * (d * (2 * di + 2 * ng * ns + nh) + di * d)
    small_bf16 = L * (K * cd + cd + di + d) + d     # conv, norms
    small_f32 = L * 3 * nh                          # A_log, dt_bias, D
    state_row = L * (nh * ns * hp * F32_BYTES + (K - 1) * cd * BF16)
    return d, L, V, ns, K, hp, ng, nh, cd, mat, small_bf16, small_f32, state_row


def decode_work(cfg: dict, rows: int, ctx_sum: int) -> Work:
    d, L, V, ns, K, hp, ng, nh, cd, mat, sb, sf, state_row = _sizes(cfg)
    flops = rows * (2 * (mat + d * V)
                    + L * (2 * K * cd + 5 * nh * ns * hp))
    weights = (mat + sb + d * V) * BF16 + sf * F32_BYTES
    nbytes = weights + rows * (d * BF16 + 2 * state_row)
    return Work(flops, nbytes)


def prefill_work(cfg: dict, rows: int, prompt_len: int) -> Work:
    S = prompt_len
    d, L, V, ns, K, hp, ng, nh, cd, mat, sb, sf, state_row = _sizes(cfg)
    c = min(cfg["ssm_cfg"]["chunk_size"], S)
    # per token and layer: C B^T once per group (heads of a group share
    # B and C), the masked product with x, the chunk state and its output
    ssd = ng * 2 * c * ns + nh * (2 * c * hp + 4 * ns * hp)
    flops = rows * (S * (2 * mat + L * (2 * K * cd + ssd)) + 2 * d * V)
    weights = (mat + sb + d * V) * BF16 + sf * F32_BYTES
    nbytes = weights + rows * (S * d * BF16 + state_row)
    return Work(flops, nbytes)


def program_fields(conf: dict) -> dict:
    s = conf["ssm_cfg"]
    return {"d_model": conf["d_model"], "n_layers": conf["n_layer"],
            "vocab_size": conf["vocab_size"],
            "norm_eps": conf["norm_epsilon"],
            "tie_embeddings": conf["tie_embeddings"],
            "ssm_state": s["d_state"], "conv_kernel": s["d_conv"],
            "ssm_expand": s["expand"], "ssm_headdim": s["headdim"],
            "ssm_ngroups": s["ngroups"],
            "vocab_pad": conf["pad_vocab_size_multiple"]}
