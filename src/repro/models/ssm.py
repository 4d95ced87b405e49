"""Mamba-2 (SSD) mixer: in-proj -> causal conv1d -> SSD chunk scan ->
gated norm -> out-proj, with a constant-size recurrent state for decode.

The SSD scan itself is a compound operation (chunk GEMMs + decay SIMD ops);
train/prefill route through the chunked algorithm (Pallas kernel or the
chunked jnp reference, chunk length COMET-tuned), decode is the O(1)
recurrence h' = exp(dA) h + B ⊗ x·dt.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kops
from .config import ModelConfig
from .param import ParamSpec

F32 = jnp.float32

__all__ = ["ssm_specs", "ssm_train", "ssm_prefill", "ssm_decode",
           "init_ssm_cache"]


def ssm_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    d = cfg.d_model
    di, ng, ns, nh = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    cd = cfg.conv_dim
    proj_out = 2 * di + 2 * ng * ns + nh     # z, x, B, C, dt
    return {
        "in_proj": ParamSpec((L, d, proj_out), ("layer", "embed", "inner"), dtype=cfg.dtype),
        "conv_w": ParamSpec((L, cfg.conv_kernel, cd), ("layer", None, "inner"),
                            scale=1.0, dtype=cfg.dtype),
        "conv_b": ParamSpec((L, cd), ("layer", "inner"), init="zeros", dtype=cfg.dtype),
        "A_log": ParamSpec((L, nh), ("layer", None), init="zeros", dtype="float32"),
        "dt_bias": ParamSpec((L, nh), ("layer", None), init="zeros", dtype="float32"),
        "D": ParamSpec((L, nh), ("layer", None), init="ones", dtype="float32"),
        "norm_scale": ParamSpec((L, di), ("layer", "inner"), init="ones", dtype=cfg.dtype),
        "out_proj": ParamSpec((L, di, d), ("layer", "inner", "embed"), dtype=cfg.dtype),
    }


def _split_proj(cfg: ModelConfig, proj: jax.Array):
    di, ng, ns, nh = cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state, cfg.ssm_nheads
    z = proj[..., :di]
    xin = proj[..., di:2 * di]
    Bm = proj[..., 2 * di:2 * di + ng * ns]
    Cm = proj[..., 2 * di + ng * ns:2 * di + 2 * ng * ns]
    dt = proj[..., 2 * di + 2 * ng * ns:]
    return z, xin, Bm, Cm, dt


def _gated_norm(cfg: ModelConfig, scale: jax.Array, y: jax.Array,
                z: jax.Array) -> jax.Array:
    yf = (y * jax.nn.silu(z)).astype(F32)
    ms = (yf * yf).mean(-1, keepdims=True)
    return (yf * jax.lax.rsqrt(ms + cfg.norm_eps) * scale.astype(F32)).astype(y.dtype)


def _conv1d(cfg: ModelConfig, w: jax.Array, b: jax.Array, u: jax.Array,
            prev: Optional[jax.Array] = None) -> jax.Array:
    """Causal depthwise conv over (B, S, C).  w: (K, C)."""
    K = cfg.conv_kernel
    pad = prev if prev is not None else jnp.zeros(
        (u.shape[0], K - 1, u.shape[2]), u.dtype)
    up = jnp.concatenate([pad, u], axis=1)
    out = sum(up[:, i:i + u.shape[1]] * w[i] for i in range(K))
    return jax.nn.silu(out + b)


def _ssd_inputs(cfg: ModelConfig, p, xbc_conv: jax.Array, dt_raw: jax.Array):
    """Split conv output & build (xdt, dA, B, C) for the SSD scan."""
    di, ng, ns, nh, hp = (cfg.d_inner, cfg.ssm_ngroups, cfg.ssm_state,
                          cfg.ssm_nheads, cfg.ssm_headdim)
    B_, S = dt_raw.shape[0], dt_raw.shape[1]
    xin = xbc_conv[..., :di]
    Bm = xbc_conv[..., di:di + ng * ns].reshape(B_, S, ng, ns)
    Cm = xbc_conv[..., di + ng * ns:].reshape(B_, S, ng, ns)
    dt = jax.nn.softplus(dt_raw.astype(F32) + p["dt_bias"].astype(F32))  # (B,S,nh)
    A = -jnp.exp(p["A_log"].astype(F32))                                  # (nh,)
    dA = dt * A                                                           # (B,S,nh)
    xh = xin.reshape(B_, S, nh, hp)
    xdt = xh.astype(F32) * dt[..., None]
    # broadcast groups to heads
    rep = nh // ng
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)
    return xh, xdt, dA, Bh, Ch


def _ssd_scan(cfg: ModelConfig, xdt, dA, Bh, Ch) -> jax.Array:
    """(B,S,nh,hp) x (B,S,nh) x (B,S,nh,ns) -> y (B,S,nh,hp)."""
    B_, S, nh, hp = xdt.shape
    ns = Bh.shape[-1]
    # flatten batch x heads for the kernel layout
    xk = xdt.transpose(0, 2, 1, 3).reshape(B_ * nh, S, hp)
    dk = dA.transpose(0, 2, 1).reshape(B_ * nh, S)
    bk = Bh.transpose(0, 2, 1, 3).reshape(B_ * nh, S, ns)
    ck = Ch.transpose(0, 2, 1, 3).reshape(B_ * nh, S, ns)
    from ..kernels.autotune import ssd_chunk_len
    chunk = min(ssd_chunk_len(S, hp, ns), S)
    if S % chunk:
        chunk = max(1, math.gcd(S, chunk))
    y = kops.ssd(xk.astype(jnp.bfloat16), dk, bk.astype(jnp.bfloat16),
                 ck.astype(jnp.bfloat16), chunk=chunk,
                 use_kernel=cfg.use_kernels)
    return y.reshape(B_, nh, S, hp).transpose(0, 2, 1, 3)


def ssm_train(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    B_, S, _ = x.shape
    di, nh, hp = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim
    proj = x @ p["in_proj"]
    z, xin, Bm, Cm, dt_raw = _split_proj(cfg, proj)
    xbc = jnp.concatenate([xin, Bm, Cm], axis=-1)
    xbc = _conv1d(cfg, p["conv_w"], p["conv_b"], xbc)
    xh, xdt, dA, Bh, Ch = _ssd_inputs(cfg, p, xbc, dt_raw)
    y = _ssd_scan(cfg, xdt, dA, Bh, Ch)
    y = y + xh.astype(F32) * p["D"].astype(F32)[None, None, :, None]
    y = y.reshape(B_, S, di).astype(x.dtype)
    y = _gated_norm(cfg, p["norm_scale"], y, z)
    return y @ p["out_proj"]


def init_ssm_cache(cfg: ModelConfig, B: int, dtype) -> Dict:
    return {
        "conv": jnp.zeros((B, cfg.conv_kernel - 1, cfg.conv_dim), dtype),
        "state": jnp.zeros((B, cfg.ssm_nheads, cfg.ssm_state,
                            cfg.ssm_headdim), F32),
    }


def ssm_prefill(cfg: ModelConfig, p, x: jax.Array) -> Tuple[jax.Array, Dict]:
    """Full-seq forward + final recurrent state for decode continuation."""
    B_, S, _ = x.shape
    di, nh, hp, ns = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    proj = x @ p["in_proj"]
    z, xin, Bm, Cm, dt_raw = _split_proj(cfg, proj)
    xbc_raw = jnp.concatenate([xin, Bm, Cm], axis=-1)
    xbc = _conv1d(cfg, p["conv_w"], p["conv_b"], xbc_raw)
    xh, xdt, dA, Bh, Ch = _ssd_inputs(cfg, p, xbc, dt_raw)
    y = _ssd_scan(cfg, xdt, dA, Bh, Ch)
    y = y + xh.astype(F32) * p["D"].astype(F32)[None, None, :, None]
    yo = _gated_norm(cfg, p["norm_scale"], y.reshape(B_, S, di).astype(x.dtype), z)
    out = yo @ p["out_proj"]
    # final state: h_S = sum_t exp(sum_{u>t} dA_u) B_t xdt_t^T  (per head)
    cs = jnp.cumsum(dA, axis=1)
    decay = jnp.exp(cs[:, -1:, :] - cs)                       # (B,S,nh)
    state = jnp.einsum("bshn,bsh,bshp->bhnp", Bh.astype(F32), decay,
                       xdt)                                   # (B,nh,ns,hp)
    conv_state = xbc_raw[:, -(cfg.conv_kernel - 1):, :]
    return out, {"conv": conv_state, "state": state}


def ssm_decode(cfg: ModelConfig, p, x: jax.Array, cache: Dict,
               i: jax.Array) -> Tuple[jax.Array, Dict]:
    """One-token step of layer ``i``.  x: (B, 1, d); ``cache`` is the
    layer-stacked cache (leading axis L): layer ``i``'s conv window and
    state are read from it and their successors written back in place."""
    B_, _, _ = x.shape
    di, nh, hp, ns = cfg.d_inner, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    proj = x @ p["in_proj"]
    z, xin, Bm, Cm, dt_raw = _split_proj(cfg, proj)
    xbc1 = jnp.concatenate([xin, Bm, Cm], axis=-1)            # (B,1,cd)
    conv_prev = jax.lax.dynamic_index_in_dim(cache["conv"], i, 0, False)
    xbc = _conv1d(cfg, p["conv_w"], p["conv_b"], xbc1, prev=conv_prev)
    new_conv = jnp.concatenate([conv_prev, xbc1], axis=1)[:, 1:, :]
    xh, xdt, dA, Bh, Ch = _ssd_inputs(cfg, p, xbc, dt_raw)
    # recurrence: h' = exp(dA) h + B ⊗ xdt
    h = jax.lax.dynamic_index_in_dim(cache["state"], i, 0, False)
    h = jnp.exp(dA[:, 0, :, None, None]) * h \
        + jnp.einsum("bhn,bhp->bhnp", Bh[:, 0].astype(F32), xdt[:, 0])
    y = jnp.einsum("bhn,bhnp->bhp", Ch[:, 0].astype(F32), h)  # (B,nh,hp)
    y = y + xh[:, 0].astype(F32) * p["D"].astype(F32)[None, :, None]
    y = y.reshape(B_, 1, di).astype(x.dtype)
    y = _gated_norm(cfg, p["norm_scale"], y, z)
    put = jax.lax.dynamic_update_index_in_dim
    return y @ p["out_proj"], {"conv": put(cache["conv"], new_conv, i, 0),
                               "state": put(cache["state"], h, i, 0)}
