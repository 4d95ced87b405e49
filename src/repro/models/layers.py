"""Shared model layers: norms, RoPE, MLP, embeddings, loss."""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig
from .param import ParamSpec

__all__ = [
    "norm_specs", "apply_norm", "rope_cos_sin", "yarn_mscale", "apply_rope",
    "mlp_specs", "mlp_apply", "embed_specs", "embed_apply", "unembed_apply",
    "cross_entropy_loss",
]

F32 = jnp.float32


# ------------------------------------------------------------------- norms


def norm_specs(cfg: ModelConfig, stacked: Optional[int] = None,
               dim: Optional[int] = None) -> Dict[str, ParamSpec]:
    d = dim or cfg.d_model
    shape = (stacked, d) if stacked else (d,)
    axes = ("layer", "embed") if stacked else ("embed",)
    out = {"scale": ParamSpec(shape, axes, init="ones", dtype=cfg.dtype)}
    if cfg.norm_type == "layernorm":
        out["bias"] = ParamSpec(shape, axes, init="zeros", dtype=cfg.dtype)
    return out


def apply_norm(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    xf = x.astype(F32)
    if cfg.norm_type == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(F32) + p["bias"].astype(F32)
    else:
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(F32)
    return y.astype(x.dtype)


# -------------------------------------------------------------------- RoPE


def rope_cos_sin(positions: jax.Array, dim: int, cfg: ModelConfig
                 ) -> Tuple[jax.Array, jax.Array]:
    """positions: (...,) int -> cos/sin (..., dim//2) f32, at
    ``cfg.rope_theta``; where ``cfg.rope_factor`` is above 1 the
    frequencies are YaRN's."""
    half = dim // 2
    freqs = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=F32) / half))
    if cfg.rope_factor > 1:
        keep = _yarn_keep(cfg, dim)
        freqs = freqs * keep + freqs / cfg.rope_factor * (1 - keep)
    ang = positions.astype(F32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


# YaRN's other settings, as DeepSeek-V3 publishes them (``rope_scaling``):
# the context it was trained at, the turns over it that bound the ramp,
# and ``mscale_all_dim``.  Only ``rope_factor`` is a field; a model with
# other settings needs them as fields too (the benchmark's deepseek_v3
# family refuses a file whose ``rope_scaling`` differs from these)
YARN_ORIGINAL_LEN, YARN_BETA_FAST, YARN_BETA_SLOW, YARN_MSCALE = 4096, 32, 1, 1.0


def _yarn_keep(cfg: ModelConfig, dim: int) -> np.ndarray:
    """YaRN's share of each of the ``dim // 2`` rope frequencies kept as
    is (1 for the fast ones, which turn more than ``YARN_BETA_FAST`` times
    over ``YARN_ORIGINAL_LEN`` positions; 0 for those slower than
    ``YARN_BETA_SLOW`` turns, divided by ``rope_factor``; linear between)."""
    def at(turns):          # the frequency index that turns ``turns`` times
        return dim * math.log(YARN_ORIGINAL_LEN / (turns * 2 * math.pi)) \
            / (2 * math.log(cfg.rope_theta))
    lo = max(math.floor(at(YARN_BETA_FAST)), 0)
    hi = min(math.ceil(at(YARN_BETA_SLOW)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 1e-3), 0, 1)
    return (1 - ramp).astype(np.float32)


def yarn_mscale(cfg: ModelConfig) -> float:
    """YaRN's attention temperature: the softmax scale is multiplied by its
    square (1 without scaling)."""
    if cfg.rope_factor <= 1:
        return 1.0
    return 0.1 * YARN_MSCALE * math.log(cfg.rope_factor) + 1.0


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, D); cos/sin: (S, D//2) (broadcast over batch/heads)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]   # (S, 1, D/2) -> broadcast over heads
    s = sin[..., None, :]
    xf1, xf2 = x1.astype(F32), x2.astype(F32)
    return jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s],
                           axis=-1).astype(x.dtype)


# --------------------------------------------------------------------- MLP


def mlp_specs(cfg: ModelConfig, stacked: Optional[int] = None,
              d_ff: Optional[int] = None) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    L = (stacked,) if stacked else ()
    la = ("layer",) if stacked else ()
    return {
        "wi": ParamSpec(L + (d, f), la + ("embed", "ff"), dtype=cfg.dtype),
        "wg": ParamSpec(L + (d, f), la + ("embed", "ff"), dtype=cfg.dtype),
        "wo": ParamSpec(L + (f, d), la + ("ff", "embed"), dtype=cfg.dtype),
    }


def mlp_apply(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    """SwiGLU MLP: silu(x@wg) * (x@wi) @ wo."""
    h = jax.nn.silu(x @ p["wg"]) * (x @ p["wi"])
    return h @ p["wo"]


# -------------------------------------------------------------- embeddings


def embed_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    V, d = cfg.padded_vocab, cfg.d_model
    out = {"embedding": ParamSpec((V, d), ("vocab", "embed"), scale=1.0,
                                  dtype=cfg.dtype)}
    if not cfg.tie_embeddings:
        out["unembed"] = ParamSpec((d, V), ("embed", "vocab"), dtype=cfg.dtype)
    return out


def embed_apply(p: Dict[str, jax.Array], tokens: jax.Array) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0)


def unembed_apply(cfg: ModelConfig, p: Dict[str, jax.Array], h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        return h @ p["embedding"].T
    return h @ p["unembed"]


# -------------------------------------------------------------------- loss


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       real_vocab: int) -> jax.Array:
    """Mean token NLL.  logits (B, S, Vp) — padded vocab entries are masked;
    labels (B, S) int32 in [0, real_vocab)."""
    lf = logits.astype(F32)
    Vp = lf.shape[-1]
    if Vp > real_vocab:
        pad_mask = jnp.arange(Vp) >= real_vocab
        lf = jnp.where(pad_mask, -1e30, lf)
    lse = jax.nn.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll)
