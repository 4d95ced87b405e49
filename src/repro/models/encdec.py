"""Encoder-decoder model (Seamless-M4T backbone).

The speech/text frontend is a stub per the brief: ``input_specs()`` feeds
precomputed frame embeddings (B, S_enc, d) straight into the encoder.
Encoder layers are bidirectional; decoder layers add causal self-attention
+ cross-attention over the encoder output.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import attention as attn
from .config import ModelConfig
from .layers import (apply_norm, embed_apply, embed_specs, mlp_apply,
                     mlp_specs, norm_specs, unembed_apply)
from .transformer import _remat, _unroll, constrain, dp_axes

__all__ = ["encdec_specs", "encdec_forward", "encdec_prefill", "encdec_decode",
           "encdec_init_cache"]


def _enc_layer_specs(cfg: ModelConfig, L: int) -> Dict[str, Any]:
    return {"norm1": norm_specs(cfg, L), "attn": attn.gqa_specs(cfg, L),
            "norm2": norm_specs(cfg, L), "mlp": mlp_specs(cfg, L)}


def _dec_layer_specs(cfg: ModelConfig, L: int) -> Dict[str, Any]:
    return {"norm1": norm_specs(cfg, L), "attn": attn.gqa_specs(cfg, L),
            "norm_x": norm_specs(cfg, L), "cross": attn.cross_specs(cfg, L),
            "norm2": norm_specs(cfg, L), "mlp": mlp_specs(cfg, L)}


def encdec_specs(cfg: ModelConfig) -> Dict[str, Any]:
    s: Dict[str, Any] = dict(embed_specs(cfg))
    s["enc_layers"] = _enc_layer_specs(cfg, cfg.n_enc_layers)
    s["dec_layers"] = _dec_layer_specs(cfg, cfg.n_layers)
    s["enc_final_norm"] = norm_specs(cfg)
    s["final_norm"] = norm_specs(cfg)
    return s


# ---------------------------------------------------------------- encoder


def _encode(cfg: ModelConfig, params: Dict, src: jax.Array,
            mesh: Optional[Mesh]) -> jax.Array:
    dp = dp_axes(mesh)

    def body(x, pl):
        h = attn.attn_train(cfg, pl["attn"], apply_norm(cfg, pl["norm1"], x),
                            causal=False)
        x = x + h
        x = x + mlp_apply(cfg, pl["mlp"], apply_norm(cfg, pl["norm2"], x))
        return constrain(x, mesh, P(dp if dp else None, None, None)), None

    body = _remat(cfg, body)
    x, _ = jax.lax.scan(body, src.astype(jnp.dtype(cfg.dtype)),
                        params["enc_layers"],
                        unroll=_unroll(cfg, cfg.n_enc_layers))
    return apply_norm(cfg, params["enc_final_norm"], x)


# ---------------------------------------------------------------- forward


def encdec_forward(cfg: ModelConfig, params: Dict, src_embeds: jax.Array,
                   tokens: jax.Array, mesh: Optional[Mesh] = None) -> jax.Array:
    dp = dp_axes(mesh)
    enc = _encode(cfg, params, src_embeds, mesh)
    x = embed_apply(params, tokens).astype(jnp.dtype(cfg.dtype))

    def body(x, pl):
        x = x + attn.attn_train(cfg, pl["attn"],
                                apply_norm(cfg, pl["norm1"], x))
        x = x + attn.cross_train(cfg, pl["cross"],
                                 apply_norm(cfg, pl["norm_x"], x), enc)
        x = x + mlp_apply(cfg, pl["mlp"], apply_norm(cfg, pl["norm2"], x))
        return constrain(x, mesh, P(dp if dp else None, None, None)), None

    body = _remat(cfg, body)
    x, _ = jax.lax.scan(body, x, params["dec_layers"],
                        unroll=_unroll(cfg, cfg.n_layers))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed_apply(cfg, params, x)
    return constrain(logits, mesh, P(dp if dp else None, None, "model"))


# ------------------------------------------------------------------ cache


def encdec_init_cache(cfg: ModelConfig, B: int, cache_len: int) -> Dict:
    dt = jnp.dtype(cfg.dtype)
    L = cfg.n_layers
    Se = max(1, cache_len // cfg.enc_ratio)
    self_c = attn.init_attn_cache(cfg, B, cache_len, dt)
    cross_c = {"k": jnp.zeros((B, Se, cfg.n_kv_heads, cfg.hd), dt),
               "v": jnp.zeros((B, Se, cfg.n_kv_heads, cfg.hd), dt)}
    stack = lambda c: jax.tree.map(
        lambda a: jnp.broadcast_to(a, (L,) + a.shape).copy(), c)
    return {"pos": jnp.zeros((B,), jnp.int32), "self": stack(self_c),
            "cross": stack(cross_c)}


def encdec_prefill(cfg: ModelConfig, params: Dict, src_embeds: jax.Array,
                   tokens: jax.Array, cache_len: int,
                   mesh: Optional[Mesh] = None) -> Tuple[jax.Array, Dict]:
    dp = dp_axes(mesh)
    enc = _encode(cfg, params, src_embeds, mesh)
    x = embed_apply(params, tokens).astype(jnp.dtype(cfg.dtype))
    B, S = tokens.shape

    def body(x, pl):
        h, ca = attn.attn_prefill(cfg, pl["attn"],
                                  apply_norm(cfg, pl["norm1"], x))
        x = x + h
        cc = attn.make_cross_cache(cfg, pl["cross"], enc)
        x = x + attn.cross_train(cfg, pl["cross"],
                                 apply_norm(cfg, pl["norm_x"], x), enc)
        x = x + mlp_apply(cfg, pl["mlp"], apply_norm(cfg, pl["norm2"], x))
        x = constrain(x, mesh, P(dp if dp else None, None, None))
        return x, {"self": ca, "cross": cc}

    x, caches = jax.lax.scan(body, x, params["dec_layers"])
    pad = cache_len - S

    def grow(a):
        # stacked self-attn leaves: k/v (L, B, S, H, hd) and the per-row
        # kpos (L, B, S) both pad the sequence axis 2; int32 leaves are
        # position indices padded with -1 (= empty slot), not 0.
        widths = [(0, 0)] * 2 + [(0, pad)] + [(0, 0)] * (a.ndim - 3)
        if a.dtype == jnp.int32:
            return jnp.pad(a, widths, constant_values=-1)
        return jnp.pad(a, widths)

    self_c = jax.tree.map(grow, caches["self"])
    x = apply_norm(cfg, params["final_norm"], x[:, -1:, :])
    logits = unembed_apply(cfg, params, x)
    return logits, {"pos": jnp.full((B,), S, jnp.int32), "self": self_c,
                    "cross": caches["cross"]}


def encdec_decode(cfg: ModelConfig, params: Dict, cache: Dict,
                  tokens: jax.Array, mesh: Optional[Mesh] = None
                  ) -> Tuple[jax.Array, Dict]:
    dp = dp_axes(mesh)
    # scalar or per-row (B,) positions — see transformer.decode
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32),
                           (tokens.shape[0],))
    x = embed_apply(params, tokens).astype(jnp.dtype(cfg.dtype))

    def body(carry, xs):
        # the self-attention cache rides in the carry and is written in
        # place (see transformer._scan_decode); the cross cache is read only
        x, cs = carry
        pl, cc, i = xs
        h, cs = attn.attn_decode(cfg, pl["attn"], apply_norm(cfg, pl["norm1"], x),
                                 cs, i, pos)
        x = x + h
        x = x + attn.cross_decode(cfg, pl["cross"],
                                  apply_norm(cfg, pl["norm_x"], x), cc)
        x = x + mlp_apply(cfg, pl["mlp"], apply_norm(cfg, pl["norm2"], x))
        return (x, cs), None

    (x, new_self), _ = jax.lax.scan(
        body, (x, cache["self"]),
        (params["dec_layers"], cache["cross"], jnp.arange(cfg.n_layers)))
    x = apply_norm(cfg, params["final_norm"], x)
    logits = unembed_apply(cfg, params, x)
    logits = constrain(logits, mesh, P(dp if dp else None, None, "model"))
    return logits, {"pos": pos + 1, "self": new_self, "cross": cache["cross"]}
