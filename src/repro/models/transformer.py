"""Unified decoder-LM engine: every assigned non-enc-dec architecture
(dense GQA/MQA, MLA+MoE, softmax-MoE, Mamba-2 SSD, Hymba hybrid) is a
configuration of this module.  Layers are scanned (stacked params) so the
HLO is O(1) in depth; a separate small scan handles DeepSeek's leading
dense layers.

Paths: ``forward`` (training, full-seq causal), ``prefill`` (builds the
cache), ``decode`` (one token, fixed shapes).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import attention as attn
from . import moe as moe_mod
from . import ssm as ssm_mod
from .config import ModelConfig
from .layers import (apply_norm, embed_apply, embed_specs, mlp_apply, mlp_specs, norm_specs, unembed_apply)
from .param import ParamSpec

__all__ = ["decoder_specs", "forward", "prefill", "decode", "init_cache",
           "dp_axes", "constrain"]


def dp_axes(mesh: Optional[Mesh]) -> Tuple[str, ...]:
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def constrain(x: jax.Array, mesh: Optional[Mesh], spec: P) -> jax.Array:
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _residual_spec(cfg: ModelConfig, mesh: Optional[Mesh]) -> P:
    """Residual-stream sharding: batch over dp; optionally the sequence dim
    over 'model' (sequence parallelism — converts per-layer TP all-reduces
    into reduce-scatter/all-gather pairs, halving collective bytes)."""
    dp = dp_axes(mesh)
    seq = "model" if (cfg.seq_shard and mesh is not None
                      and "model" in mesh.axis_names) else None
    return P(dp if dp else None, seq, None)


def _remat(cfg: ModelConfig, body):
    if not cfg.remat or cfg.remat_policy == "none":
        return body
    pol = (jax.checkpoint_policies.nothing_saveable
           if cfg.remat_policy == "full"
           else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body, policy=pol)


def _unroll(cfg: ModelConfig, length: int) -> int:
    return max(1, min(cfg.scan_unroll, length))


# ------------------------------------------------------------------ specs


def _mixer_specs(cfg: ModelConfig, L: int) -> Dict[str, Any]:
    s: Dict[str, Any] = {}
    if cfg.has_attention:
        if cfg.attn_type == "mla":
            s["attn"] = attn.mla_specs(cfg, L)
        else:
            s["attn"] = attn.gqa_specs(cfg, L)
    if cfg.has_ssm:
        s["ssm"] = ssm_mod.ssm_specs(cfg, L)
    if cfg.family == "hybrid":
        s["alpha_attn"] = ParamSpec((L, cfg.d_model), ("layer", "embed"),
                                    init="ones", dtype=cfg.dtype)
        s["alpha_ssm"] = ParamSpec((L, cfg.d_model), ("layer", "embed"),
                                   init="ones", dtype=cfg.dtype)
    return s


def _layer_specs(cfg: ModelConfig, L: int, use_moe: bool) -> Dict[str, Any]:
    s: Dict[str, Any] = {"norm1": norm_specs(cfg, L)}
    s.update(_mixer_specs(cfg, L))
    if cfg.is_moe and use_moe:
        s["norm2"] = norm_specs(cfg, L)
        s["moe"] = moe_mod.moe_specs(cfg, L)
    elif cfg.d_ff > 0:
        s["norm2"] = norm_specs(cfg, L)
        s["mlp"] = mlp_specs(cfg, L)
    return s


def decoder_specs(cfg: ModelConfig) -> Dict[str, Any]:
    n_moe = cfg.n_layers - cfg.first_dense_layers
    s: Dict[str, Any] = dict(embed_specs(cfg))
    if cfg.first_dense_layers > 0:
        dense_cfg = cfg.with_(n_experts=0)
        s["dense_layers"] = _layer_specs(dense_cfg, cfg.first_dense_layers,
                                         use_moe=False)
        s["layers"] = _layer_specs(cfg, n_moe, use_moe=True)
    else:
        s["layers"] = _layer_specs(cfg, cfg.n_layers, use_moe=cfg.is_moe)
    s["final_norm"] = norm_specs(cfg)
    return s


# ------------------------------------------------------------------ layer


# Every layer path names its pieces with the same scopes (``attention``,
# ``ssd``, ``mlp``, ``moe``, ``norm``; ``embed`` and ``lm_head`` around the
# stack), so a profiler trace can attribute device time to a layer kind.
# A scope changes op metadata only, never the program.


def _mix(cfg: ModelConfig, pl: Dict, x: jax.Array, attn_fn, ssm_fn
         ) -> Tuple[jax.Array, Dict]:
    """``x`` plus the layer's sequence mixer, under its scope: attention,
    the SSD, or both (hybrid, each in its own).  ``attn_fn``/``ssm_fn``
    return (output, cache).  The residual add sits in the scope, so an
    output projection fused into it is counted with its mixer."""
    if cfg.family == "hybrid":
        with jax.named_scope("attention"):
            a, ca = attn_fn()
        with jax.named_scope("ssd"):
            s, cs = ssm_fn()
        return (x + 0.5 * (a * pl["alpha_attn"] + s * pl["alpha_ssm"]),
                {"attn": ca, "ssm": cs})
    if cfg.has_ssm:
        with jax.named_scope("ssd"):
            mix, cs = ssm_fn()
            return x + mix, {"ssm": cs}
    with jax.named_scope("attention"):
        mix, ca = attn_fn()
        return x + mix, {"attn": ca}


def _norm(cfg: ModelConfig, p: Dict, x: jax.Array) -> jax.Array:
    with jax.named_scope("norm"):
        return apply_norm(cfg, p, x)


def _ffn(cfg: ModelConfig, mesh: Optional[Mesh], use_moe: bool, pl: Dict,
         x: jax.Array) -> jax.Array:
    """The layer's second half: ``x`` plus the MoE or MLP of its norm (the
    residual add in the scope, as in ``_mix``)."""
    if use_moe and cfg.is_moe:
        h = _norm(cfg, pl["norm2"], x)
        with jax.named_scope("moe"):
            return x + moe_mod.moe_apply(cfg, pl["moe"], h, mesh=mesh)
    if cfg.d_ff > 0:
        h = _norm(cfg, pl["norm2"], x)
        with jax.named_scope("mlp"):
            return x + mlp_apply(cfg, pl["mlp"], h)
    return x


def _embed(cfg: ModelConfig, params: Dict, tokens: jax.Array) -> jax.Array:
    with jax.named_scope("embed"):
        return embed_apply(params, tokens).astype(jnp.dtype(cfg.dtype))


def _head(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    """Final norm, then the output head's logits."""
    x = _norm(cfg, params["final_norm"], x)
    with jax.named_scope("lm_head"):
        return unembed_apply(cfg, params, x)


def _layer_train(cfg: ModelConfig, mesh: Optional[Mesh], use_moe: bool,
                 x: jax.Array, pl: Dict) -> jax.Array:
    h = _norm(cfg, pl["norm1"], x)
    x, _ = _mix(cfg, pl, x,
                lambda: (attn.attn_train(cfg, pl["attn"], h), None),
                lambda: (ssm_mod.ssm_train(cfg, pl["ssm"], h), None))
    x = constrain(x, mesh, _residual_spec(cfg, mesh))
    return constrain(_ffn(cfg, mesh, use_moe, pl, x), mesh,
                     _residual_spec(cfg, mesh))


def _scan_stack(cfg: ModelConfig, mesh, use_moe, x, stacked):
    fn = functools.partial(_layer_train, cfg, mesh, use_moe)

    def body(carry, pl):
        return fn(carry, pl), None

    body = _remat(cfg, body)
    L = jax.tree.leaves(stacked)[0].shape[0]
    x, _ = jax.lax.scan(body, x, stacked, unroll=_unroll(cfg, L))
    return x


# ---------------------------------------------------------------- forward


def forward(cfg: ModelConfig, params: Dict, tokens: jax.Array,
            mesh: Optional[Mesh] = None) -> jax.Array:
    """Training forward: tokens (B, S) -> logits (B, S, Vp)."""
    dp = dp_axes(mesh)
    x = _embed(cfg, params, tokens)
    x = constrain(x, mesh, P(dp if dp else None, None, None))
    if cfg.first_dense_layers > 0:
        dense_cfg = cfg.with_(n_experts=0)
        x = _scan_stack(dense_cfg, mesh, False, x, params["dense_layers"])
    x = _scan_stack(cfg, mesh, cfg.is_moe, x, params["layers"])
    logits = _head(cfg, params, x)
    return constrain(logits, mesh, P(dp if dp else None, None, "model"))


# ------------------------------------------------------------------ cache


def _layer_cache(cfg: ModelConfig, B: int, cache_len: int, dtype) -> Dict:
    c: Dict[str, Any] = {}
    if cfg.has_attention:
        c["attn"] = attn.init_attn_cache(cfg, B, cache_len, dtype)
    if cfg.has_ssm:
        c["ssm"] = ssm_mod.init_ssm_cache(cfg, B, dtype)
    return c


def _stack_cache(cache: Dict, L: int) -> Dict:
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (L,) + a.shape).copy()
                        if L else a, cache)


def init_cache(cfg: ModelConfig, B: int, cache_len: int) -> Dict:
    dt = jnp.dtype(cfg.dtype)
    # per-row positions from the start (see decode): the cache keeps one
    # shape whether or not a serving engine ever staggers its slots
    out: Dict[str, Any] = {"pos": jnp.zeros((B,), jnp.int32)}
    n_moe = cfg.n_layers - cfg.first_dense_layers
    if cfg.first_dense_layers > 0:
        out["dense"] = _stack_cache(
            _layer_cache(cfg.with_(n_experts=0), B, cache_len, dt),
            cfg.first_dense_layers)
        out["layers"] = _stack_cache(_layer_cache(cfg, B, cache_len, dt), n_moe)
    else:
        out["layers"] = _stack_cache(_layer_cache(cfg, B, cache_len, dt),
                                     cfg.n_layers)
    return out


# ---------------------------------------------------------------- prefill


def _layer_prefill(cfg, mesh, use_moe, x, pl):
    h = _norm(cfg, pl["norm1"], x)
    x, new_c = _mix(cfg, pl, x, lambda: attn.attn_prefill(cfg, pl["attn"], h),
                    lambda: ssm_mod.ssm_prefill(cfg, pl["ssm"], h))
    x = constrain(x, mesh, _residual_spec(cfg, mesh))
    return (constrain(_ffn(cfg, mesh, use_moe, pl, x), mesh,
                      _residual_spec(cfg, mesh)), new_c)


def _scan_prefill(cfg, mesh, use_moe, x, stacked):
    fn = functools.partial(_layer_prefill, cfg, mesh, use_moe)

    def body(carry, pl):
        x2, c = fn(carry, pl)
        return x2, c

    body = _remat(cfg, body)
    L = jax.tree.leaves(stacked)[0].shape[0]
    return jax.lax.scan(body, x, stacked, unroll=_unroll(cfg, L))


def prefill(cfg: ModelConfig, params: Dict, tokens: jax.Array,
            cache_len: int, mesh: Optional[Mesh] = None
            ) -> Tuple[jax.Array, Dict]:
    """Process the prompt; returns (logits for last position, cache).

    The cache is padded/relaid to ``cache_len`` slots.
    """
    B, S = tokens.shape
    dp = dp_axes(mesh)
    x = _embed(cfg, params, tokens)
    x = constrain(x, mesh, P(dp if dp else None, None, None))
    cache: Dict[str, Any] = {"pos": jnp.full((B,), S, jnp.int32)}
    if cfg.first_dense_layers > 0:
        x, cd = _scan_prefill(cfg.with_(n_experts=0), mesh, False, x,
                              params["dense_layers"])
        cache["dense"] = _pad_cache(cfg.with_(n_experts=0), cd, S, cache_len)
        x, cl = _scan_prefill(cfg, mesh, True, x, params["layers"])
        cache["layers"] = _pad_cache(cfg, cl, S, cache_len)
    else:
        x, cl = _scan_prefill(cfg, mesh, cfg.is_moe, x, params["layers"])
        cache["layers"] = _pad_cache(cfg, cl, S, cache_len)
    logits = _head(cfg, params, x[:, -1:, :])
    return logits, cache


def _pad_cache(cfg: ModelConfig, c: Dict, S: int, cache_len: int) -> Dict:
    """Grow prefill caches (seq dim S or ring W) to the serving cache_len."""
    def grow(path_a):
        def g(a):
            return a
        return g

    def pad_leaf(a, target_len, axis):
        pad = target_len - a.shape[axis]
        if pad <= 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, pad)
        if a.dtype == jnp.int32:
            return jnp.pad(a, widths, constant_values=-1)
        return jnp.pad(a, widths)

    out = dict(c)
    if "attn" in c:
        ac = dict(c["attn"])
        if cfg.attn_type == "mla":
            ac["ckv"] = pad_leaf(ac["ckv"], cache_len, 2)
            ac["kr"] = pad_leaf(ac["kr"], cache_len, 2)
        else:
            W = min(cfg.window, cache_len) if cfg.window else cache_len
            ac["k"] = pad_leaf(ac["k"], W, 2)
            ac["v"] = pad_leaf(ac["v"], W, 2)
            ac["kpos"] = pad_leaf(ac["kpos"], W, 2)    # (L, B, W) per-row
        out["attn"] = ac
    return out


# ----------------------------------------------------------------- decode


def _scan_decode(cfg, mesh, use_moe, x, stacked, cache, pos):
    """One token through a layer stack.  The stack's cache rides in the
    scan's carry beside the residual, so each layer writes only what it
    changes into the donated buffer, in place: no cache leaf is a scan
    input or output, which would slice every layer out, write a second
    stacked cache and copy it whole."""
    def body(carry, xs):
        x, c = carry
        pl, i = xs
        h = _norm(cfg, pl["norm1"], x)
        x, c = _mix(
            cfg, pl, x,
            lambda: attn.attn_decode(cfg, pl["attn"], h, c["attn"], i, pos),
            lambda: ssm_mod.ssm_decode(cfg, pl["ssm"], h, c["ssm"], i))
        return (_ffn(cfg, mesh, use_moe, pl, x), c), None

    L = jax.tree.leaves(stacked)[0].shape[0]
    (x, cache), _ = jax.lax.scan(body, (x, cache), (stacked, jnp.arange(L)),
                                 unroll=_unroll(cfg, L))
    return x, cache


def decode(cfg: ModelConfig, params: Dict, cache: Dict, tokens: jax.Array,
           mesh: Optional[Mesh] = None) -> Tuple[jax.Array, Dict]:
    """One decode step.  tokens: (B, 1) -> (logits (B, 1, Vp), new cache).

    ``cache['pos']`` may be a scalar (every row at the same depth — the
    plain prefill-then-decode flow) or a per-row (B,) vector (continuous
    batching: a serving engine re-prefilled some slots mid-decode).  It
    is normalized to (B,) here so attention layers always see per-row
    positions."""
    dp = dp_axes(mesh)
    pos = jnp.broadcast_to(jnp.asarray(cache["pos"], jnp.int32),
                           (tokens.shape[0],))
    x = _embed(cfg, params, tokens)
    x = constrain(x, mesh, P(dp if dp else None, None, None))
    new_cache: Dict[str, Any] = {"pos": pos + 1}
    if cfg.first_dense_layers > 0:
        x, new_cache["dense"] = _scan_decode(
            cfg.with_(n_experts=0), mesh, False, x, params["dense_layers"],
            cache["dense"], pos)
    x, new_cache["layers"] = _scan_decode(cfg, mesh, cfg.is_moe, x,
                                          params["layers"], cache["layers"],
                                          pos)
    logits = _head(cfg, params, x)
    logits = constrain(logits, mesh, P(dp if dp else None, None, "model"))
    return logits, new_cache
