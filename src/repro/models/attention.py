"""Attention: GQA/MQA (optionally sliding-window, qk-norm), MLA (DeepSeek),
and cross-attention — with train (full-seq), prefill (cache-building) and
decode (cached, fixed-shape) paths.

Training/prefill uses a *blocked* online-softmax implementation (pure jnp
``lax.scan`` over KV blocks — the FlashAttention dataflow the paper costs,
expressed at the XLA level) so the S×S score matrix is never materialized;
``use_kernels=True`` routes through the Pallas kernel instead.  Decode uses
dense einsums over the cache (the flash-decoding merge across shards is
handled by the collective planner at the sharding level).
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import ops as kops
from .config import ModelConfig
from .layers import apply_norm, apply_rope, rope_cos_sin, yarn_mscale
from .param import ParamSpec

F32 = jnp.float32
NEG = -1e30

__all__ = [
    "gqa_specs", "mla_specs", "cross_specs",
    "attn_train", "attn_prefill", "attn_decode",
    "cross_train", "cross_decode", "make_cross_cache",
    "init_attn_cache", "blocked_attention",
]


# ============================================================ blocked attn


def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, window: Optional[int],
                      scale: float, block_k: int = 512,
                      q_offset: int = 0) -> jax.Array:
    """Online-softmax attention, scanning KV blocks.

    q: (B, Hq, Sq, Dq); k: (B, Hkv, Skv, Dq); v: (B, Hkv, Skv, Dv).
    ``q_offset``: absolute position of q[0] minus absolute position of k[0]
    (for prefill Sq == Skv -> offset 0; decode handled elsewhere).
    Returns (B, Hq, Sq, Dv) in q.dtype.
    """
    B, Hq, Sq, Dq = q.shape
    Hkv, Skv, Dv = k.shape[1], k.shape[2], v.shape[-1]
    group = Hq // Hkv
    bk = min(block_k, Skv)
    pad = (-Skv) % bk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nblk = (Skv + pad) // bk
    kb = jnp.moveaxis(k.reshape(B, Hkv, nblk, bk, Dq), 2, 0)   # (nblk,B,Hkv,bk,Dq)
    vb = jnp.moveaxis(v.reshape(B, Hkv, nblk, bk, Dv), 2, 0)
    qf = q.astype(F32)
    q_pos = jnp.arange(Sq) + q_offset                          # (Sq,)

    # grouped-query layout: (B, Hkv, group, Sq, D) — no KV repeat, so TP
    # sharding of kv-heads/seq never forces a reshard of the cache.
    qg = qf.reshape(B, Hkv, group, Sq, Dq)

    def step(carry, inp):
        m, l, acc = carry
        kblk, vblk, bi = inp
        kf = kblk.astype(F32)
        vf = vblk.astype(F32)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf) * scale
        k_pos = bi * bk + jnp.arange(bk)                       # (bk,)
        mask = k_pos[None, :] < Skv                            # padding
        if causal:
            mask = mask & (q_pos[:, None] >= k_pos[None, :])
        if window is not None:
            mask = mask & ((q_pos[:, None] - k_pos[None, :]) < window)
        s = jnp.where(mask[None, None, None], s, NEG)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhgqk,bhkd->bhgqd", p, vf)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, Hkv, group, Sq), NEG, F32)
    l0 = jnp.zeros((B, Hkv, group, Sq), F32)
    a0 = jnp.zeros((B, Hkv, group, Sq, Dv), F32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0),
                                  (kb, vb, jnp.arange(nblk)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).reshape(B, Hq, Sq, Dv)
    return out.astype(q.dtype)


def banded_window_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            window: int, scale: float) -> jax.Array:
    """Causal sliding-window self-attention in O(S·2W) instead of O(S²):
    queries are processed in blocks of W; each block attends only its
    [iW−W, iW+W) key band (beyond-paper optimization; see EXPERIMENTS §Perf
    hymba hillclimb).  Requires Sq == Skv (training/prefill self-attn)."""
    B, Hq, S, Dq = q.shape
    Hkv, Dv = k.shape[1], v.shape[-1]
    group = Hq // Hkv
    W = window
    pad = (-S) % W
    Sp = S + pad
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0))) if pad else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (W, pad), (0, 0)))   # front band pad
    vp = jnp.pad(v, ((0, 0), (0, 0), (W, pad), (0, 0)))
    nb = Sp // W
    qf = qp.astype(F32).reshape(B, Hkv, group, Sp, Dq)
    rel = W + jnp.arange(W)[:, None] - jnp.arange(2 * W)[None, :]  # q-k dist
    band_ok = (rel >= 0) & (rel < W)

    def step(_, i):
        qi = jax.lax.dynamic_slice_in_dim(qf, i * W, W, axis=3)      # (B,Hkv,g,W,D)
        ki = jax.lax.dynamic_slice_in_dim(kp, i * W, 2 * W, axis=2)  # (B,Hkv,2W,D)
        vi = jax.lax.dynamic_slice_in_dim(vp, i * W, 2 * W, axis=2)
        s = jnp.einsum("bhgqd,bhkd->bhgqk", qi, ki.astype(F32)) * scale
        k_pos = i * W - W + jnp.arange(2 * W)                         # original idx
        q_pos = i * W + jnp.arange(W)
        mask = band_ok & (k_pos[None, :] >= 0) & (k_pos[None, :] < S) \
            & (q_pos[:, None] < S)
        s = jnp.where(mask[None, None, None], s, NEG)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhgqk,bhkd->bhgqd", p, vi.astype(F32))
        return None, o

    _, outs = jax.lax.scan(step, None, jnp.arange(nb))
    # outs: (nb, B, Hkv, g, W, Dv) -> (B, Hq, Sp, Dv)
    out = jnp.moveaxis(outs, 0, 3).reshape(B, Hkv, group, Sp, Dv)
    out = out.reshape(B, Hq, Sp, Dv)
    return out[:, :, :S].astype(q.dtype)


def _attend(cfg: ModelConfig, q, k, v, *, causal, window, scale, q_offset=0):
    """Dispatch: banded-window / Pallas kernel / blocked scan / reference."""
    Dq, Dv = q.shape[-1], v.shape[-1]
    Sq, Skv = q.shape[2], k.shape[2]
    if (window is not None and causal and Sq == Skv and q_offset == 0
            and Skv >= 2 * window and cfg.banded_attention):
        return banded_window_attention(q, k, v, window=window, scale=scale)
    if cfg.use_kernels and Dq == Dv:
        return kops.mha(q, k, v, causal=causal, scale=scale, window=window,
                        use_kernel=True)
    if k.shape[2] > 1024:
        return blocked_attention(q, k, v, causal=causal, window=window,
                                 scale=scale, q_offset=q_offset)
    from ..kernels.ref import attention_ref
    if Dq == Dv and q_offset == 0:
        return attention_ref(q, k, v, causal=causal, scale=scale,
                             window=window)
    return blocked_attention(q, k, v, causal=causal, window=window,
                             scale=scale, q_offset=q_offset)


# ================================================================= specs


def gqa_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    s = {
        "wq": ParamSpec((L, d, H * hd), ("layer", "embed", "heads"), dtype=cfg.dtype),
        "wk": ParamSpec((L, d, Hkv * hd), ("layer", "embed", "kv_heads"), dtype=cfg.dtype),
        "wv": ParamSpec((L, d, Hkv * hd), ("layer", "embed", "kv_heads"), dtype=cfg.dtype),
        "wo": ParamSpec((L, H * hd, d), ("layer", "heads", "embed"), dtype=cfg.dtype),
    }
    if cfg.qk_norm:
        s["q_norm"] = ParamSpec((L, hd), ("layer", None), init="ones", dtype=cfg.dtype)
        s["k_norm"] = ParamSpec((L, hd), ("layer", None), init="ones", dtype=cfg.dtype)
    return s


def mla_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    d, H = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.hd, cfg.rope_head_dim, cfg.v_head_dim
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    return {
        "wdq": ParamSpec((L, d, qr), ("layer", "embed", None), dtype=cfg.dtype),
        "q_norm": ParamSpec((L, qr), ("layer", None), init="ones", dtype=cfg.dtype),
        "wuq": ParamSpec((L, qr, H * (dn + dr)), ("layer", None, "heads"), dtype=cfg.dtype),
        "wdkv": ParamSpec((L, d, kvr + dr), ("layer", "embed", None), dtype=cfg.dtype),
        "kv_norm": ParamSpec((L, kvr), ("layer", None), init="ones", dtype=cfg.dtype),
        "wuk": ParamSpec((L, kvr, H * dn), ("layer", None, "heads"), dtype=cfg.dtype),
        "wuv": ParamSpec((L, kvr, H * dv), ("layer", None, "heads"), dtype=cfg.dtype),
        "wo": ParamSpec((L, H * dv, d), ("layer", "heads", "embed"), dtype=cfg.dtype),
    }


def cross_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    return gqa_specs(cfg, L)


# =============================================================== GQA paths


def _qkv(cfg: ModelConfig, p, x, positions):
    B, S, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, Hkv, hd)
    v = (x @ p["wv"]).reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        qn = {"scale": p["q_norm"]}
        kn = {"scale": p["k_norm"]}
        if cfg.norm_type == "layernorm":
            qn["bias"] = jnp.zeros_like(p["q_norm"])
            kn["bias"] = jnp.zeros_like(p["k_norm"])
        q = apply_norm(cfg, qn, q)
        k = apply_norm(cfg, kn, k)
    cos, sin = rope_cos_sin(positions, hd, cfg)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    return q, k, v


def attn_train(cfg: ModelConfig, p, x, *, causal: bool = True) -> jax.Array:
    if cfg.attn_type == "mla":
        return _mla_train(cfg, p, x)
    B, S, d = x.shape
    q, k, v = _qkv(cfg, p, x, jnp.arange(S))
    scale = 1.0 / math.sqrt(cfg.hd)
    o = _attend(cfg, q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=causal, window=cfg.window,
                scale=scale)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.hd)
    return o @ p["wo"]


def init_attn_cache(cfg: ModelConfig, B: int, cache_len: int, dtype) -> Dict:
    """Fixed-shape cache.  Windowed layers use a ring buffer of width
    min(window, cache_len); global layers use the full length.  ``kpos``
    is per-row (B, W): decode positions are per-slot so a serving engine
    can re-prefill one slot while the others keep decoding."""
    if cfg.attn_type == "mla":
        return {
            "ckv": jnp.zeros((B, cache_len, cfg.kv_lora_rank), dtype),
            "kr": jnp.zeros((B, cache_len, cfg.rope_head_dim), dtype),
        }
    W = min(cfg.window, cache_len) if cfg.window else cache_len
    return {
        "k": jnp.zeros((B, W, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((B, W, cfg.n_kv_heads, cfg.hd), dtype),
        "kpos": jnp.full((B, W), -1, jnp.int32),
    }


def attn_prefill(cfg: ModelConfig, p, x) -> Tuple[jax.Array, Dict]:
    """Full-sequence forward that also returns the populated cache."""
    B, S, d = x.shape
    if cfg.attn_type == "mla":
        o, ckv, kr = _mla_train(cfg, p, x, return_cache=True)
        return o, {"ckv": ckv, "kr": kr}
    q, k, v = _qkv(cfg, p, x, jnp.arange(S))
    scale = 1.0 / math.sqrt(cfg.hd)
    o = _attend(cfg, q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True, window=cfg.window,
                scale=scale)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * cfg.hd)
    if cfg.window and cfg.window < S:
        W = cfg.window
        # last W positions land at ring slots (pos % W)
        pos = jnp.arange(S - W, S)
        slots = pos % W
        k_ring = jnp.zeros((B, W) + k.shape[2:], k.dtype).at[:, slots].set(k[:, S - W:])
        v_ring = jnp.zeros((B, W) + v.shape[2:], v.dtype).at[:, slots].set(v[:, S - W:])
        kpos = jnp.full((W,), -1, jnp.int32).at[slots].set(pos)
        cache = {"k": k_ring, "v": v_ring,
                 "kpos": jnp.broadcast_to(kpos, (B, W))}
    else:
        cache = {"k": k, "v": v,
                 "kpos": jnp.broadcast_to(
                     jnp.arange(k.shape[1], dtype=jnp.int32),
                     (B, k.shape[1]))}
    return o @ p["wo"], cache


def _layer(a: jax.Array, i: jax.Array) -> jax.Array:
    """Layer ``i`` of a stacked cache leaf (L, ...)."""
    return jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)


def attn_decode(cfg: ModelConfig, p, x, cache: Dict, i: jax.Array,
                pos: jax.Array) -> Tuple[jax.Array, Dict]:
    """One-token decode of layer ``i``.  x: (B, 1, d); ``cache`` is the
    layer-stacked cache (leading axis L), into which the new position is
    written in place and from which layer ``i`` is read; it is returned
    with only that position changed.  pos: per-row (B,) int32 (per-slot
    positions — continuous-batching engines re-prefill individual slots,
    so rows may sit at different depths)."""
    if cfg.attn_type == "mla":
        return _mla_decode(cfg, p, x, cache, i, pos)
    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q, k1, v1 = _qkv(cfg, p, x, pos[:, None])      # per-row RoPE positions
    W = cache["k"].shape[2]
    slot = pos % W
    rows = jnp.arange(B)
    cache = {"k": cache["k"].at[i, rows, slot].set(k1[:, 0]),
             "v": cache["v"].at[i, rows, slot].set(v1[:, 0]),
             "kpos": cache["kpos"].at[i, rows, slot].set(pos)}
    k, v, kpos = (_layer(cache[n], i) for n in ("k", "v", "kpos"))
    scale = 1.0 / math.sqrt(hd)
    group = H // Hkv
    qg = q.astype(F32).reshape(B, Hkv, group, hd)              # grouped layout
    kf = k.astype(F32)
    vf = v.astype(F32)
    s = jnp.einsum("bhgd,bshd->bhgs", qg, kf) * scale
    valid = (kpos >= 0) & (kpos <= pos[:, None])               # (B, W)
    if cfg.window:
        valid = valid & (kpos > (pos - cfg.window)[:, None])
    s = jnp.where(valid[:, None, None, :], s, NEG)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgs,bshd->bhgd", pr, vf).astype(x.dtype)
    o = o.reshape(B, 1, H * hd)
    return o @ p["wo"], cache


# =============================================================== MLA paths


def _mla_scale(cfg) -> float:
    """Softmax scale over the (nope + rope) query width, times YaRN's
    mscale squared."""
    return yarn_mscale(cfg) ** 2 / math.sqrt(cfg.hd + cfg.rope_head_dim)


def _mla_q(cfg, p, x, positions):
    B, S, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.hd, cfg.rope_head_dim
    cq = x @ p["wdq"]
    cq = apply_norm(cfg.with_(norm_type="rmsnorm"), {"scale": p["q_norm"]}, cq)
    q = (cq @ p["wuq"]).reshape(B, S, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    cos, sin = rope_cos_sin(positions, dr, cfg)
    q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _mla_kv_compress(cfg, p, x, positions):
    kvr, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    ckv_full = x @ p["wdkv"]                                   # (B,S,kvr+dr)
    ckv, kr = ckv_full[..., :kvr], ckv_full[..., kvr:]
    ckv = apply_norm(cfg.with_(norm_type="rmsnorm"), {"scale": p["kv_norm"]}, ckv)
    cos, sin = rope_cos_sin(positions, dr, cfg)
    kr = apply_rope(kr[:, :, None, :], cos, sin)[:, :, 0, :]   # shared across heads
    return ckv, kr


def _mla_train(cfg, p, x, *, return_cache: bool = False):
    B, S, _ = x.shape
    H, dn, dr, dv = cfg.n_heads, cfg.hd, cfg.rope_head_dim, cfg.v_head_dim
    positions = jnp.arange(S)
    q_nope, q_rope = _mla_q(cfg, p, x, positions)
    ckv, kr = _mla_kv_compress(cfg, p, x, positions)
    k_nope = (ckv @ p["wuk"]).reshape(B, S, H, dn)
    v = (ckv @ p["wuv"]).reshape(B, S, H, dv)
    q = jnp.concatenate([q_nope, q_rope], -1)                  # (B,S,H,dn+dr)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(kr[:, :, None, :],
                                                  (B, S, H, dr))], -1)
    o = _attend(cfg, q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=True, window=None,
                scale=_mla_scale(cfg))
    o = o.transpose(0, 2, 1, 3).reshape(B, S, H * dv)
    out = o @ p["wo"]
    if return_cache:
        return out, ckv, kr
    return out


def _mla_decode(cfg, p, x, cache, i, pos):
    """Absorbed MLA decode of layer ``i``: attention runs in the latent
    (kv_lora) space — the compressed cache is never decompressed (DeepSeek
    inference opt.).  The new position is written into the stacked latent
    cache in place, as in ``attn_decode``; ``pos`` is per-row (B,)."""
    B = x.shape[0]
    H, dn, dr, dv = cfg.n_heads, cfg.hd, cfg.rope_head_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    posv = pos[:, None]                                        # (B,1)
    q_nope, q_rope = _mla_q(cfg, p, x, posv)                   # (B,1,H,*)
    ckv1, kr1 = _mla_kv_compress(cfg, p, x, posv)              # (B,1,kvr),(B,1,dr)
    rows = jnp.arange(B)
    cache = {"ckv": cache["ckv"].at[i, rows, pos].set(ckv1[:, 0]),
             "kr": cache["kr"].at[i, rows, pos].set(kr1[:, 0])}
    ckv, kr = _layer(cache["ckv"], i), _layer(cache["kr"], i)
    S = ckv.shape[1]
    wuk = p["wuk"].reshape(kvr, H, dn)
    # absorb: q_lat[b,h,:] = W_uk[:,h,:] @ q_nope[b,h,:]
    q_lat = jnp.einsum("bhd,khd->bhk", q_nope[:, 0].astype(F32),
                       wuk.astype(F32))                        # (B,H,kvr)
    s = jnp.einsum("bhk,bsk->bhs", q_lat, ckv.astype(F32))
    s = s + jnp.einsum("bhr,bsr->bhs", q_rope[:, 0].astype(F32),
                       kr.astype(F32))
    s = s * _mla_scale(cfg)
    mask = jnp.arange(S)[None, :] <= pos[:, None]              # (B, S)
    s = jnp.where(mask[:, None, :], s, NEG)
    pr = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhs,bsk->bhk", pr, ckv.astype(F32))  # (B,H,kvr)
    wuv = p["wuv"].reshape(kvr, H, dv)
    o = jnp.einsum("bhk,khd->bhd", ctx_lat, wuv.astype(F32)).astype(x.dtype)
    o = o.reshape(B, 1, H * dv)
    return o @ p["wo"], cache


# ============================================================ cross-attn


def cross_train(cfg: ModelConfig, p, x, enc: jax.Array) -> jax.Array:
    """Decoder cross-attention over encoder output ``enc`` (B, Se, d)."""
    B, S, d = x.shape
    Se = enc.shape[1]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (enc @ p["wk"]).reshape(B, Se, Hkv, hd)
    v = (enc @ p["wv"]).reshape(B, Se, Hkv, hd)
    scale = 1.0 / math.sqrt(hd)
    o = _attend(cfg, q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=False, window=None, scale=scale)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return o @ p["wo"]


def make_cross_cache(cfg: ModelConfig, p, enc: jax.Array) -> Dict:
    B, Se, _ = enc.shape
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    return {"k": (enc @ p["wk"]).reshape(B, Se, Hkv, hd),
            "v": (enc @ p["wv"]).reshape(B, Se, Hkv, hd)}


def cross_decode(cfg: ModelConfig, p, x, cross_cache: Dict) -> jax.Array:
    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    group = H // Hkv
    q = (x @ p["wq"]).reshape(B, H, hd)
    k, v = cross_cache["k"], cross_cache["v"]
    kf = jnp.repeat(k.astype(F32), group, axis=2) if group > 1 else k.astype(F32)
    vf = jnp.repeat(v.astype(F32), group, axis=2) if group > 1 else v.astype(F32)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(F32), kf) / math.sqrt(hd)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhs,bshd->bhd", pr, vf).astype(x.dtype).reshape(B, 1, H * hd)
    return o @ p["wo"]
