"""Mixture-of-Experts FFN with expert parallelism.

Design (DESIGN.md §6): activations are replicated across the ``model`` mesh
axis between layers (standard TP), so every model shard already holds all
local-batch tokens.  Experts are sharded over ``model`` (EP); each shard
gathers the tokens routed to *its* experts (capacity-bounded, GShard-style
dropping), runs the expert FFNs, scatters gate-weighted outputs back, and
the cross-shard combine is a single psum — the same collective TP already
pays for the FFN, i.e. **no token all-to-all is required**.  The psum is an
explicit collective planned/costed by COMET (core integration); the
alternative all-to-all dispatch is evaluated as a mapping variant in the
benchmarks.

Routing: softmax top-k (Qwen3-style, renormalized) or sigmoid+bias
(DeepSeek-V3 aux-free) per ``cfg.router_type``.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .param import ParamSpec

F32 = jnp.float32

__all__ = ["moe_specs", "moe_apply", "moe_local", "router_weights"]


def moe_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    s = {
        "router": ParamSpec((L, d, E), ("layer", "embed", None), dtype="float32"),
        "wi": ParamSpec((L, E, d, f), ("layer", "experts", "embed", None), dtype=cfg.dtype),
        "wg": ParamSpec((L, E, d, f), ("layer", "experts", "embed", None), dtype=cfg.dtype),
        "wo": ParamSpec((L, E, f, d), ("layer", "experts", None, "embed"), dtype=cfg.dtype),
    }
    if cfg.router_type == "sigmoid":
        s["router_bias"] = ParamSpec((L, E), ("layer", None), init="zeros",
                                     dtype="float32")
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        s["shared_wi"] = ParamSpec((L, d, fs), ("layer", "embed", "ff"), dtype=cfg.dtype)
        s["shared_wg"] = ParamSpec((L, d, fs), ("layer", "embed", "ff"), dtype=cfg.dtype)
        s["shared_wo"] = ParamSpec((L, fs, d), ("layer", "ff", "embed"), dtype=cfg.dtype)
    return s


def router_weights(cfg: ModelConfig, p: Dict, x2d: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """(gates (T, k) f32, idx (T, k) int32)."""
    logits = (x2d.astype(F32) @ p["router"].astype(F32))
    if cfg.router_type == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores + p["router_bias"].astype(F32)       # bias only for routing
        _, idx = jax.lax.top_k(sel, cfg.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
    else:
        _, idx = jax.lax.top_k(logits, cfg.top_k)
        sel_logits = jnp.take_along_axis(logits, idx, axis=-1)
        gates = jax.nn.softmax(sel_logits, axis=-1)
    return gates, idx


def moe_local(cfg: ModelConfig, x2d: jax.Array, gates: jax.Array,
              idx: jax.Array, wi: jax.Array, wg: jax.Array, wo: jax.Array,
              e_offset: int, e_local: int, capacity: int) -> jax.Array:
    """Local-expert contribution for tokens x2d (T, d).

    wi/wg: (e_local, d, f); wo: (e_local, f, d).  Tokens routed to experts
    in [e_offset, e_offset + e_local) are gathered into (e_local, C, d)
    buffers (capacity-dropped), processed, and scatter-added back.
    Pure local computation — caller psums across expert shards.
    """
    T, d = x2d.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)                              # (T*k,)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)

    # position of each assignment within its expert (stable by token order)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(cfg.n_experts))
    pos_sorted = jnp.arange(T * k) - seg_start[sorted_e]
    pos = jnp.zeros_like(pos_sorted).at[order].set(pos_sorted)

    local = (flat_e >= e_offset) & (flat_e < e_offset + e_local) & (pos < capacity)
    slot = jnp.where(local, (flat_e - e_offset) * capacity + pos, e_local * capacity)

    # dispatch: (e_local*C + 1 overflow row, d)
    buf = jnp.zeros((e_local * capacity + 1, d), x2d.dtype)
    buf = buf.at[slot].add(jnp.where(local[:, None], x2d[flat_t], 0))
    xe = buf[:-1].reshape(e_local, capacity, d)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg)) \
        * jnp.einsum("ecd,edf->ecf", xe, wi)
    ye = jnp.einsum("ecf,efd->ecd", h, wo).reshape(e_local * capacity, d)
    ye = jnp.concatenate([ye, jnp.zeros((1, d), ye.dtype)], 0)

    # combine: gate-weighted scatter-add back to tokens
    contrib = ye[slot] * jnp.where(local, flat_g, 0.0)[:, None].astype(ye.dtype)
    out = jnp.zeros_like(x2d).at[flat_t].add(contrib)
    return out


def _shared_ffn(p: Dict, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["shared_wg"]) * (x @ p["shared_wi"])) @ p["shared_wo"]


def moe_apply(cfg: ModelConfig, p: Dict, x: jax.Array,
              mesh: Optional[jax.sharding.Mesh] = None) -> jax.Array:
    """MoE FFN.  x: (B, S, d).  With a mesh, experts are sharded over the
    'model' axis via shard_map; without (CPU smoke tests) all experts are
    local."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    x2d = x.reshape(B * S, d)
    T = B * S

    def _cap(t_loc: int) -> int:
        # expected load + slack; small token populations (decode steps,
        # smoke tests) are dropless — production sizes use the float factor
        return max(int(t_loc * k / E * cfg.capacity_factor),
                   min(t_loc, 32))

    if mesh is None or "model" not in mesh.axis_names:
        capacity = _cap(T)
        gates, idx = router_weights(cfg, p, x2d)
        out = moe_local(cfg, x2d, gates, idx, p["wi"], p["wg"], p["wo"],
                        0, E, capacity)
    else:
        import math as _math
        from jax.sharding import PartitionSpec as P
        ep = mesh.shape["model"]
        assert E % ep == 0, (E, ep)
        e_local = E // ep
        # usable dp axes: token count must divide evenly for shard_map
        dp_axes = []
        dp_n = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names and T % (dp_n * mesh.shape[a]) == 0:
                dp_axes.append(a)
                dp_n *= mesh.shape[a]
        dp_axes = tuple(dp_axes)
        t_local = T // dp_n
        capacity = _cap(t_local)

        def shard_fn(x_l, router, rbias, wi, wg, wo):
            pp = {"router": router}
            if rbias is not None:
                pp["router_bias"] = rbias
            gates, idx = router_weights(cfg, pp, x_l)
            ei = jax.lax.axis_index("model") * e_local
            y = moe_local(cfg, x_l, gates, idx, wi, wg, wo, ei, e_local,
                          capacity)
            return jax.lax.psum(y, "model")

        rbias = p.get("router_bias")
        out = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(dp_axes if dp_axes else None, None),
                      P(None, None),
                      (P(None) if rbias is not None else P(None)),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(dp_axes if dp_axes else None, None),
            check_vma=False,
        )(x2d, p["router"], rbias if rbias is not None else
          jnp.zeros((E,), F32), p["wi"], p["wg"], p["wo"])

    if cfg.n_shared_experts:
        out = out + _shared_ffn(p, x2d)
    return out.reshape(B, S, d)
