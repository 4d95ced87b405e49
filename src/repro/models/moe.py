"""Mixture-of-Experts FFN with expert parallelism.

Design (DESIGN.md §6): activations are replicated across the ``model`` mesh
axis between layers (standard TP), so every model shard already holds all
local-batch tokens.  Experts are sharded over ``model`` (EP); each shard
runs its own experts over the tokens routed to them and weights their
outputs by the gates, and the cross-shard combine is a single psum — the
same collective TP already pays for the FFN, i.e. **no token all-to-all is
required**.  The psum is an explicit collective planned/costed by COMET
(core integration); the alternative all-to-all dispatch is evaluated as a
mapping variant in the benchmarks.

**Held experts.**  A layer may hold only some of the experts its router
scores (``cfg.n_experts_held`` of ``cfg.n_experts``: one chip's
expert-parallel share).  It routes every token over all ``n_experts`` and
returns its held experts' part of the result, plus the shared expert;
what the other experts would add is left to the chips that hold them.

**Dropless.**  Every assignment to a held expert is computed, so a token's
result never depends on what shares its batch.  Tokens go in blocks of at
most ``TOKEN_BLOCK``, so the temporaries grow with the block, never with
all tokens × top_k, in one of two forms chosen by the shapes:

* at most ``top_k`` experts held (one chip's share of a large expert
  layer): every held expert runs every token of the block, weighted by its
  gate (0 where the token did not pick it).  That is no more rows than a
  token's assignments can be, and each expert's matmul streams its
  weights once.  On the v5e it is the faster form: in the deepseek-v3.chat
  cell a grouped matmul in its place took the decode step from 22.7 to
  35.5 ms and a refill's prefill from 24.0 to 42.6 ms.
* more held than ``top_k`` (every registry configuration without a mesh,
  or with few expert shards, in training and the dry run): the block's
  (token, expert) assignments are sorted by held expert, those to other
  experts last, and one grouped matmul (``jax.lax.ragged_dot``) per
  projection runs each held expert over its own rows: tokens × top_k rows,
  where every held expert over every token would cost held ÷ top_k times
  as much (16 for qwen3-moe, 32 for deepseek-v3-671b).

Routing: softmax top-k (Qwen3-style, renormalized) or sigmoid (DeepSeek-V3
``noaux_tc``: an optional load-balancing ``router_bias`` that only steers
the choice, the choice limited to the best ``topk_group`` of ``n_group``
expert groups, the chosen scores normalised and scaled by
``routed_scaling``) per ``cfg.router_type``.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .param import ParamSpec

F32 = jnp.float32
TOKEN_BLOCK = 1024

__all__ = ["moe_specs", "moe_apply", "moe_local", "router_weights"]


def moe_specs(cfg: ModelConfig, L: int) -> Dict[str, ParamSpec]:
    """The layer's weights.  DeepSeek-V3's correction bias is no weight:
    it is load-balancing state, set outside the gradient, which a caller
    may put in the layer's dict as ``router_bias`` (L, n_experts)."""
    d, f, E, Eh = cfg.d_model, cfg.moe_d_ff, cfg.n_experts, cfg.experts_held
    s = {
        "router": ParamSpec((L, d, E), ("layer", "embed", None), dtype="float32"),
        "wi": ParamSpec((L, Eh, d, f), ("layer", "experts", "embed", None), dtype=cfg.dtype),
        "wg": ParamSpec((L, Eh, d, f), ("layer", "experts", "embed", None), dtype=cfg.dtype),
        "wo": ParamSpec((L, Eh, f, d), ("layer", "experts", None, "embed"), dtype=cfg.dtype),
    }
    if cfg.n_shared_experts:
        fs = cfg.moe_d_ff * cfg.n_shared_experts
        s["shared_wi"] = ParamSpec((L, d, fs), ("layer", "embed", "ff"), dtype=cfg.dtype)
        s["shared_wg"] = ParamSpec((L, d, fs), ("layer", "embed", "ff"), dtype=cfg.dtype)
        s["shared_wo"] = ParamSpec((L, fs, d), ("layer", "ff", "embed"), dtype=cfg.dtype)
    return s


def router_weights(cfg: ModelConfig, p: Dict, x2d: jax.Array
                   ) -> Tuple[jax.Array, jax.Array]:
    """(gates (T, k) f32, idx (T, k) int32) over all ``cfg.n_experts``."""
    logits = (x2d.astype(F32) @ p["router"].astype(F32))
    if cfg.router_type == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        sel = scores
        if "router_bias" in p:                        # steers the choice only
            sel = sel + p["router_bias"].astype(F32)
        if cfg.n_group > 1:
            T, E = sel.shape
            grouped = sel.reshape(T, cfg.n_group, E // cfg.n_group)
            group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)    # (T, G)
            _, best = jax.lax.top_k(group_score, cfg.topk_group)
            keep = (best[:, :, None] == jnp.arange(cfg.n_group)).any(1)
            sel = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(T, E)
        _, idx = jax.lax.top_k(sel, cfg.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-9)
        gates = gates * cfg.routed_scaling
    else:
        _, idx = jax.lax.top_k(logits, cfg.top_k)
        sel_logits = jnp.take_along_axis(logits, idx, axis=-1)
        gates = jax.nn.softmax(sel_logits, axis=-1)
    return gates, idx


def _weighted(wi, wg, wo, xg):
    """Every held expert over every token of a block, weighted by the
    gates g (t, e_local), zero where a token did not pick the expert."""
    xb, gb = xg                                                  # (t, d), (t, e)
    xe = jnp.broadcast_to(xb, (wi.shape[0],) + xb.shape)         # (e, t, d)
    h = jax.nn.silu(jnp.einsum("etd,edf->etf", xe, wg)) \
        * jnp.einsum("etd,edf->etf", xe, wi)
    h = h * gb.T[:, :, None].astype(h.dtype)
    return jnp.einsum("etf,efd->td", h, wo)


def _grouped(wi, wg, wo, e_offset, xgi):
    """Each held expert over the rows of the tokens that picked it: the
    block's assignments sorted by held expert (others last, in no group)
    through a grouped matmul."""
    xb, gb, ib = xgi                                             # (t, d), (t, k) x2
    t, k = ib.shape
    e_local = wi.shape[0]
    e = ib.reshape(-1) - e_offset                                # (t*k,)
    key = jnp.where((e >= 0) & (e < e_local), e, e_local)
    rows = jnp.argsort(key, stable=True)
    sizes = jnp.zeros((e_local,), jnp.int32).at[key].add(1, mode="drop")
    live = (jnp.arange(t * k) < sizes.sum())[:, None]
    tok = rows // k
    xs = xb[tok]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, wg, sizes)) \
        * jax.lax.ragged_dot(xs, wi, sizes)
    h = h * jnp.where(live, gb.reshape(-1)[rows][:, None], 0.0).astype(h.dtype)
    y = jax.lax.ragged_dot(h, wo, sizes, preferred_element_type=F32)
    y = jnp.where(live, y, 0.0)
    return jnp.zeros((t, xb.shape[1]), F32).at[tok].add(y).astype(xb.dtype)


def moe_local(x2d: jax.Array, gates: jax.Array, idx: jax.Array,
              wi: jax.Array, wg: jax.Array, wo: jax.Array,
              e_offset) -> jax.Array:
    """The part of the result that experts [e_offset, e_offset + e_local)
    give tokens x2d (T, d): each token's outputs of those of its chosen
    experts, weighted by their gates; zero for the rest.

    wi/wg: (e_local, d, f); wo: (e_local, f, d).  Dropless; tokens in
    blocks of ``TOKEN_BLOCK``, each in the form the module docstring
    gives.  Pure local computation — a caller that shards experts psums
    the shares.
    """
    T, d = x2d.shape
    e_local = wi.shape[0]
    if e_local <= idx.shape[1]:
        held = jnp.arange(e_local) + e_offset                    # (e_local,)
        # (T, e_local): a token's gate for each held expert, 0 where not chosen
        g = jnp.where(idx[:, :, None] == held, gates[:, :, None], 0.0).sum(1)
        block, args = partial(_weighted, wi, wg, wo), (x2d, g)
    else:
        block, args = partial(_grouped, wi, wg, wo, e_offset), (x2d, gates, idx)

    if T <= TOKEN_BLOCK:
        return block(args)
    pad = (-T) % TOKEN_BLOCK
    nb = (T + pad) // TOKEN_BLOCK
    # padded tokens have gates 0: they add nothing, and are cut off
    blocks = tuple(jnp.pad(a, ((0, pad), (0, 0))).reshape(
        (nb, TOKEN_BLOCK) + a.shape[1:]) for a in args)
    return jax.lax.map(block, blocks).reshape(nb * TOKEN_BLOCK, d)[:T]


def _shared_ffn(p: Dict, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["shared_wg"]) * (x @ p["shared_wi"])) @ p["shared_wo"]


def moe_apply(cfg: ModelConfig, p: Dict, x: jax.Array,
              mesh: Optional[jax.sharding.Mesh] = None) -> jax.Array:
    """MoE FFN.  x: (B, S, d).  The held experts are the first
    ``cfg.experts_held`` of the router's; with a mesh they are sharded
    over the 'model' axis via shard_map, without (one chip, CPU tests)
    all of them are local."""
    B, S, d = x.shape
    Eh = cfg.experts_held
    x2d = x.reshape(B * S, d)
    T = B * S
    rbias = p.get("router_bias")

    if mesh is None or "model" not in mesh.axis_names:
        with jax.named_scope("router"):
            gates, idx = router_weights(cfg, p, x2d)
        with jax.named_scope("experts"):
            out = moe_local(x2d, gates, idx, p["wi"], p["wg"], p["wo"], 0)
    else:
        from jax.sharding import PartitionSpec as P
        ep = mesh.shape["model"]
        assert Eh % ep == 0, (Eh, ep)
        e_local = Eh // ep
        # usable dp axes: token count must divide evenly for shard_map
        dp_axes = []
        dp_n = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names and T % (dp_n * mesh.shape[a]) == 0:
                dp_axes.append(a)
                dp_n *= mesh.shape[a]
        dp_axes = tuple(dp_axes)

        def shard_fn(x_l, router, rb, wi, wg, wo):
            pp = {"router": router}
            if rbias is not None:
                pp["router_bias"] = rb
            with jax.named_scope("router"):
                gates, idx = router_weights(cfg, pp, x_l)
            with jax.named_scope("experts"):
                ei = jax.lax.axis_index("model") * e_local
                y = moe_local(x_l, gates, idx, wi, wg, wo, ei)
            return jax.lax.psum(y, "model")

        out = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(P(dp_axes if dp_axes else None, None),
                      P(None, None), P(None),
                      P("model", None, None), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(dp_axes if dp_axes else None, None),
            check_vma=False,
        )(x2d, p["router"], rbias if rbias is not None else
          jnp.zeros((cfg.n_experts,), F32), p["wi"], p["wg"], p["wo"])

    if cfg.n_shared_experts:
        with jax.named_scope("shared_expert"):
            out = out + _shared_ffn(p, x2d)
    return out.reshape(B, S, d)
