"""COMET-driven collective planning (DESIGN.md §2, model-level use).

The paper's central case study — distSM vs SM for a softmax whose reduction
dimension is sharded — occurs in this framework wherever the vocabulary-
sharded logits feed the cross-entropy loss (every training cell) and in
TP/flash-decoding attention merges.  This module:

1. ``plan_softmax_strategy``: costs both mappings with the COMET collective
   model (Eq. 3/4) on the actual mesh/tensor shapes and returns the
   cheaper one — 'dist' (two All-Reduces over M×1 stats, operate in place)
   or 'gather' (All-Gather the sharded rows, compute locally).
2. ``sharded_softmax_xent``: shard_map implementation of BOTH strategies —
   the framework's explicit-collective realization of Fig. 4(c).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.collectives import collective_cost, noc_latency
from repro.core.hardware import tpu_v5e

F32 = jnp.float32

__all__ = ["softmax_collective_schedule", "plan_softmax_strategy",
           "sharded_softmax_xent", "DeclaredCollective",
           "train_collective_schedule", "price_collective_schedule"]


def softmax_collective_schedule(strategy: str, rows: int, cols: int,
                                participants: int, *,
                                dp_participants: int = 1):
    """The DECLARED collective schedule of :func:`sharded_softmax_xent` —
    the single source of truth that both the planner (which costs it) and
    the static contract checker (``repro.analysis.contracts``, which
    audits the traced jaxpr against it) consume.  If the implementation
    gains or loses a collective, this list must change with it or the
    contract check fails.

    Returns ``[(col_type, dv_bytes, participants, count), ...]`` with DV
    in the cost model's convention (full tensor for All-Reduce, gathered
    result for All-Gather).  Stats and logits are f32 on the wire:
    ``_local_logits`` upcasts before the gather, so the gather arm is
    charged at 4 B/elem regardless of the input dtype.

    distSM: three (rows,) f32 stat All-Reduces over the model axis — the
    pmax of the running max, the psum of the exp-sums, and the psum of
    the label logits.  SM/gather: one All-Gather of the (rows, cols/P)
    f32 logit shards.  Both arms add two scalar loss-normalization
    All-Reduces over the data axis when it exists.
    """
    calls = []
    if participants > 1:
        if strategy == "dist":
            calls.append(("AllReduce", rows * 4.0, participants, 3))
        else:
            calls.append(("AllGather", rows * cols * 4.0, participants, 1))
    if dp_participants > 1:
        calls.append(("AllReduce", 4.0, dp_participants, 2))
    return calls


@functools.lru_cache(maxsize=1024)
def plan_softmax_strategy(rows: int, cols: int, participants: int,
                          dtype_bytes: int = 2) -> str:
    """COMET Eq. 3/4 comparison of the two softmax collective mappings.

    rows=M (tokens), cols=N (sharded softmax dim, e.g. padded vocab),
    participants=#shards on the reduction axis.  Costs exactly the
    collectives :func:`softmax_collective_schedule` declares (the data-
    axis scalar psums are common to both arms and cancel).  dtype_bytes
    is kept for call compatibility; the wire dtype is f32 either way
    (see the schedule's docstring).
    """
    if participants <= 1:
        return "dist"
    arch = tpu_v5e()
    noc = arch.cluster_noc

    def lat(schedule) -> float:
        total = 0.0
        for col_type, dv, P, count in schedule:
            cc = collective_cost(col_type, dv, P, noc)
            total += count * (cc.volume_bytes / noc.channel_bandwidth
                              + noc_latency(cc, noc))
        return total

    dist = lat(softmax_collective_schedule("dist", rows, cols, participants))
    gather = lat(softmax_collective_schedule("gather", rows, cols,
                                             participants))
    return "dist" if dist <= gather else "gather"


def sharded_softmax_xent(h: jax.Array, unembed: jax.Array,
                         labels: jax.Array, mesh: Mesh, *,
                         real_vocab: int,
                         strategy: str = "auto") -> jax.Array:
    """Cross-entropy over vocab-sharded logits with explicit collectives.

    h: (B, S, D) sharded over dp; unembed: (D, Vp) sharded over 'model';
    labels: (B, S).  Returns the scalar mean NLL.  'dist' computes the
    global max/logsumexp via All-Reduces of per-shard statistics (the
    paper's distSM); 'gather' All-Gathers the logit shards and computes
    locally (the paper's SM).  'auto' asks the COMET planner.
    """
    B, S, D = h.shape
    Vp = unembed.shape[1]
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    mdl = "model"
    P_model = mesh.shape[mdl]
    if strategy == "auto":
        local_rows = (B * S) // max(1, int(np.prod([mesh.shape[a] for a in dp])) if dp else 1)
        strategy = plan_softmax_strategy(local_rows, Vp, P_model)

    v_local = Vp // P_model

    def _local_logits(h_l, w_l):
        return (h_l.reshape(-1, D) @ w_l).astype(F32)        # (T_l, V_l)

    def _mask_pad(lg, v0):
        idx = v0 + jnp.arange(lg.shape[-1])
        return jnp.where(idx[None, :] >= real_vocab, -1e30, lg)

    def dist_fn(h_l, w_l, y_l):
        lg = _local_logits(h_l, w_l)
        v0 = jax.lax.axis_index(mdl) * v_local
        lg = _mask_pad(lg, v0)
        # stability max is gradient-free (pmax has no AD rule; the exact
        # gradient flows through the logsumexp below regardless of m)
        m = jax.lax.stop_gradient(
            jax.lax.pmax(jax.lax.stop_gradient(lg.max(-1)), mdl))  # CO_1^0
        e = jnp.exp(lg - m[:, None])
        s = jax.lax.psum(e.sum(-1), mdl)                     # CO_1^1: AR(add)
        y = y_l.reshape(-1)
        in_shard = (y >= v0) & (y < v0 + v_local)
        safe = jnp.clip(y - v0, 0, v_local - 1)
        ll_local = jnp.where(in_shard,
                             jnp.take_along_axis(lg, safe[:, None], 1)[:, 0],
                             0.0)
        ll = jax.lax.psum(ll_local, mdl)
        nll = (jnp.log(s) + m - ll).sum()
        total = jax.lax.psum(jnp.float32(y.shape[0]), dp) if dp else y.shape[0]
        return jax.lax.psum(nll, dp) / total if dp else nll / total

    def gather_fn(h_l, w_l, y_l):
        lg = _local_logits(h_l, w_l)
        lg_full = jax.lax.all_gather(lg, mdl, axis=1, tiled=True)  # CO: AG
        lg_full = _mask_pad(lg_full, 0)
        m = lg_full.max(-1)
        s = jnp.exp(lg_full - m[:, None]).sum(-1)
        y = y_l.reshape(-1)
        ll = jnp.take_along_axis(lg_full, y[:, None], 1)[:, 0]
        nll = (jnp.log(s) + m - ll).sum()
        total = jax.lax.psum(jnp.float32(y.shape[0]), dp) if dp else y.shape[0]
        return jax.lax.psum(nll, dp) / total if dp else nll / total

    fn = dist_fn if strategy == "dist" else gather_fn
    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(dp_spec, None, None), P(None, mdl), P(dp_spec, None)),
        out_specs=P(),
        check_vma=False,
    )(h, unembed, labels)


# ---------------------------------------------------------------------------
# Declared train-step collective schedule (PR 8 tentpole).
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DeclaredCollective:
    """One declared collective of the train step.

    ``dv_bytes`` is the per-occurrence logical data volume in the cost
    model's convention (full tensor for All-Reduce, gathered result for
    All-Gather, full input for Reduce-Scatter) — the same convention
    ``repro.analysis.jaxpr`` records, so declared and traced entries
    compare directly.  ``origin`` partitions the schedule into the two
    audit regimes:

    * ``"explicit"`` — emitted by our shard_map bodies (softmax-xent, MoE
      combine) and their AD transposes.  These appear as collective
      primitives in the traced jaxpr and the contract checker asserts
      exact (type, participants, count, DV) equality.
    * ``"gspmd"`` — left to XLA's sharding propagation (data-axis grad
      all-reduces, tensor-parallel activation reductions in attention and
      the dense FFN).  Invisible in the jaxpr by construction; they are
      priced by the cost model and reconciled against the compiled HLO
      (``repro.analysis.reconcile``), not jaxpr-audited.
    """

    label: str
    col_type: str
    dv_bytes: float
    participants: int
    count: float
    origin: str = "explicit"

    def to_dict(self) -> dict:
        return {"label": self.label, "type": self.col_type,
                "dv_bytes": self.dv_bytes, "participants": self.participants,
                "count": self.count, "origin": self.origin}


def _dp_axes_size(mesh: Mesh):
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n = 1
    for a in dp:
        n *= int(mesh.shape[a])
    return dp, n


def train_collective_schedule(cfg, mesh: Mesh, batch: int, seq: int, *,
                              microbatches: int = 1, params=None,
                              planner_loss: bool = True):
    """The DECLARED per-layer collective schedule of one planner-loss
    training step — the single source of truth that the cost model prices
    (:func:`price_collective_schedule`) and the static contract checker
    (``repro.analysis.contracts`` train arm) audits against the traced
    jaxpr and, through ``repro.analysis.reconcile``, against the compiled
    HLO.  If ``make_train_step``'s implementation gains or loses a
    collective, this declaration must change with it or the audit fails.

    The explicit entries encode the empirically pinned shard_map AD rules
    (regression-tested in ``tests/test_static_analysis.py``):

    * every differentiable forward ``psum`` appears twice in the traced
      grad jaxpr — the forward op plus its transpose, which is again a
      psum of the same shape (``pmax`` under ``stop_gradient`` has no
      transpose);
    * an ``all_gather`` transposes to one ``reduce_scatter`` of the full
      gathered cotangent;
    * every shard_map *input* that is replicated over a mesh-axis set A
      (its in_spec leaves A unmentioned) and lies on the differentiation
      path contributes one cotangent ``psum`` over A, sized as the local
      operand (sharded inputs instead get a trivial ``psum(axes=())``
      which the audit ignores as participants == 1);
    * ``jax.checkpoint``/remat does NOT change traced collective counts;
      ``lax.scan`` multiplies its body counts by the trip count.

    Returns a list of :class:`DeclaredCollective`.  ``params`` is the
    (abstract or real) parameter tree used to size the data-axis gradient
    all-reduces; when None it is built from ``cfg`` via
    ``Model.abstract_params()``.
    """
    if params is None:
        from repro.models.model import Model
        params = Model(cfg).abstract_params()

    dp, P_dp = _dp_axes_size(mesh)
    P_m = int(mesh.shape["model"]) if "model" in mesh.axis_names else 1
    dtype_b = np.dtype(cfg.dtype).itemsize
    D = cfg.d_model
    Vp = cfg.padded_vocab
    v_local = Vp // max(P_m, 1)
    m = int(microbatches)
    b = batch // m                       # per-microbatch global batch
    B_local = b // max(P_dp, 1)
    rows = B_local * seq                 # local token rows per microbatch
    sched = []

    # ---- softmax-xent (explicit; composes softmax_collective_schedule's
    #      forward declaration with its AD transposes) -------------------
    if planner_loss and not cfg.tie_embeddings and not cfg.is_encdec:
        strategy = cfg.softmax_strategy
        if strategy in ("auto", "gspmd"):
            strategy = plan_softmax_strategy(rows, Vp, P_m)
        if P_m > 1:
            if strategy == "dist":
                # fwd: pmax + 2 psums (softmax_collective_schedule);
                # bwd: the 2 psums transpose, pmax is stop_gradient'd.
                sched.append(DeclaredCollective(
                    "xent/stats", "AllReduce", rows * 4.0, P_m, 5 * m))
            else:
                sched.append(DeclaredCollective(
                    "xent/logit-gather", "AllGather",
                    rows * Vp * 4.0, P_m, 1 * m))
                sched.append(DeclaredCollective(
                    "xent/logit-gather-grad", "ReduceScatter",
                    rows * Vp * 4.0, P_m, 1 * m))
            # h enters the shard_map replicated over 'model' -> one
            # cotangent psum of the local (B_local, S, D) activation.
            sched.append(DeclaredCollective(
                "xent/hidden-cotangent", "AllReduce",
                B_local * seq * D * dtype_b, P_m, 1 * m))
        if P_dp > 1:
            # fwd: token-count + nll psums; bwd: nll transpose (the token
            # count is constant under AD, so no fourth op).
            sched.append(DeclaredCollective(
                "xent/loss-norm", "AllReduce", 4.0, P_dp, 3 * m))
            # unembed enters replicated over dp -> cotangent psum of the
            # local (D, Vp/P_m) shard.
            sched.append(DeclaredCollective(
                "xent/unembed-grad", "AllReduce",
                D * v_local * dtype_b, P_dp, 1 * m))

    # ---- MoE combine + expert/router grads (explicit) ------------------
    n_moe = (cfg.n_layers - cfg.first_dense_layers) if cfg.is_moe else 0
    if n_moe and P_m > 1:
        E = cfg.n_experts
        e_local = E // P_m
        f = cfg.moe_d_ff
        t_local = (b * seq) // max(P_dp, 1)
        # combine psum (fwd) + its transpose + the x cotangent psum
        # (x enters replicated over 'model'): the checked realization of
        # the "no token all-to-all" claim in models/moe.py.
        sched.append(DeclaredCollective(
            "moe/combine", "AllReduce",
            t_local * D * dtype_b, P_m, 3 * n_moe * m))
        if P_dp > 1:
            # wi/wg/wo enter sharded over 'model', replicated over dp ->
            # one cotangent psum each of the local (e_local, d, f) shard.
            sched.append(DeclaredCollective(
                "moe/expert-grad", "AllReduce",
                e_local * D * f * dtype_b, P_dp, 3 * n_moe * m))
        if P_dp * P_m > 1:
            # router enters fully replicated -> cotangent psum over ALL
            # mesh axes (f32 by spec).
            sched.append(DeclaredCollective(
                "moe/router-grad", "AllReduce",
                D * E * 4.0, P_dp * P_m, 1 * n_moe * m))
            if cfg.router_type == "sigmoid":
                sched.append(DeclaredCollective(
                    "moe/router-bias-grad", "AllReduce",
                    E * 4.0, P_dp * P_m, 1 * n_moe * m))

    # ---- GSPMD-owned collectives (priced + HLO-reconciled only) --------
    # Data-axis gradient all-reduces, sized from the real param tree.
    # Leaves whose gradients are already reduced by an explicit cotangent
    # psum above (unembed under the planner loss, the MoE expert stack)
    # are excluded — declaring them twice would double-charge.
    if P_dp > 1:
        explicit = []
        if planner_loss and not cfg.tie_embeddings and not cfg.is_encdec:
            explicit.append("unembed")
        if n_moe and P_m > 1:
            explicit.append("moe")
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        rs = bool(cfg.fsdp)
        for path, leaf in flat:
            keys = [str(getattr(k, "key", k)) for k in path]
            if any(k in explicit for k in keys):
                continue
            nbytes = float(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            sched.append(DeclaredCollective(
                "grads/" + ".".join(keys),
                "ReduceScatter" if rs else "AllReduce",
                nbytes, P_dp, 1, origin="gspmd"))
            if rs:  # ZeRO-3: fwd + bwd param regathers
                sched.append(DeclaredCollective(
                    "params/" + ".".join(keys), "AllGather",
                    nbytes, P_dp, 2, origin="gspmd"))
    # Tensor-parallel activation reductions: one AR after the attention
    # out-projection and one after the dense-FFN down-projection, forward
    # and backward (Megatron f/g) — the MoE FFN's reduction is the
    # explicit combine psum above.
    if cfg.tensor_parallel and P_m > 1:
        act = B_local * seq * D * dtype_b
        sched.append(DeclaredCollective(
            "tp/attn-out", "AllReduce", act, P_m,
            2 * cfg.n_layers * m, origin="gspmd"))
        n_dense_ffn = cfg.n_layers - n_moe
        if n_dense_ffn:
            sched.append(DeclaredCollective(
                "tp/ffn-out", "AllReduce", act, P_m,
                2 * n_dense_ffn * m, origin="gspmd"))
    return sched


def price_collective_schedule(schedule, noc=None) -> float:
    """COMET Eq. 3/4 latency of a declared schedule (seconds)."""
    if noc is None:
        noc = tpu_v5e().cluster_noc
    total = 0.0
    for d in schedule:
        if d.participants <= 1:
            continue
        cc = collective_cost(d.col_type, d.dv_bytes, d.participants, noc)
        total += d.count * (cc.volume_bytes / noc.channel_bandwidth
                            + noc_latency(cc, noc))
    return total
