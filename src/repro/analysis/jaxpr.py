"""Structural FLOP *and collective* counting from the jaxpr (scan-aware).

XLA's ``cost_analysis()`` does not multiply while-loop bodies by their trip
counts, so scanned-layer models under-report FLOPs by ~n_layers (observed
useful_flop_ratio >> 1, see EXPERIMENTS §Roofline).  The jaxpr still knows
every ``scan`` length statically, so we count matmul FLOPs exactly by
walking it recursively with a trip-count multiplier — and, for the static
contract checker (:mod:`repro.analysis.contracts`), we count the explicit
collective primitives (``psum``/``pmax``/``pmin``/``all_gather``/
``psum_scatter``/``all_to_all``/``ppermute``) the same scan-aware way,
recording per-(type, participants) occurrence counts and data volumes.

Counted FLOPs: ``dot_general`` (2·M·N·K·batch), ``ragged_dot_general``
(2 · lhs elements · the rhs's free size: every lhs row meets one group,
over the rows it is given, whether or not a group claims them) and
``conv_general_dilated`` (2 · output points · kernel spatial ·
in-channels-per-group).  Elementwise/
reduce FLOPs are a few percent of LM totals and are not counted
(documented).  Returned FLOPs are GLOBAL (whole-program,
pre-partitioning): divide by the device count for per-device numbers.

Trip-count multipliers
----------------------
``scan``       body × ``length`` — nested scans multiply (outer × inner),
               pinned by a regression test.
``while``      body × the static trip count when the loop is the bounded
               counter pattern (``i < literal`` cond, literal step/init);
               otherwise body × 1 with an explicit ``while-unbounded``
               finding in :attr:`TraceCounts.findings` (never silence).
``shard_map``  FLOPs × mesh device count (body runs on every device over
               1/N of the data; global FLOPs = body × N).  Collectives are
               **not** multiplied: N devices execute one *logical*
               collective (SPMD), and its cost is already a function of the
               participant count.
``pallas_call``  body × grid product — one kernel-body trace per grid cell.
``cond``       the maximum-FLOP branch; collectives take the per-type
               maximum across branches (conservative upper bound).
``pjit``/``remat``/``custom_vjp`` and other call-like primitives recurse
with unchanged multipliers.

Collective volume conventions match ``core/collectives.py``: ``dv_bytes``
is the *logical* tensor size the collective operates on (full tensor for
All-Reduce, gathered result for All-Gather, full input for
Reduce-Scatter), so ``collective_cost(type, dv_bytes, participants, noc)``
charges the traced op exactly as the cost model charges the planned one.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Tuple

import jax
import numpy as np

__all__ = ["count_flops", "structural_flops", "CollectiveRecord",
           "TraceCounts", "trace_counts", "count_jaxpr"]


# COMET collective type each jax collective primitive realizes.  pmax/pmin
# are max/min-AllReduces: same exchange schedule and wire volume as psum.
_PRIM_TO_TYPE = {
    "psum": "AllReduce",
    "pmax": "AllReduce",
    "pmin": "AllReduce",
    "all_gather": "AllGather",
    # jax.lax.psum_scatter binds a primitive named ``reduce_scatter``; the
    # ``psum_scatter`` alias is kept for jax versions that use the API name.
    # (Before PR 8 only the alias was listed, so every traced Reduce-Scatter
    # — e.g. the transpose of the gather-arm softmax All-Gather — was
    # silently dropped from the contract audit.)
    "reduce_scatter": "ReduceScatter",
    "psum_scatter": "ReduceScatter",
    "all_to_all": "AllToAll",
    "ppermute": "Permute",
    "pshuffle": "Permute",
}


@dataclass
class CollectiveRecord:
    """Aggregated trace of one (collective type, participant count) pair."""

    col_type: str          # COMET type: AllReduce/AllGather/ReduceScatter/...
    participants: int
    count: float = 0.0     # occurrences × trip-count multipliers
    dv_bytes: float = 0.0  # Σ logical data volume (cost-model DV convention)
    shard_bytes: float = 0.0  # Σ per-shard operand bytes (as traced)

    def merge(self, other: "CollectiveRecord") -> None:
        self.count += other.count
        self.dv_bytes += other.dv_bytes
        self.shard_bytes += other.shard_bytes

    def to_dict(self) -> Dict:
        return {"type": self.col_type, "participants": self.participants,
                "count": self.count, "dv_bytes": self.dv_bytes,
                "shard_bytes": self.shard_bytes}


@dataclass
class TraceCounts:
    """FLOPs + collectives counted from one jaxpr walk."""

    flops: float = 0.0
    collectives: Dict[Tuple[str, int], CollectiveRecord] = field(
        default_factory=dict)
    # Non-fatal analysis findings, e.g. a ``while`` whose trip count could
    # not be statically determined (body counted once — a lower bound).
    # Each finding is {"kind": ..., "detail": ...}.
    findings: list = field(default_factory=list)

    def add_finding(self, kind: str, detail: str) -> None:
        self.findings.append({"kind": kind, "detail": detail})

    def add_collective(self, col_type: str, participants: int, count: float,
                       dv_bytes: float, shard_bytes: float) -> None:
        key = (col_type, int(participants))
        rec = self.collectives.get(key)
        if rec is None:
            rec = self.collectives[key] = CollectiveRecord(
                col_type, int(participants))
        rec.count += count
        rec.dv_bytes += dv_bytes
        rec.shard_bytes += shard_bytes

    def merge(self, other: "TraceCounts") -> None:
        self.flops += other.flops
        self.findings.extend(other.findings)
        for key, rec in other.collectives.items():
            mine = self.collectives.get(key)
            if mine is None:
                self.collectives[key] = CollectiveRecord(
                    rec.col_type, rec.participants, rec.count,
                    rec.dv_bytes, rec.shard_bytes)
            else:
                mine.merge(rec)

    def merge_max(self, other: "TraceCounts") -> None:
        """Per-type conservative merge for ``cond`` branches: keep the
        heavier branch's record for each (type, participants) key."""
        self.flops = max(self.flops, other.flops)
        self.findings.extend(other.findings)
        for key, rec in other.collectives.items():
            mine = self.collectives.get(key)
            if mine is None or rec.dv_bytes > mine.dv_bytes:
                self.collectives[key] = CollectiveRecord(
                    rec.col_type, rec.participants, rec.count,
                    rec.dv_bytes, rec.shard_bytes)

    def total_collective_dv(self) -> float:
        return sum(r.dv_bytes for r in self.collectives.values())

    def by_type(self) -> Dict[str, CollectiveRecord]:
        """Per-type totals (participants field holds the max seen)."""
        out: Dict[str, CollectiveRecord] = {}
        for rec in self.collectives.values():
            t = out.get(rec.col_type)
            if t is None:
                out[rec.col_type] = CollectiveRecord(
                    rec.col_type, rec.participants, rec.count,
                    rec.dv_bytes, rec.shard_bytes)
            else:
                t.participants = max(t.participants, rec.participants)
                t.merge(rec)
        return out

    def to_dict(self) -> Dict:
        return {"flops": self.flops,
                "collectives": [r.to_dict() for _, r in
                                sorted(self.collectives.items())],
                "findings": list(self.findings)}


def _dot_flops(eqn) -> float:
    a, b = eqn.invars[0].aval, eqn.invars[1].aval
    dims = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dims
    batch = 1
    for d in lb:
        batch *= a.shape[d]
    contract = 1
    for d in lc:
        contract *= a.shape[d]
    m = 1
    for i, s in enumerate(a.shape):
        if i not in lc and i not in lb:
            m *= s
    n = 1
    for i, s in enumerate(b.shape):
        if i not in rc and i not in rb:
            n *= s
    return 2.0 * batch * m * n * contract


def _ragged_dot_flops(eqn) -> float:
    a, b = eqn.invars[0].aval, eqn.invars[1].aval
    rdims = eqn.params["ragged_dot_dimension_numbers"]
    (_, rc), (_, rb) = rdims.dot_dimension_numbers
    bound = set(rc) | set(rb) | set(rdims.rhs_group_dimensions)
    n = 1
    for i, s in enumerate(b.shape):
        if i not in bound:
            n *= s
    return 2.0 * a.size * n


def _conv_flops(eqn) -> float:
    """conv_general_dilated as a dot per output point: every output element
    is a (kernel-spatial × in-channels-per-group) MAC reduction."""
    rhs = eqn.invars[1].aval
    out = eqn.outvars[0].aval
    dn = eqn.params["dimension_numbers"]
    rhs_spec = dn.rhs_spec  # (out_feature_dim, in_feature_dim, *spatial)
    in_ch_per_group = rhs.shape[rhs_spec[1]]
    k_spatial = 1
    for d in rhs_spec[2:]:
        k_spatial *= rhs.shape[d]
    out_pts = 1
    for s in out.shape:
        out_pts *= s
    return 2.0 * out_pts * k_spatial * in_ch_per_group


def _aval_bytes(aval) -> float:
    n = 1
    for s in aval.shape:
        n *= s
    return float(n) * np.dtype(aval.dtype).itemsize


def _axis_tuple(v) -> Tuple:
    return v if isinstance(v, (tuple, list)) else (v,)


def _participants(params, axis_env: Dict[str, int],
                  axis_keys=("axes", "axis_name")) -> int:
    """Participant count of a collective eqn: the replica-group length if
    ``axis_index_groups`` is set, else the product of the mapped axis sizes
    (``axis_size`` param when present — all_gather/psum_scatter carry it)."""
    groups = params.get("axis_index_groups")
    if groups:
        return len(groups[0])
    if "axis_size" in params:
        return int(params["axis_size"])
    p = 1
    for key in axis_keys:
        if key in params:
            for ax in _axis_tuple(params[key]):
                p *= int(axis_env.get(ax, 1))
            break
    return p


def _record_collective(eqn, prim: str, mult: float, axis_env: Dict[str, int],
                       out: TraceCounts) -> None:
    col_type = _PRIM_TO_TYPE[prim]
    P = _participants(eqn.params, axis_env)
    shard = sum(_aval_bytes(v.aval) for v in eqn.invars)
    if col_type == "AllGather":
        # DV convention: the gathered result (per-shard operand × P).
        dv = shard * P
    else:
        # AllReduce: per-shard partials span the full logical tensor.
        # ReduceScatter/AllToAll/Permute: DV is the full input.
        dv = shard
    out.add_collective(col_type, P, mult, dv * mult, shard * mult)


def _grid_product(params) -> float:
    gm = params.get("grid_mapping")
    grid = getattr(gm, "grid", None) if gm is not None else params.get("grid")
    if not grid:
        return 1.0
    n = 1.0
    for g in grid:
        try:
            n *= float(g)
        except TypeError:  # symbolic/dynamic grid dim: count once
            pass
    return n


def _literal_value(var):
    """Concrete python value of a jaxpr Literal, else None."""
    val = getattr(var, "val", None)
    if val is None:
        return None
    try:
        return float(np.asarray(val).reshape(()))
    except Exception:
        return None


def _while_trip_count(eqn):
    """Static trip count of a ``while`` eqn, or None if unbounded.

    Recognizes the counter pattern ``lax.while_loop`` lowers bounded loops
    to (and that ``fori_loop`` with traced-but-constant bounds produces):
    the cond jaxpr is a single ``i < bound`` comparison of a carry slot
    against a literal, and the body advances that slot by a literal step.
    The initial counter value must be a literal at the call site.  Anything
    else — data-dependent predicates, non-literal bounds — returns None and
    the caller emits a ``while-unbounded`` finding.
    """
    try:
        params = eqn.params
        cond = params["cond_jaxpr"].jaxpr
        body = params["body_jaxpr"].jaxpr
        cn = int(params.get("cond_nconsts", 0))
        bn = int(params.get("body_nconsts", 0))
        pred = cond.outvars[0]
        pred_eqn = None
        for e in cond.eqns:
            if pred in e.outvars:
                pred_eqn = e
        if pred_eqn is None or pred_eqn.primitive.name != "lt":
            return None
        ivar, bvar = pred_eqn.invars
        bound = _literal_value(bvar)
        carry = list(cond.invars[cn:])
        if bound is None or ivar not in carry:
            return None
        idx = carry.index(ivar)
        # the body must advance carry slot idx by a literal step
        out_i = body.outvars[idx]
        step_eqn = None
        for e in body.eqns:
            if out_i in e.outvars:
                step_eqn = e
        if step_eqn is None or step_eqn.primitive.name != "add":
            return None
        body_carry = list(body.invars[bn:])
        step = None
        for a, b in (step_eqn.invars, reversed(step_eqn.invars)):
            if a is body_carry[idx]:
                step = _literal_value(b)
                break
        if not step or step <= 0:
            return None
        init = _literal_value(eqn.invars[cn + bn + idx])
        if init is None:
            return None
        import math
        return max(0, int(math.ceil((bound - init) / step)))
    except Exception:
        return None


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    try:
        return {str(k): int(v) for k, v in dict(mesh.shape).items()}
    except Exception:
        return {}


def _walk(jaxpr, flops_mult: float, coll_mult: float,
          axis_env: Dict[str, int], out: TraceCounts) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        if prim == "dot_general":
            out.flops += flops_mult * _dot_flops(eqn)
        elif prim == "ragged_dot_general":
            out.flops += flops_mult * _ragged_dot_flops(eqn)
        elif prim == "conv_general_dilated":
            out.flops += flops_mult * _conv_flops(eqn)
        elif prim in _PRIM_TO_TYPE:
            _record_collective(eqn, prim, coll_mult, axis_env, out)
        elif prim == "scan":
            inner = eqn.params["jaxpr"].jaxpr
            length = eqn.params["length"]
            # nested scans multiply: an inner scan walked with mult×L_outer
            # passes mult×L_outer×L_inner down (regression-tested).
            _walk(inner, flops_mult * length, coll_mult * length,
                  axis_env, out)
        elif prim == "while":
            trip = _while_trip_count(eqn)
            if trip is None:
                # data-dependent trip count: body counted once (a lower
                # bound) and flagged so downstream consumers know the
                # totals under-count instead of silently trusting them.
                out.add_finding(
                    "while-unbounded",
                    "while primitive has no static trip count; body "
                    "counted once (flops/collectives are a lower bound)")
                trip = 1
            _walk(eqn.params["body_jaxpr"].jaxpr, flops_mult * trip,
                  coll_mult * trip, axis_env, out)
        elif prim == "shard_map":
            sub = eqn.params.get("jaxpr")
            if sub is not None:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                # shard_map body runs on EVERY device over 1/N of data: the
                # global flop count is body × num_devices (mesh size).  The
                # mesh axes also name the collective axes inside the body.
                mesh = eqn.params.get("mesh")
                sizes = _mesh_axis_sizes(mesh) if mesh is not None else {}
                n = 1
                for s in sizes.values():
                    n *= s
                env = dict(axis_env)
                env.update(sizes)
                _walk(inner, flops_mult * max(n, 1), coll_mult, env, out)
        elif prim == "cond":
            branches = eqn.params.get("branches", ())
            merged = None
            for b in branches:
                sub = TraceCounts()
                _walk(b.jaxpr, flops_mult, coll_mult, axis_env, sub)
                if merged is None:
                    merged = sub
                else:
                    merged.merge_max(sub)
            if merged is not None:
                out.merge(merged)
        elif prim == "pallas_call":
            sub = eqn.params.get("jaxpr")
            if sub is not None:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                # the kernel body executes once per grid cell
                g = _grid_product(eqn.params)
                _walk(inner, flops_mult * g, coll_mult * g, axis_env, out)
        else:
            # generic call-like primitives (pjit, remat2, custom_vjp, ...)
            sub = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                   or eqn.params.get("fun_jaxpr"))
            if sub is not None:
                inner = sub.jaxpr if hasattr(sub, "jaxpr") else sub
                _walk(inner, flops_mult, coll_mult, axis_env, out)


def count_jaxpr(closed_jaxpr) -> TraceCounts:
    """Walk a closed jaxpr, returning FLOPs + per-(type, P) collectives."""
    out = TraceCounts()
    _walk(closed_jaxpr.jaxpr, 1.0, 1.0, {}, out)
    return out


def count_flops(closed_jaxpr) -> float:
    return count_jaxpr(closed_jaxpr).flops


def structural_flops(fn, *abstract_args, **abstract_kwargs) -> float:
    """Global matmul FLOPs of ``fn`` traced on abstract inputs."""
    cj = jax.make_jaxpr(fn)(*abstract_args, **abstract_kwargs)
    return count_flops(cj)


def trace_counts(fn, *abstract_args, **abstract_kwargs) -> TraceCounts:
    """FLOPs + collectives of ``fn`` traced on abstract inputs."""
    cj = jax.make_jaxpr(fn)(*abstract_args, **abstract_kwargs)
    return count_jaxpr(cj)
