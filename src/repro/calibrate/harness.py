"""PARAM/nccl-tests-style collective microbenchmark harness.

The COMET collective model (Eqs. 3–4, ``core/collectives.py`` +
``core/cost.py``) is purely analytical: every per-NoC volume/hop/step
factor comes from the paper's HiSIM/Orion constants.  This harness is
the *measured* side of the calibration loop — it times real ``jax.lax``
collectives (the four COMET collective types that appear in compound-op
dataflows) over a log-spaced message-size sweep, nccl-tests style:

    for each collective type:
        for each log-spaced data volume DV:
            warmup, then best-of-``iters`` timed executions

The backend is pluggable.  :func:`run_sweep` drives any
``measure_fn(col_type, dv_bytes, participants) -> seconds`` — one timed
execution per call — so tests and benchmarks swap the real mesh for
:func:`synthetic_measure_fn` (an analytic generator from known
``NoCParams``, optionally jittered) and the whole fit path is
deterministic in CI.  :func:`jax_measure_fn` is the real backend: it
shards a buffer over every available device with ``shard_map`` and times
``psum`` / ``all_gather`` / ``psum_scatter`` / ``all_to_all``.  Timing
uses an injectable ``clock=`` (the ``planstore.py`` ``now=`` pattern),
so even the real backend can be driven with a fake clock.

Data-volume convention matches ``core/collectives.py``: ``dv_bytes`` is
the *logical tensor size* the collective operates on (the full tensor
for All-Reduce / Reduce-Scatter / All-to-All, the gathered result for
All-Gather), so measured points feed the fitter and
``collective_latency_terms`` without unit conversion.

Fault behavior (pinned by ``tests/test_calibrate.py``): a ``measure_fn``
that raises, returns non-finite/non-positive values, or produces wildly
non-monotone timings mid-sweep degrades the sweep to the surviving
points — one ``RuntimeWarning`` per cause (planstore-style), never a
crash, and the dropped points are tallied in ``SweepResult.dropped`` so
persistence can refuse to write a fit built from nothing.
"""
from __future__ import annotations

import threading
import time
import warnings
from dataclasses import dataclass, field, replace
from typing import Callable, Dict, List, Optional, Tuple

from repro.core.collectives import collective_seconds
from repro.core.hardware import NoCParams

__all__ = [
    "CALIBRATED_TYPES",
    "MeasuredPoint",
    "SweepConfig",
    "SweepResult",
    "run_sweep",
    "log_sizes",
    "jax_measure_fn",
    "synthetic_measure_fn",
]

#: the four COMET collective types a real backend can execute directly
#: (Gather/Broadcast have no first-class jax.lax collective; their
#: dissemination-tree factors share the AllGather exchange schedule).
CALIBRATED_TYPES = ("AllReduce", "AllGather", "ReduceScatter", "AllToAll")

#: a point whose timing falls below this fraction of the running maximum
#: of *smaller* messages of the same type is non-monotone noise (a
#: 4 MiB collective cannot be 4x faster than a 4 KiB one) and is dropped
NONMONOTONE_FRACTION = 0.25


@dataclass(frozen=True)
class MeasuredPoint:
    """One timed collective execution (best-of-iters)."""

    col_type: str
    data_volume_bytes: int      # logical tensor size (COMET DV convention)
    participants: int
    seconds: float

    def to_json(self) -> Dict:
        return {"col_type": self.col_type,
                "data_volume_bytes": self.data_volume_bytes,
                "participants": self.participants,
                "seconds": self.seconds}

    @classmethod
    def from_json(cls, d: Dict) -> "MeasuredPoint":
        return cls(str(d["col_type"]), int(d["data_volume_bytes"]),
                   int(d["participants"]), float(d["seconds"]))


@dataclass(frozen=True)
class SweepConfig:
    """Sweep shape: which collectives, which sizes, how many repeats."""

    col_types: Tuple[str, ...] = CALIBRATED_TYPES
    min_bytes: int = 1 << 12            # 4 KiB
    max_bytes: int = 1 << 24            # 16 MiB
    n_sizes: int = 8                    # log-spaced points per type
    warmup: int = 1                     # untimed executions per point
    iters: int = 5                      # timed executions; best is kept


@dataclass
class SweepResult:
    """Surviving measurements plus the fault tally of one sweep."""

    points: List[MeasuredPoint] = field(default_factory=list)
    dropped: Dict[str, int] = field(default_factory=dict)
    participants: Tuple[int, ...] = ()
    config: Optional[SweepConfig] = None

    @property
    def n_dropped(self) -> int:
        return sum(self.dropped.values())


# ------------------------------------------------------------- warn-once

_WARNED: set = set()
_WARNED_LOCK = threading.Lock()


def _warn_once(cause_key: Tuple, msg: str) -> None:
    """One warning per cause for the life of the process (planstore
    style): a flaky backend degrades once, not once per point."""
    with _WARNED_LOCK:
        if cause_key in _WARNED:
            return
        _WARNED.add(cause_key)
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _reset_warned() -> None:
    """Test hook: forget which sweep degradations have been warned."""
    with _WARNED_LOCK:
        _WARNED.clear()


# ------------------------------------------------------------ size sweep


def log_sizes(min_bytes: int, max_bytes: int, n: int, *,
              multiple: int = 4) -> List[int]:
    """``n`` log-spaced byte sizes in [min_bytes, max_bytes], each
    rounded to a positive multiple of ``multiple`` (element size x
    participants, so per-device shards divide evenly), deduplicated and
    ascending."""
    if n <= 0:
        return []
    if n == 1:
        targets = [float(max_bytes)]
    else:
        ratio = (max_bytes / min_bytes) ** (1.0 / (n - 1))
        targets = [min_bytes * ratio ** i for i in range(n)]
    out: List[int] = []
    for t in targets:
        size = max(1, round(t / multiple)) * multiple
        if not out or size > out[-1]:
            out.append(size)
    return out


def run_sweep(
    measure_fn: Callable[[str, int, int], float],
    participants,
    *,
    config: Optional[SweepConfig] = None,
) -> SweepResult:
    """Drive ``measure_fn`` over the (type x size x participants) grid.

    ``participants`` is one int (the real backend: every device) or a
    sequence (synthetic backends can sweep several group sizes, which
    sharpens the fit's separation of the per-hop and per-byte terms).

    Each grid point is measured ``config.warmup + config.iters`` times;
    the best (minimum) timed iteration survives — the nccl-tests
    convention, which rejects one-sided scheduler noise.  Faults degrade
    per the module docstring; the returned ``SweepResult.dropped`` maps
    cause (``error`` / ``not-finite`` / ``non-monotone``) to the number
    of grid points lost to it.
    """
    cfg = config or SweepConfig()
    ps: Tuple[int, ...] = (tuple(participants)
                           if isinstance(participants, (list, tuple))
                           else (int(participants),))
    result = SweepResult(participants=ps, config=cfg)

    def drop(cause: str, detail: str) -> None:
        result.dropped[cause] = result.dropped.get(cause, 0) + 1
        _warn_once(("sweep", cause),
                   f"calibration sweep: dropping point(s) [{cause}] — "
                   f"{detail}; continuing with a partial sweep")

    for col_type in cfg.col_types:
        for P in ps:
            # shards must divide: DV multiple of elem_size * P * P (the
            # all-to-all split needs P^2 alignment of the flat buffer)
            sizes = log_sizes(cfg.min_bytes, cfg.max_bytes, cfg.n_sizes,
                              multiple=4 * max(1, P) * max(1, P))
            running_max = 0.0
            for dv in sizes:
                best = None
                try:
                    for _ in range(cfg.warmup):
                        measure_fn(col_type, dv, P)
                    for _ in range(cfg.iters):
                        t = float(measure_fn(col_type, dv, P))
                        if best is None or t < best:
                            best = t
                except Exception as e:  # noqa: BLE001 — degrade, never crash
                    drop("error", f"{col_type}@{dv}B/P={P} raised {e!r}")
                    continue
                if best is None or not (best > 0.0) or best != best \
                        or best == float("inf"):
                    drop("not-finite",
                         f"{col_type}@{dv}B/P={P} returned {best!r}")
                    continue
                if running_max > 0.0 and best < NONMONOTONE_FRACTION * running_max:
                    drop("non-monotone",
                         f"{col_type}@{dv}B/P={P}: {best:.3e}s after "
                         f"{running_max:.3e}s at a smaller size")
                    continue
                running_max = max(running_max, best)
                result.points.append(
                    MeasuredPoint(col_type, dv, P, best))
    return result


# ------------------------------------------------------------- backends


def jax_measure_fn(mesh=None, *, clock: Callable[[], float] = time.perf_counter,
                   dtype=None) -> Callable[[str, int, int], float]:
    """Real backend: time one execution of the requested collective over
    a 1-D device mesh with ``shard_map``.

    ``mesh`` defaults to all of ``jax.devices()`` on one axis — under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (which
    ``python -m repro.calibrate`` sets before importing jax) that is the
    forced 8-virtual-device CPU backend.  ``participants`` must equal
    the mesh size: a real collective cannot run over a subgroup the mesh
    does not express.  Jitted executables are cached per (type, shape),
    so the warmup iteration absorbs compilation and the timed iterations
    measure execution only.  ``clock`` is injectable (planstore ``now=``
    pattern) for deterministic tests.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), ("cal",))
    axis = mesh.axis_names[0]
    n_devices = int(np.prod(mesh.devices.shape))
    dtype = dtype or jnp.float32
    elem = jnp.dtype(dtype).itemsize

    # COMET DV (logical tensor bytes) -> global flat element count.  The
    # global array is sharded over the axis; AllGather's DV is the
    # *gathered* result, everyone else's the full input tensor.
    def bodies():
        return {
            "AllReduce": (lambda x: jax.lax.psum(x, axis), P(axis), P()),
            "AllGather": (lambda x: jax.lax.all_gather(x, axis, tiled=True),
                          P(axis), P()),
            "ReduceScatter": (lambda x: jax.lax.psum_scatter(
                x, axis, tiled=True), P(axis), P(axis)),
            "AllToAll": (lambda x: jax.lax.all_to_all(
                x, axis, 0, 0, tiled=True), P(axis), P(axis)),
        }

    compiled: Dict[Tuple[str, int], Tuple[Callable, object]] = {}

    def measure(col_type: str, dv_bytes: int, participants: int) -> float:
        if participants != n_devices:
            raise ValueError(
                f"jax backend measures over all {n_devices} mesh devices; "
                f"got participants={participants}")
        if col_type not in CALIBRATED_TYPES:
            raise ValueError(f"jax backend cannot execute {col_type!r}")
        elems = max(1, dv_bytes // elem)
        # every per-device shard must hold a whole number of elements,
        # and AllReduce shards the *replicated-sum* input per device
        elems = max(1, elems // (n_devices * n_devices)) \
            * n_devices * n_devices
        if col_type == "AllReduce":
            # DV is the full tensor each device contributes: global
            # input is P stacked shards of DV bytes
            global_elems = elems * n_devices
        elif col_type == "AllGather":
            global_elems = elems          # gathered result == DV
        else:
            # ReduceScatter / AllToAll: each device holds DV bytes
            global_elems = elems * n_devices
        key = (col_type, global_elems)
        if key not in compiled:
            body, ins, outs = bodies()[col_type]
            compiled[key] = (
                jax.jit(jax.shard_map(body, mesh=mesh, in_specs=ins,
                                      out_specs=outs, check_vma=False)),
                NamedSharding(mesh, ins))
        fn, in_sharding = compiled[key]
        # placed on the mesh before the clock starts, so the timed call
        # is the collective alone and not also the scatter of its input
        x = jax.device_put(jnp.zeros((global_elems,), dtype), in_sharding)
        jax.block_until_ready(x)
        t0 = clock()
        jax.block_until_ready(fn(x))
        return clock() - t0

    return measure


def synthetic_measure_fn(params: NoCParams, *, jitter: float = 0.0,
                         seed: int = 0) -> Callable[[str, int, int], float]:
    """Analytic backend: generate timings from known ``NoCParams``
    through the exact Eq. 1/3/4 prediction (``collective_seconds``) the
    fitter inverts, optionally with bounded multiplicative jitter
    (uniform in ``[1-jitter, 1+jitter]``, seeded, deterministic).

    This is the ground-truth generator of the recovery tests: a
    noise-free sweep must let the fitter recover ``params`` to float
    precision, and a jittered one must stay within the documented
    tolerance.
    """
    import random

    rng = random.Random(seed)

    def measure(col_type: str, dv_bytes: int, participants: int) -> float:
        t = collective_seconds(col_type, float(dv_bytes), int(participants),
                               params)
        if jitter > 0.0:
            t *= 1.0 + rng.uniform(-jitter, jitter)
        return t

    return measure


def _replace_mesh(params: NoCParams, mesh: Tuple[int, int]) -> NoCParams:
    """Reference NoC re-meshed to the measured topology (hop distances
    must be computed on the mesh the sweep actually ran on)."""
    return replace(params, mesh=mesh)
