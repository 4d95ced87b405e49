"""Production mesh construction.

Single pod: 16×16 = 256 chips, axes (data, model).
Multi-pod: 2×16×16 = 512 chips, axes (pod, data, model) — the 'pod' axis
carries data parallelism across pods (gradient all-reduce crosses the
pod-interconnect; int8 compression applies there).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "POD_SHAPE"]

POD_SHAPE = (16, 16)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: Optional[int] = None) -> jax.sharding.Mesh:
    """Largest (data, model) mesh on the devices actually present (tests,
    examples, smoke runs)."""
    n = len(jax.devices())
    mp = model_parallel or 1
    while n % mp:
        mp -= 1
    return jax.make_mesh((n // mp, mp), ("data", "model"))
