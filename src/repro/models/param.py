"""Parameter specs: one source of truth for shapes, dtypes, logical axes
and initializers.  Used to (a) init real params, (b) build abstract
ShapeDtypeStructs for the dry-run, and (c) derive NamedShardings from the
logical-axis rules in parallel/sharding.py.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ParamSpec", "init_tree", "abstract_tree", "axes_tree", "count_params"]


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]       # logical axis names, len == ndim
    init: str = "normal"                  # normal | zeros | ones | small_normal
    scale: float = 1.0                    # stddev multiplier for normal init
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_one(spec: ParamSpec, key: jax.Array) -> jax.Array:
    dt = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    std = spec.scale / math.sqrt(max(1, fan_in))
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(dt)


# One compiled program per leaf: the draw, scale and cast fuse, so a
# bf16 leaf never has f32 copies beside it — op by op, the largest leaf
# of a full-width model briefly holds three, which one 16 GB chip has no
# room for next to the leaves already made.
_init_one_jit = jax.jit(_init_one, static_argnums=0)


def init_tree(specs, key: jax.Array):
    """Initialize a pytree of arrays from a pytree of ParamSpecs."""
    leaves, treedef = jax.tree.flatten(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [_init_one_jit(s, k) for s, k in zip(leaves, keys)])


def abstract_tree(specs):
    """ShapeDtypeStruct pytree (no allocation) from a ParamSpec pytree."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def axes_tree(specs):
    """Logical-axes pytree mirroring the params pytree."""
    return jax.tree.map(lambda s: s.axes, specs,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def count_params(specs) -> int:
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    return int(sum(int(np.prod(s.shape)) for s in leaves))
