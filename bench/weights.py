"""Weights drawn by the benchmark from ``--seed``, on the device, in one
jitted call, in the dtype each leaf is served in.

Only the layout (the tree of leaf names, shapes and dtypes) comes from
the program; the values follow the rules below, so the reference and the
program are handed the same numbers and neither made them.

- ``embedding``: N(0, 1/d), d its width, so that a tied output matrix
  gives logits of unit scale, as the untied one does.
- norm scales (leaves named ``scale`` or ``norm_scale``): 1 + N(0, 0.1),
  so a norm whose scale is dropped shows.
- ``A_log``: log U[1, 16]; ``dt_bias``: softplus⁻¹ of a dt drawn
  log-uniform on [1e-3, 1e-1]; ``D``: 1 — the Mamba-2 initialisation.
- ``conv_b``: N(0, 0.1).
- every other leaf: N(0, 1) / sqrt(fan_in), fan_in its second-to-last
  dimension (the input width of a (layers, in, out) matrix).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def prng_key(seed: int) -> jax.Array:
    """A threefry key from any non-negative seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.wrap_key_data(
        jnp.array([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32))


def _leaf(name: str, shape, dtype, key) -> jax.Array:
    if name == "embedding":
        x = jax.random.normal(key, shape, F32) / math.sqrt(shape[-1])
    elif name in ("scale", "norm_scale"):
        x = 1.0 + 0.1 * jax.random.normal(key, shape, F32)
    elif name == "A_log":
        x = jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    elif name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, F32, math.log(1e-3),
                                        math.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif name == "D":
        x = jnp.ones(shape, F32)
    elif name == "conv_b":
        x = 0.1 * jax.random.normal(key, shape, F32)
    else:
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        x = jax.random.normal(key, shape, F32) / math.sqrt(fan_in)
    return x.astype(dtype)


def draw(abstract, seed: int):
    """Arrays for the tree of ``jax.ShapeDtypeStruct`` ``abstract``."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract)

    def make(key):
        keys = jax.random.split(key, len(paths))
        return jax.tree.unflatten(treedef, [
            _leaf(path[-1].key, leaf.shape, leaf.dtype, k)
            for (path, leaf), k in zip(paths, keys)])

    return jax.jit(make)(prng_key(seed))
