"""Everything the benchmark knows of a model family: one module per
family, named by the ``reference`` key of a configuration file.  A new
family is one new module here; no other file of the benchmark names one.

A family module gives, each from the configuration file's published keys:

- ``hidden(cfg, params, tokens, mm)``: the final, normed hidden state
  (T, d) in float32 of one sequence, from the weight arrays alone, with no
  cache, batching or kernel, and every matrix product through ``mm``;
- ``head(cfg, params)``: the (d, padded vocab) output matrix;
- ``decode_work(cfg, rows, ctx_sum)`` and ``prefill_work(cfg, rows,
  prompt_len)``: the ``work.Work`` a decode step and a prefill call need,
  counted from shapes alone (``bench/work.py`` says what "needed" means);
- ``program_fields(conf)``: the ``ModelConfig`` fields the file's
  published keys set, with the values they set them to.

The two products below are the reference's (float32 at ``highest``) and
the control's (int8 weights and activations, the step below the bfloat16
the configurations state).
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(x: jax.Array, w: jax.Array) -> jax.Array:
    """x @ w in float32 at the highest matmul precision."""
    return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)


def _int8(a: jax.Array, axis: int):
    scale = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(a / scale), -127, 127).astype(jnp.int8), scale


def mm_int8(x: jax.Array, w: jax.Array) -> jax.Array:
    """x @ w with x quantised per row and w per output column to int8
    (symmetric, round to nearest), accumulated in int32."""
    xq, xs = _int8(x.astype(F32), -1)
    wq, ws = _int8(w.astype(F32), -2)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(F32) * xs * ws.reshape((1,) * (x.ndim - 1) + (-1,))


def family(name: str):
    """The reference module of a configuration's ``reference`` key."""
    return importlib.import_module(f"{__name__}.{name}")


def part(name: str, fn: str):
    """Function ``fn`` of family ``name``'s module; an error that names
    both where the module lacks it."""
    mod = family(name)
    got = getattr(mod, fn, None)
    if not callable(got):
        raise NotImplementedError(
            f"family module {mod.__name__} gives no {fn}(): a family module "
            f"gives hidden, head, decode_work, prefill_work and program_fields")
    return got


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)
