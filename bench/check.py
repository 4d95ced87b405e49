"""The comparison that decides ``correct``.

After the window, a sample of the requests the window finished (drawn
from the seed, the longest among them) is run once through the float32
reference over its prompt and the tokens the engine fed: the prefill's
argmax and then every decode token.  For each of those tokens the
reference's logits give the gap by which it lies below the reference's
best token; the number compared is the widest gap.  Greedy decoding in
bfloat16 may pick a runner-up only where the two lie within its rounding.

The control is the same reference with int8 matrix products, put in the
program's place: at each of those positions its own first choice is read
off in the float32 logits the same way.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .traffic import rng_for

BLOCK = 256           # positions per call of the output matrix


def sample(finished: Sequence, seed: int, n: int) -> List:
    """``n`` finished requests drawn from ``seed``, the longest included."""
    if not finished:
        return []
    longest = max(finished, key=lambda s: (len(s.times), -s.rid))
    rest = [s for s in finished if s is not longest]
    pick = rng_for(seed, 4).permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


@partial(jax.jit, static_argnames=("vocab",))
def _gap(w, h, tok, *, vocab):
    logits = reference.mm_f32(h, w)[:, :vocab]
    at = jnp.take_along_axis(logits, tok[:, None], 1)[:, 0]
    return jnp.max(logits, -1) - at


@partial(jax.jit, static_argnames=("vocab", "mm"))
def _first(w, h, *, vocab, mm):
    return jnp.argmax(mm(h, w)[:, :vocab], -1).astype(jnp.int32)


def _blocks(fn, h, *rest):
    out = []
    for i in range(0, h.shape[0], BLOCK):
        out.append(fn(h[i:i + BLOCK], *(r[i:i + BLOCK] for r in rest)))
    return jnp.concatenate(out)


def gaps(conf: dict, params, rows: Sequence[Dict], prompt_len: int,
         max_new: int, *, control: bool = False, rows_per_call: int = 1
         ) -> np.ndarray:
    """For every token of ``rows`` (``prompt`` and ``fed``, the tokens the
    engine fed after the prompt), its gap below the reference's best; with
    ``control``, the gap of the control's first choice at that position.
    Sequences are padded at the end to one length, ``prompt_len +
    max_new``, so the reference compiles once per cell."""
    fam = reference.family(conf["reference"])
    w, vocab = fam.head(conf, params), conf["vocab_size"]
    T = prompt_len + max_new
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(rows), rows_per_call):
            group = rows[i:i + rows_per_call]
            toks = np.zeros((rows_per_call, T), np.int32)
            for j, r in enumerate(group):
                seq = np.concatenate([r["prompt"], r["fed"][:-1]])
                toks[j, :len(seq)] = seq
            h = fam.hidden(conf, params, toks, reference.mm_f32)
            hc = (fam.hidden(conf, params, toks, reference.mm_int8)
                  if control else None)
            for j, r in enumerate(group):
                pos = slice(prompt_len - 1, prompt_len - 1 + len(r["fed"]))
                if control:
                    tok = _blocks(partial(_first, w, vocab=vocab,
                                          mm=reference.mm_int8), hc[j, pos])
                else:
                    tok = jnp.asarray(r["fed"], jnp.int32)
                out.append(np.asarray(
                    _blocks(partial(_gap, w, vocab=vocab), h[j, pos], tok)))
    return np.concatenate(out) if out else np.zeros(0)
