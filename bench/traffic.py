"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<name>.json``) fixes the closed loop's batch (the
number of clients, one per engine slot), the prompt length, the cache
length and the distribution of output lengths.  Every seed gets the same
output lengths in the same order (drawn once, from a fixed stream): the
order decides how often slots free on the same step, which moved a
closed loop's throughput by some percent from seed to seed, so the seed
changes the prompt tokens (drawn uniformly from the vocabulary) and the
weights, never the work.

A fixed-length mix keeps its slots in step, as a batch job does: every
slot frees on the same step.  A mix whose lengths vary forgets how it
started, and is served from its steady state: the first ``batch``
requests stand for the requests a long-running loop has in flight, with
lengths at the quantiles of the mix's residual life (what is left of a
request in a slot at a random step, ``P(R = r) = P(L >= r) / E[L]``), so
slots free at the steady rate from the window's open instead of all
together.  They are marked ``in_flight`` and give no time to first token.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"
ORDER_STREAM = 1            # the seed the order of output lengths comes from


@dataclass(frozen=True)
class Mix:
    name: str
    batch: int
    prompt_len: int
    cache_len: int
    requests: int
    output: Dict

    @property
    def max_new(self) -> int:
        out = self.output
        return out["tokens"] if out["kind"] == "fixed" else out["max"]


def load_mix(name: str, directory: Path = TRAFFIC_DIR) -> Mix:
    raw = json.loads((directory / f"{name}.json").read_text())
    if raw.get("loop") != "closed":
        raise ValueError(f"traffic {name}: only closed-loop mixes are served")
    mix = Mix(name=name, batch=int(raw["batch"]),
              prompt_len=int(raw["prompt_len"]),
              cache_len=int(raw["cache_len"]), requests=int(raw["requests"]),
              output=dict(raw["output"]))
    output_lengths(mix)                     # rejects an unknown kind early
    if mix.prompt_len + mix.max_new > mix.cache_len:
        raise ValueError(f"traffic {name}: prompt_len + longest output "
                         f"exceeds cache_len {mix.cache_len}")
    return mix


def output_lengths(mix: Mix) -> np.ndarray:
    """The mix's output lengths in queue order before shuffling: for a
    lognormal mix, the quantiles at (i + 1/2) / n, clipped."""
    out, n = mix.output, mix.requests
    if out["kind"] == "fixed":
        return np.full(n, int(out["tokens"]), np.int64)
    if out["kind"] == "lognormal":
        z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
        lens = np.rint(out["median"] * np.exp(out["sigma"] * z))
        return np.clip(lens, out["min"], out["max"]).astype(np.int64)
    raise ValueError(f"traffic {mix.name}: unknown output kind {out['kind']!r}")


def residual_lengths(lengths: np.ndarray, n: int) -> np.ndarray:
    """The ``n`` quantiles at (i + 1/2) / n of the residual life of
    ``lengths``: ``P(R = r) = #(L >= r) / sum(L)`` for r = 1 .. max(L)."""
    ordered = np.sort(lengths)
    r = np.arange(1, ordered[-1] + 1)
    at_least = len(ordered) - np.searchsorted(ordered, r, side="left")
    cdf = np.cumsum(at_least) / at_least.sum()
    return np.searchsorted(cdf, (np.arange(n) + 0.5) / n) + 1


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one purpose (``stream``) of one seed; takes
    any non-negative seed, 64-bit ones included."""
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def generate(mix: Mix, seed: int, vocab_size: int) -> List[Dict]:
    """``mix.requests`` requests in queue order: prompt tokens, the number
    of tokens each asks for, and whether it stands for a request already
    in flight.  The queue is cut into blocks of one batch, each block
    takes every ``requests / batch``-th length of the sorted lengths, and
    the fixed stream orders the blocks and the lengths within each."""
    rng = rng_for(ORDER_STREAM, 1)
    ordered = np.sort(output_lengths(mix))
    n_blocks = -(-mix.requests // mix.batch)
    blocks = [ordered[b::n_blocks] for b in range(n_blocks)]
    lens = np.concatenate([rng.permutation(blocks[b])
                           for b in rng.permutation(n_blocks)])
    in_flight = mix.batch if ordered[0] != ordered[-1] else 0
    if in_flight:
        lens[:in_flight] = rng.permutation(residual_lengths(ordered, in_flight))
    prompts = rng_for(seed, 2).integers(
        0, vocab_size, size=(mix.requests, mix.prompt_len), dtype=np.int32)
    return [{"prompt": prompts[i], "max_new": int(lens[i]),
             "in_flight": i < in_flight} for i in range(mix.requests)]
