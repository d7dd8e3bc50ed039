"""Traffic from a mix file and a seed.

Every seed gets the same multiset of sizes and of gaps between arrivals,
in another order: each block of ``set_size`` requests holds the
stratified quantiles of the mix's length distributions (and, for an open
loop, of the exponential gaps), permuted by the seed.  The seed also draws
the token ids, uniformly over the vocabulary.  So two seeds do the same
work, and only the order and the content differ.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass
class Item:
    """One request of the stream, before it is handed to the engine."""

    prompt: np.ndarray      # (L,) int32 token ids
    max_new_tokens: int
    offset_s: float         # due time after the window opens (open loop)


def rng(seed: int, stream: str) -> np.random.Generator:
    """A generator for one named stream of one seed.  Any integer seed,
    negative or beyond 64 bits, maps to a valid entropy value."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([seed % (1 << 64), tag])


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The n stratified quantiles (i + 1/2) / n of a length distribution,
    rounded to whole tokens and clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    elif dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        x = np.floor(x)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """Stratified quantiles of the exponential gap of a Poisson process."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def stream(mix: dict, seed: int, vocab: int, *,
           rate: float | None = None, name: str = "window"):
    """The seed's endless stream of requests, as a generator.  ``rate``
    (requests per second) is needed for an open loop; ``name`` separates
    the window's stream from other streams of the same seed."""
    size = int(mix["set_size"])
    r = rng(seed, name)
    prompts = quantiles(mix["prompt_tokens"], size)
    outputs = quantiles(mix["output_tokens"], size)
    open_loop = mix["loop"] == "open"
    if open_loop:
        if not rate or rate <= 0:
            raise ValueError("an open-loop mix needs a positive rate")
        gaps = exp_gaps(rate, size)
    t = 0.0
    while True:
        p, o = r.permutation(prompts), r.permutation(outputs)
        g = r.permutation(gaps) if open_loop else np.zeros(size)
        for i in range(size):
            t += float(g[i])
            ids = r.integers(1, vocab, size=int(p[i]), dtype=np.int32)
            yield Item(prompt=ids, max_new_tokens=int(o[i]),
                       offset_s=t if open_loop else 0.0)
