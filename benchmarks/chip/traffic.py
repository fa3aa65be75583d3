"""The general traffic generator: job sizes from a traffic file's
parameters.

Every seed gets the same job sizes in the same order. In a closed loop
the order decides which jobs share a drain, and so the latencies; the
seed varies only the weights and inputs, never the work.
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, stream); any whole seed."""
    words = [ord(c) for c in stream]
    return np.random.default_rng([seed % 2**64, *words])


def power_law_sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a power law P(k) ~ k**-alpha on
    [min, max], in increasing order."""
    k = np.arange(spec["min"], spec["max"] + 1)
    p = k.astype(float) ** -spec["alpha"]
    cdf = np.cumsum(p / p.sum())
    q = (np.arange(n) + 0.5) / n
    return k[np.minimum(np.searchsorted(cdf, q), len(k) - 1)]


def size_stream(spec: dict, stream: str):
    """Endless job sizes: each block of ``block`` jobs is a shuffle of the
    same ``block`` quantiles, in an order fixed by ``stream`` alone."""
    block = power_law_sizes(spec, spec["block"])
    rng = rng_for(0, stream)
    while True:
        for s in rng.permutation(block):
            yield int(s)
