"""The one traffic generator: it turns a mix's parameters and a seed into
the events a run sends.

Every seed gets the same set of sizes and arrival gaps, in another order,
so that runs with different seeds do the same work:

* closed loops cycle through a seeded permutation of the event pool (or of
  its batches);
* open loops with ``"arrivals": "poisson"`` space ``rate_per_s * seconds``
  arrivals by the midpoint quantiles of an exponential distribution of
  mean ``1 / rate_per_s``, shuffled by the seed.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

ARRIVALS = ("poisson",)


def seed_words(seed: int) -> np.ndarray:
    """Four independent 32-bit words from a seed of any size: weights,
    event pool, pool order, arrival gaps."""
    return np.random.SeedSequence(abs(int(seed))).generate_state(4)


def pool_order(n: int, word: int) -> np.ndarray:
    """A seeded permutation of ``range(n)``; closed loops cycle through it."""
    return np.random.default_rng(word).permutation(n)


def arrival_times(mix: Dict, seconds: float, word: int) -> np.ndarray:
    """Due times, in seconds from the window's start, of an open loop."""
    if mix.get("arrivals") not in ARRIVALS:
        raise ValueError(f"arrivals {mix.get('arrivals')!r} not in "
                         f"{ARRIVALS}")
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return np.cumsum(np.random.default_rng(word).permutation(gaps))
