"""Open-loop arrivals at a fixed rate, with log-normal lengths.

The schedule covers the warm-up and the window: the inter-arrival gaps
are the exponential's quantiles at (i + 0.5) / N, the prompt and output
lengths the quantiles of log-normals clipped to the mix's range (placed
so that the clipped set keeps the mean the mix states), each set shuffled
once in one fixed order (`ORDER_SEED`). The run's seed draws the token
ids only. So every seed offers the same work in the same order: above the
knee the order of lengths decides which ticks prefill, and seeds that
shuffled it read tokens/s 66-89 where two runs of one seed agreed within
2 %.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

# the one order of gaps and lengths every seed is served in
ORDER_SEED = 0


def _lognormal_grid(spec: dict, n: int) -> np.ndarray:
    """n quantiles of a log-normal clipped to [min, max], its location
    chosen so that the clipped set has the stated mean."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])

    def grid(mu):
        return np.clip(np.exp(mu + spec["sigma"] * z).round(), spec["min"],
                       spec["max"])
    lo, hi = math.log(spec["min"]) - 5, math.log(spec["max"]) + 5
    for _ in range(60):
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if grid(mid).mean() < spec["mean"] else (lo, mid)
    return grid(lo).astype(int)


def generate(mix: dict, seed: int, *, vocab: int, seconds: float) -> list:
    order = np.random.default_rng(ORDER_SEED)
    rng = np.random.default_rng(seed)
    rate = mix["rate_per_s"]
    span = mix["warm_s"] + seconds
    n = int(math.ceil(rate * span))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(order.permutation(gaps))
    prompts = order.permutation(_lognormal_grid(mix["prompt_tokens"], n))
    outputs = order.permutation(_lognormal_grid(mix["output_tokens"], n))
    return [dict(uid=u, prompt=rng.integers(0, vocab, (int(p),), np.int32),
                 max_new_tokens=int(o), due_s=float(t))
            for u, (t, p, o) in enumerate(zip(due, prompts, outputs))]
