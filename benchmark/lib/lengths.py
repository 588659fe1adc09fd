"""Length distributions of the traffic files, drawn as fixed sets.

A set of n draws is the n quantiles at (i + 0.5) / n of the distribution:
every seed gets the same multiset of sizes and only their order changes, so
two seeds offer the same work.
"""

from __future__ import annotations

import math
import random
import statistics

_NORMAL = statistics.NormalDist()


def quantile(dist: dict, q: float) -> int:
    kind = dist["dist"]
    if kind == "lognormal":
        x = math.exp(math.log(dist["median"])
                     + dist["sigma"] * _NORMAL.inv_cdf(q))
    elif kind == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        x = math.exp(lo + q * (hi - lo))
    elif kind == "fixed":
        x = dist["value"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = dist.get("min", 1), dist.get("max", x)
    return int(min(max(round(x), lo), hi))


def stratified(dist: dict, n: int) -> list[int]:
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


def exponential_gaps(rate: float, n: int) -> list[float]:
    """The n quantiles of an exponential inter-arrival time of mean 1/rate,
    scaled so that they sum to exactly n / rate."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (n / rate) / sum(raw)
    return [g * scale for g in raw]


def prompt_ids(seed: int, index: int, n: int, vocab: int,
               reserved: int = 16) -> list[int]:
    """n word ids for request ``index``; the first ``reserved`` ids belong
    to the tokenizer's template words."""
    rng = random.Random((seed * 1_000_003 + index) & 0xFFFFFFFFFFFF)
    return [rng.randrange(reserved, vocab) for _ in range(n)]
