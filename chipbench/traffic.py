"""The one generator of traffic, driven by a mix's data file.

Every seed gets the same work: the same requests with the same prompt
lengths, output lengths and gaps between arrivals (each drawn at evenly
spaced quantiles of its distribution, in one fixed shuffled order), and
only other token ids.  A tail over some tens of requests moves by a
quarter with the order alone, so the order is part of the mix, and runs of
different seeds differ by content, as two runs of one seed differ by
timing alone.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


ORDER_SEED = 0x5C4ED  # the one order of every mix's lengths and gaps


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: int
    due_s: float          # from the window's start
    prompt: np.ndarray    # int32 token ids
    max_new: int


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict[str, Any], n: int) -> np.ndarray:
    q = quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"] + 1)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)
    buckets = spec.get("buckets")
    if buckets:
        b = np.asarray(sorted(buckets))
        x = b[np.searchsorted(b, x)]
    return x


def poisson_gaps(rate: float, n: int) -> np.ndarray:
    """Exponential quantiles: a Poisson stream's gaps, evenly sampled."""
    return -np.log1p(-quantiles(n)) / rate


def open_loop(spec: Dict[str, Any], seed: int, seconds: float, vocab: int,
              rate: float | None = None) -> List[Arrival]:
    """Requests due in a window of ``seconds`` at the mix's rate (or
    ``rate``), in arrival order.  The first is due at 0."""
    rate = spec["rate_per_s"] if rate is None else rate
    n = max(1, math.floor(rate * seconds))
    order = np.random.default_rng(ORDER_SEED)
    gaps = order.permutation(poisson_gaps(rate, n))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    if due[-1] >= seconds:
        due *= seconds / (due[-1] + gaps[-1])
    plen = order.permutation(lengths(spec["prompt"], n))
    out = order.permutation(lengths(spec["output"], n))
    rng = np.random.default_rng([int(seed), 0x7A11])
    toks = rng.integers(0, vocab, size=int(plen.sum()), dtype=np.int32)
    cuts = np.cumsum(plen)[:-1]
    return [Arrival(i, float(due[i]), p, int(out[i]))
            for i, p in enumerate(np.split(toks, cuts))]
