"""What the traffic generators share.

A generator is a pure function of ``(seed, traffic, vocab, seconds)``. The
traffic file fixes the *structure* of a run: how many arrivals, which
lengths, which gaps, which session or document each belongs to, in which
order (all drawn from the file's ``structure_seed`` over fixed quantile
sets). ``--seed`` fixes the token values (and the weights). So every seed
offers the same sizes and arrivals, and runs with different seeds differ by
the system's noise, not by the draw: with the order drawn from ``--seed``
too, six seeds of the sessions mix spread by 14-19% in median time to first
token while two runs of one seed agreed within 4% (PERF.md, PR 24). Another
structure is another traffic file with another ``structure_seed``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import numpy as np


@dataclass
class Arrival:
    """One request of a schedule. ``due`` is seconds from the window's start
    (open loop) or None (closed loop, where ``client`` orders the sends).
    ``extends`` says the prompt repeats the beginning of an earlier prompt
    of this schedule, so a cache can serve part of it."""

    prompt: list
    max_new: int
    due: Optional[float] = None
    client: int = 0
    extends: bool = False
    kind: str = ""


@dataclass
class Schedule:
    arrivals: list = field(default_factory=list)
    # Served to completion during set-up, in order, before the window.
    setup: list = field(default_factory=list)


def rng_for(seed: int, salt: int) -> np.random.Generator:
    """``seed`` is any whole number up to a little over 2**31."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, salt])


def rngs(seed: int, traffic: dict, salt: int) -> tuple:
    """(structure, tokens): the first from the traffic file's
    ``structure_seed``, the second from ``--seed``."""
    return (rng_for(int(traffic.get("structure_seed", 0)), salt),
            rng_for(seed, salt + 1000))


def quantile_set(n: int, lo: float, hi: float, law: str) -> np.ndarray:
    """``n`` whole numbers in [lo, hi] at the mid-quantiles of the law:
    the same set for every seed."""
    if n <= 0:
        return np.zeros((0,), np.int64)
    u = (np.arange(n) + 0.5) / n
    if law == "uniform":
        x = lo + (hi - lo) * u
    elif law == "loguniform":
        x = np.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * u)
    else:
        raise ValueError(f"unknown law {law!r} (uniform, loguniform)")
    return np.rint(x).astype(np.int64)


def shuffled(rng: np.random.Generator, xs) -> np.ndarray:
    xs = np.asarray(xs).copy()
    rng.shuffle(xs)
    return xs


def apportion(n: int, weights) -> np.ndarray:
    """``n`` items over slots in proportion to ``weights`` (largest
    remainder): whole counts that sum to ``n``."""
    w = np.asarray(weights, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = n - int(counts.sum())
    if rest:
        order = np.argsort(-(exact - counts), kind="stable")
        counts[order[:rest]] += 1
    return counts


def zipf_weights(n: int, s: float) -> np.ndarray:
    return 1.0 / np.arange(1, n + 1, dtype=np.float64) ** s


def poisson_offsets(rng: np.random.Generator, n: int,
                    seconds: float) -> np.ndarray:
    """``n`` arrival times in [0, seconds): the gaps are the mid-quantiles
    of the exponential law (so their set and their sum are the same for
    every seed) in a seeded order."""
    if n <= 0:
        return np.zeros((0,), np.float64)
    gaps = shuffled(rng, -np.log1p(-(np.arange(n) + 0.5) / n))
    t = np.cumsum(gaps) - 0.5 * gaps[0]
    return t / gaps.sum() * seconds


def burst_offsets(rng: np.random.Generator, n: int, seconds: float,
                  burst: tuple, within_s: float) -> np.ndarray:
    """``n`` arrival times in bursts of ``burst[0]..burst[1]`` requests
    inside ``within_s`` seconds, the bursts themselves a Poisson process of
    the same mean rate."""
    sizes = []
    left = n
    cycle = quantile_set(max(1, n // burst[0]), burst[0], burst[1],
                         "uniform")
    for size in shuffled(rng, cycle):
        if left <= 0:
            break
        sizes.append(int(min(size, left)))
        left -= sizes[-1]
    if left > 0:
        sizes.append(left)
    starts = poisson_offsets(rng, len(sizes), seconds)
    out = []
    for start, size in zip(starts, sizes):
        inside = np.sort(rng.uniform(0.0, within_s, size))
        out.extend(np.minimum(start + inside, seconds * (1 - 1e-9)))
    return np.sort(np.asarray(out))


def arrival_offsets(rng: np.random.Generator, traffic: dict,
                    seconds: float) -> np.ndarray:
    """Arrival times of an open-loop mix: ``rate`` requests a second over
    the whole of ``seconds``, Poisson unless the mix names bursts."""
    n = int(round(float(traffic["rate"]) * seconds))
    arrivals = traffic.get("arrivals", {"law": "poisson"})
    if arrivals["law"] == "poisson":
        return poisson_offsets(rng, n, seconds)
    if arrivals["law"] == "bursts":
        return burst_offsets(rng, n, seconds, tuple(arrivals["size"]),
                             float(arrivals["within_s"]))
    raise ValueError(f"unknown arrival law {arrivals['law']!r}")


def tokens(rng: np.random.Generator, n: int, vocab: int) -> list:
    """``n`` token ids in [1, vocab), as the plain list the engine takes."""
    return rng.integers(1, vocab, int(n)).tolist()
