"""The one traffic generator: a traffic file's parameters and a seed in,
a fixed list of requests out.

Every seed gets the same set of sizes and arrival gaps, in its own order,
so seeds change which request comes when and what noise it starts from,
never how much work a run holds.

Arrival kinds:

* ``poisson`` — open loop at ``rate_rps``: the ``n = rate * seconds``
  gaps are the exponential distribution's quantiles at ``(i + 0.5) / n``,
  scaled to span the window, in an order the seed picks: with
  ``order_seed`` in the file, one fixed shuffle drawn from it, rotated by
  the seed (every seed then meets the same bursts, at other times);
  without it, a shuffle of the seed's own.
* ``backlog`` — ``ceil(backlog_rps * seconds)`` requests all due at the
  window's start.
* ``bursty`` — a burst of ``burst_size`` requests every ``period_s``
  seconds through the window, spread over ``jitter_s`` inside each burst
  at the uniform distribution's quantiles, shuffled by the seed.

Tiers are drawn in exact proportion to their weights, then shuffled.
"""
from __future__ import annotations

import math
from typing import List

import numpy as np


def request_count(traffic, seconds: float) -> int:
    kind = traffic["arrivals"]
    if kind == "poisson":
        return max(1, int(round(traffic["rate_rps"] * seconds)))
    if kind == "backlog":
        return max(1, int(math.ceil(traffic["backlog_rps"] * seconds)))
    if kind == "bursty":
        return _bursts(traffic, seconds) * traffic["burst_size"]
    raise ValueError(f"unknown arrivals {kind!r}")


def _bursts(traffic, seconds: float) -> int:
    return max(1, int(seconds // traffic["period_s"]))


def arrival_times(traffic, n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    if traffic["arrivals"] == "backlog":
        return np.zeros(n)
    if traffic["arrivals"] == "bursty":
        size = traffic["burst_size"]
        q = (np.arange(size) + 0.5) / size * traffic["jitter_s"]
        return np.concatenate([b * traffic["period_s"] + rng.permutation(q)
                               for b in range(_bursts(traffic, seconds))])
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    if "order_seed" in traffic:
        gaps = np.random.default_rng(traffic["order_seed"]).permutation(gaps)
        gaps = np.roll(gaps, int(rng.integers(n)))
    else:
        gaps = rng.permutation(gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds / gaps.sum())


def tier_list(traffic, n: int, rng: np.random.Generator) -> List[dict]:
    tiers = traffic["tiers"]
    w = np.asarray([t["weight"] for t in tiers], np.float64)
    counts = np.floor(n * w / w.sum()).astype(int)
    counts[: n - counts.sum()] += 1
    drawn = [t for t, c in zip(tiers, counts) for _ in range(c)]
    return [drawn[i] for i in rng.permutation(n)]


def generate(traffic, seed: int, seconds: float) -> List[dict]:
    """Requests as dicts: ``arrival`` (seconds from the window's start),
    ``tol``, ``num_steps`` and ``noise_seed`` (below 2**31)."""
    rng = np.random.default_rng(seed)
    n = request_count(traffic, seconds)
    times = arrival_times(traffic, n, seconds, rng)
    tiers = tier_list(traffic, n, rng)
    base = int(rng.integers(0, 2 ** 31 - 1 - n))
    noise = base + rng.permutation(n)
    return [{"arrival": float(times[i]), "tol": float(tiers[i]["tol"]),
             "num_steps": int(traffic["num_steps"]),
             "noise_seed": int(noise[i])} for i in range(n)]
