"""Arithmetic of the end-to-end metrics and of the traffic schedules.

Pure functions over plain numbers, so the tests can pin each one and no
program change can move them.
"""

from __future__ import annotations

import math

import numpy as np


def rng(seed: int, salt: int = 0) -> np.random.Generator:
    """A generator from any whole seed (negative or past 64 bits too)."""
    return np.random.default_rng([seed & (2**64 - 1), salt])


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100): the smallest value with at
    least q% of the values at or below it. Infinite values (misses)
    count and sort last."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = max(0, math.ceil(q / 100.0 * len(xs)) - 1)
    return float(xs[k])


def open_loop_latencies(scheduled, completed, give_up: float) -> list[float]:
    """Latency of each request from its scheduled arrival to its result.

    `completed[i]` is None for a request that failed, was refused or
    never came back: it counts as a miss, with the latency it had
    reached when the run gave up waiting (`give_up`), a lower bound of
    its unbounded latency that keeps the number finite."""
    out = []
    for s, c in zip(scheduled, completed):
        out.append((c if c is not None else give_up) - s)
    return out


def served_per_s(n_ok: int, start: float, end: float) -> float:
    """Requests completed correctly over the whole window."""
    return n_ok / (end - start)


def tree_nodes_per_s(trees, start: float, end: float) -> float:
    """Explored tree nodes of the whole solves in the window, over the
    time from the first solve's start to the last one's end."""
    return float(sum(trees)) / (end - start)


def open_loop_schedule(rate: float, seconds: float, rows, seed: int,
                       arrivals: str = "regular"):
    """Arrival offsets and rows of an open loop of n = round(rate *
    seconds) requests, drawn from the run's `seed`.

    Rows come in blocks, each block every row once in an order drawn
    from the seed, so every seed offers the same requests. Arrivals are
    `regular` (one every 1/rate seconds, the same for every seed) or
    `poisson` (exponential gaps drawn from the seed, scaled to fill
    `seconds`)."""
    n = max(1, int(round(rate * seconds)))
    r = rng(seed, 1)
    if arrivals == "regular":
        gaps = np.full(n, seconds / n)
    elif arrivals == "poisson":
        gaps = r.exponential(1.0, n)
        gaps *= seconds / gaps.sum()
    else:
        raise ValueError(f"unknown arrivals {arrivals!r}")
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    order = []
    while len(order) < n:
        order += [rows[i] for i in r.permutation(len(rows))]
    return [float(a) for a in offsets], order[:n]


def closed_cycle(rows, seed: int, cycle: int) -> list:
    """The rows of one cycle of a closed loop, in the seed's order."""
    order = rng(seed, 2 + cycle).permutation(len(rows))
    return [rows[i] for i in order]


def spread(values) -> float:
    """Inter-quartile distance as a share of the median (Python's
    `statistics.quantiles(values, n=4)`)."""
    import statistics
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med
