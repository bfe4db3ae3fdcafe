"""Open-loop arrivals and the latency percentiles taken from them.

Requests are due on a schedule fixed before the run; each one's latency
runs from when it was due, not from when the generator got round to
sending it, so a stall is charged to every request it delayed. A request
that failed or never came back counts as infinitely late.
"""
from __future__ import annotations

import math
from typing import Sequence

import numpy as np


def exponential_schedule(rng: np.random.Generator, rate: float,
                         seconds: float, block_s: float) -> np.ndarray:
    """Due times (seconds from the start) of ``rate * seconds`` requests
    whose gaps are the exponential law's quantiles at ``rate``, stretched
    to span ``seconds``. The quantiles are dealt round-robin into blocks
    of about ``block_s`` seconds each, and the blocks and the gaps inside
    each are put in an order drawn from ``rng``. Every seed thus sends the
    same requests with the same gaps at the same load in every block:
    seeds differ in where the bursts fall inside a block, not in how much
    work there is or how it bunches over the window (which would move a
    tail percentile between seeds far more than between runs)."""
    n = int(round(rate * seconds))
    if n < 1 or seconds <= 0 or block_s <= 0:
        raise ValueError(f"bad schedule: rate={rate}, seconds={seconds}, "
                         f"block_s={block_s}")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum()
    nb = max(1, min(n, int(round(seconds / block_s))))
    order = np.concatenate([rng.permutation(gaps[b::nb])
                            for b in rng.permutation(nb)])
    return np.cumsum(order) - gaps.min() / 2


def latencies(due: Sequence[float], done: Sequence[float]) -> np.ndarray:
    """Seconds from due to completion; NaN completions (failed or never
    answered) become +inf."""
    due = np.asarray(due, np.float64)
    done = np.asarray(done, np.float64)
    lat = done - due
    return np.where(np.isnan(lat), np.inf, lat)


def percentile(lat: np.ndarray, q: float) -> float:
    """Nearest-rank percentile over every request (no interpolation, so a
    reported tail is a latency some request really had)."""
    lat = np.sort(np.asarray(lat, np.float64))
    if lat.size == 0:
        raise ValueError("no requests")
    rank = max(1, int(math.ceil(q / 100.0 * lat.size)))
    return float(lat[rank - 1])
