"""Open-loop arrival schedules, read from a traffic mix's parameters.

An arrival process is a dict with ``process`` and ``per_s`` (mean
arrivals per second over the window):

- ``fixed``: evenly spaced, the first at the window's start;
- ``poisson``: exponential gaps.  The gaps are the n exponential
  quantiles (i + 1/2)/n, scaled to fill the window, in an order drawn from
  the seed: every seed offers the same number of arrivals with the same
  set of gaps, so seeds change which request meets which state and not
  how much work there is.

With ``order_seed`` the order of the gaps is drawn from that number, not
from the run's seed: every run then offers the same arrival times, and
its seed changes only what is asked.  A queue's tail follows how the gaps
are ordered, so this keeps one mix's tail from swinging with the seed.

The schedule never depends on how fast the service is: arrivals are due
at their offsets whatever happened before.
"""
from __future__ import annotations

import numpy as np


def _poisson(n: int, seconds: float, rng) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u)
    rng.shuffle(gaps)
    gaps *= seconds / gaps.sum()
    return np.cumsum(gaps) - gaps          # first arrival at 0, last < seconds


def offsets(spec: dict, seconds: float, rng) -> np.ndarray:
    """Due offsets in [0, seconds) of one arrival process."""
    n = int(round(float(spec["per_s"]) * seconds))
    if n <= 0:
        return np.zeros((0,))
    kind = spec["process"]
    if "order_seed" in spec:
        rng = np.random.default_rng(int(spec["order_seed"]))
    if kind == "fixed":
        return np.arange(n) / float(spec["per_s"])
    if kind == "poisson":
        return _poisson(n, seconds, rng)
    raise ValueError(f"unknown arrival process {kind!r}")


def ingest_mode(traffic: dict) -> str:
    """'open' (points due on a schedule), 'closed' (the next point is
    always ready) or 'none' (a read-only mix)."""
    ing = traffic.get("ingest")
    if ing is None:
        return "none"
    return ing.get("mode", "open")
