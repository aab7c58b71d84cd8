"""Percentiles over all requests of a window.

A request that never completed counts as ``inf``, so it lies above every
latency limit and raises the tail, as a missed request does for a user.
The interpolation is numpy's default (linear between closest ranks), the
arithmetic of the program's ``obs.hub`` histograms.
"""
from __future__ import annotations

import math

import numpy as np


def percentile(samples, q: float) -> float:
    """q-th percentile (0..100) of ``samples``; inf where it reaches a
    missing request, nan for no samples."""
    xs = np.sort(np.asarray(samples, float))
    if xs.size == 0:
        return math.nan
    pos = (xs.size - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if math.isinf(xs[hi]):
        return math.inf
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(samples) -> float:
    return percentile(samples, 50.0)
