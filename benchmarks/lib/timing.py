"""Arithmetic of the measured window.

A throughput is all the items of the window over all its time: every step
that ended inside it, and the wall time from the start of the first to the
end of the last, so that a stall between or inside steps moves it. A step
tail is the 95th percentile of the single steps' wall times.
"""
from __future__ import annotations

import math


def rate(n_steps: int, items_per_step: float, window_s: float) -> float:
    if n_steps < 1 or window_s <= 0:
        raise ValueError("the window holds no step")
    return items_per_step * n_steps / window_s


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile, q in [0, 100]."""
    if not values:
        raise ValueError("no values")
    s = sorted(values)
    k = (len(s) - 1) * q / 100.0
    lo = math.floor(k)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (k - lo)


def step_ms_p95(step_times) -> float:
    return 1e3 * percentile(step_times, 95.0)
