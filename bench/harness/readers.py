"""Arithmetic shared by the metric readers in ``bench/metrics/``."""
from __future__ import annotations

import math

import numpy as np

from . import trace as tr

__all__ = ["p95", "due_in_window", "idle_share", "module_mean_ms"]


def p95(values) -> float | None:
    """The 95th percentile by nearest rank: the smallest value with at
    least 95% of the sample at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return None
    return float(v[math.ceil(0.95 * v.size) - 1])


def due_in_window(run) -> list:
    """Requests of an open loop that were due in the window."""
    return [r for r in run.recs if r.section == "window"]


def idle_share(run) -> float | None:
    """Percent of the traced window in which the device ran nothing."""
    if run.trace is None or not run.trace.devices or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s(run.trace) / run.trace.window_s)


def module_mean_ms(run, part: str) -> float | None:
    """Mean device milliseconds per run of the programs named ``part``."""
    if run.trace is None:
        return None
    sec = tr.module_seconds(run.trace, part)
    return 1e3 * float(np.mean(sec)) if sec else None
