"""Arithmetic shared by the metric readers in ``bench/metrics/``."""
from __future__ import annotations

import math

import numpy as np

from . import trace as tr

__all__ = ["nearest_rank", "p95", "due_in_window", "idle_share", "module_mean_ms",
           "scope_ms", "idle_under_ms"]


def nearest_rank(values, q: float) -> float | None:
    """The ``q`` quantile by nearest rank: the smallest value with at
    least ``q`` of the sample at or below it."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        return None
    return float(v[math.ceil(q * v.size) - 1])


def p95(values) -> float | None:
    return nearest_rank(values, 0.95)


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


def scope_ms(run, program: str, scope: str) -> float | None:
    """Mean device milliseconds per run of the programs named ``program``
    spent in the operations whose ``op_name`` has ``scope`` as a path
    segment: a ``jax.named_scope`` of the program, found through its
    ``op_scopes`` map of ``program`` (instruction -> ``op_name``).  Each
    operation counts for the program run that encloses it, containers
    are left out, and only runs wholly within the traced window count.
    None where the map has no operation of the scope or none ran."""
    names = {i for i, op in run.op_scopes.get(program, {}).items()
             if scope in op.split("/")}
    if run.trace is None or not names:
        return None
    sec = tr.scope_seconds(run.trace, program, names)
    return 1e3 * float(np.mean(sec)) if sec and any(sec) else None


def idle_under_ms(run, span: str) -> float | None:
    """Device-idle milliseconds per decode step (a step of the bench that
    decoded) that ``trace.gaps_by_host`` charges to the host span
    ``span``, the innermost span over each gap, in the traced window.
    None where the trace holds no such span or the window no decode."""
    if run.trace is None or not run.trace.devices or run.traced is None:
        return None
    n = sum(1 for s in run.steps_in(run.traced) if s.decode_pos)
    if not n or not any(e.name == span for e in run.trace.host):
        return None
    return 1e3 * dict(tr.gaps_by_host(run.trace)).get(span, 0.0) / n
