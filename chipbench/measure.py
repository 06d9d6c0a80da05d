"""Arithmetic the metric readers share: which requests count, percentiles,
and counter deltas over the window. Imports no JAX."""
from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np


def percentile(values: List[float], q: float) -> Optional[float]:
    """numpy's linear percentile; None when it is not finite (a failed
    request, counted as infinitely late, sets it) or there are no values."""
    if not values:
        return None
    v = float(np.percentile(np.asarray(values, np.float64), q))
    return v if math.isfinite(v) else None


def due(run) -> List[Dict]:
    """The requests due in the window (sent in it, by their schedule)."""
    return [r for r in run.records if r["phase"] == "window"]


def latencies(run) -> List[float]:
    """Scheduled send to final answer; a failed request is infinitely
    late."""
    return [r["done_t"] - r["sched_t"] if r.get("ok") else math.inf
            for r in due(run)]


def delta(run, key: str) -> List[float]:
    """Per pool: the counter's growth over the window."""
    return [b[key] - a[key] for a, b in zip(run.c0, run.c1)]


def window_s(run) -> float:
    return run.window[1] - run.window[0]


def slot_step_flops(run) -> float:
    """Operations of the eps evaluations the window's ticks made: the
    configuration's trunk's ``eps_flops`` per slot-step."""
    from reference import trunk
    return sum(delta(run, "slot_steps")) * trunk(run.config).eps_flops(
        run.config)


def step_mfu(run):
    """The whole tick's share of the chips' peak, in %: eps operations of
    every slot-step the window made over window seconds times the bf16
    peak times the chips."""
    if run.peaks is None:
        return None
    return 100.0 * slot_step_flops(run) / (
        window_s(run) * run.peaks["flops_bf16"] * run.chips)


def idle_share(run):
    """Share of the traced window, in %, in which no op ran on the device:
    one less the union of op intervals over the window, the mean over
    chips."""
    return None if run.trace is None else 100.0 * run.trace.idle_share

