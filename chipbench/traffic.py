"""The one traffic generator: a traffic file's parameters to request specs.

A traffic file (``chipbench/traffic/<name>.json``) holds:

  loop        "open" (arrivals on a schedule) or "closed" (clients that
              each send their next request when the last one finished)
  rate_per_s  open loop: mean arrival rate of the Poisson process
  clients     closed loop: how many clients
  ramp_s      open loop: seconds of arrivals sent before the window opens
              (not counted), so the window starts in steady state
  fixed       fields every request carries (a dict of wire fields)
  factors     {factor: [[value, weight], ...]}: each request draws one
              value per factor; a dict value is merged into the request,
              any other value is set under the factor's own name
  source      where each sourced parameter comes from, and
  assumed     each parameter that has no source, with why it was chosen
              (the generator reads neither)

Every seed gets the same work in another order: the joint mix of the
factors is split into exact counts (largest remainder) and shuffled, and
the open loop's gaps are the exponential distribution's quantiles at
evenly spaced probabilities, shuffled. Only the order, the gaps' order and
each request's noise seed depend on ``--seed``. Imports no JAX.
"""
from __future__ import annotations

import itertools
import json
import math
from pathlib import Path
from typing import Dict, List

import numpy as np


def load(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per use of the run's seed."""
    return np.random.default_rng([int(seed) % (2 ** 64), stream])


def joint_mix(traffic: Dict) -> List[Dict]:
    """[(spec, weight)] over the product of the factors."""
    names = sorted(traffic.get("factors", {}))
    out = []
    for combo in itertools.product(*(traffic["factors"][n] for n in names)):
        spec, w = dict(traffic.get("fixed", {})), 1.0
        for name, (value, weight) in zip(names, combo):
            if isinstance(value, dict):
                spec.update(value)
            else:
                spec[name] = value
            w *= float(weight)
        if w > 0:
            out.append((spec, w))
    total = sum(w for _, w in out)
    return [(s, w / total) for s, w in out]


def exact_counts(weights: List[float], n: int) -> List[int]:
    """Split n into counts proportional to weights (largest remainder)."""
    raw = [w * n for w in weights]
    counts = [math.floor(r) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: (counts[i] - raw[i], i))
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def specs(traffic: Dict, n: int, seed: int, stream: int) -> List[Dict]:
    """n request specs in the mix's exact proportions, seed-shuffled, each
    with its own noise seed."""
    mix = joint_mix(traffic)
    out: List[Dict] = []
    for (spec, _), c in zip(mix, exact_counts([w for _, w in mix], n)):
        out.extend(dict(spec) for _ in range(c))
    rng = rng_for(seed, stream)
    rng.shuffle(out)
    for s, noise_seed in zip(out, rng.integers(0, 2 ** 31 - 1, len(out))):
        s["seed"] = int(noise_seed)
    return out


def poisson_gaps(rate: float, n: int, seed: int, stream: int) -> np.ndarray:
    """n exponential gaps at mean 1/rate: the quantiles at probabilities
    (i + 1/2) / n, in a seed-shuffled order."""
    p = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-p) / rate
    rng_for(seed, stream).shuffle(gaps)
    return gaps


def open_schedule(traffic: Dict, seconds: float, seed: int) -> Dict:
    """Arrival times relative to the window's opening: the ramp's at
    negative times, the window's in [0, seconds)."""
    rate = float(traffic["rate_per_s"])
    out = {}
    for phase, length, stream in (("ramp", traffic.get("ramp_s", 0.0), 1),
                                  ("window", seconds, 2)):
        n = max(1, round(rate * length)) if length > 0 else 0
        gaps = poisson_gaps(rate, n, seed, 10 + stream)
        t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) if n else []
        t = np.asarray(t) * (length / max(float(np.sum(gaps)), 1e-9))
        if phase == "ramp":
            t = t - length
        out[phase] = [{"t": float(ti), "spec": s} for ti, s in
                      zip(t, specs(traffic, n, seed, 20 + stream))]
    return out


def warm_specs(traffic: Dict, n: int, seed: int) -> List[Dict]:
    """n requests at the mix's median step budget (the upper one of two)
    that cover every kind of request in it (JSON and SSE, every solver
    order and eta), so the warm round also runs the program's periodic
    host work (its checkpoint sweep comes every few ticks)."""
    mix = joint_mix(traffic)
    steps = sorted({s.get("S", 20) for s, _ in mix})
    s_warm = steps[len(steps) // 2]
    kinds = []
    for s, _ in mix:
        k = dict(s, S=s_warm)
        if k not in kinds:
            kinds.append(k)
    noise = rng_for(seed, 3).integers(0, 2 ** 31 - 1, n)
    return [dict(kinds[i % len(kinds)], seed=int(noise[i]))
            for i in range(n)]


def closed_specs(traffic: Dict, seed: int, blocks: int = 64) -> List[Dict]:
    """Closed-loop specs in blocks of twice the client count, each block in
    the mix's exact proportions, so any prefix the clients use is too."""
    n = 2 * int(traffic["clients"])
    return [s for b in range(blocks)
            for s in specs(traffic, n, seed, 100 + b)]
