"""The program's own host spans in a traced run, as the readers of the
per-layer metrics take them.

``run.trace.host`` holds every ``/host:CPU`` event whose name starts with
``repro/`` or ``chipbench/`` as (name, start_ns, end_ns). The program
names its spans (``obs/profiling.py`` in the program):
``repro/pool<i>/{tick, admit, states, launch, block, previews, retire,
checkpoint}``, ``repro/fleet/dispatch``, ``repro/gateway/pump``,
``repro/gateway/deliver``, ``repro/bridge/commands`` and
``repro/http/encode``. A program that has none of them gives the readers
nothing to read, and they return None. Imports no JAX.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, List, Tuple

Span = Tuple[str, float, float]
POOL = re.compile(r"^repro/pool(\d+)/(\w+)$")


def select(run, pattern: str) -> List[Span]:
    """The program spans whose whole name matches ``pattern``, clipped to
    the traced window ``[lo, hi)``; spans wholly outside it are left out.
    Sorted by start."""
    if run.trace is None:
        return []
    lo, hi = run.trace.lo, run.trace.hi
    rx = re.compile(pattern)
    out = [(n, max(a, lo), min(b, hi)) for n, a, b in run.trace.host
           if rx.fullmatch(n) and min(b, hi) > max(a, lo)]
    return sorted(out, key=lambda s: s[1])


def seconds(spans: List[Span]) -> float:
    return sum(b - a for _, a, b in spans) / 1e9


def by_pool(spans: List[Span]) -> Dict[int, List[Span]]:
    """``repro/pool<i>/...`` spans grouped by the pool's number."""
    out: Dict[int, List[Span]] = {}
    for s in spans:
        m = POOL.match(s[0])
        if m:
            out.setdefault(int(m.group(1)), []).append(s)
    return out


def inside(outer: List[Span], inner: List[Span]) -> List[List[Span]]:
    """For each outer span, the inner spans that lie wholly within it (the
    engine thread's spans nest; one thread holds both kinds)."""
    starts = [a for _, a, _ in inner]
    out = []
    for _, a, b in outer:
        i = bisect.bisect_left(starts, a)
        j = bisect.bisect_right(starts, b)
        out.append([s for s in inner[i:j] if s[2] <= b])
    return out


# ------------------------------------------------ what the readers read
def checkpoint_share(run):
    """Share of the traced window, in %, in the supervisor's checkpoint
    sweeps (``repro/pool<i>/checkpoint``, one per swept pool): the pools'
    times summed, since one thread sweeps them all in turn."""
    if not select(run, r"repro/pool\d+/tick"):
        return None
    sweeps = select(run, r"repro/pool\d+/checkpoint")
    return 100.0 * seconds(sweeps) / run.trace.window_s


def engine_host_ms(run):
    """Host milliseconds per engine tick: a pool's time in
    ``repro/pool<i>/tick`` less its time in ``repro/pool<i>/block`` (the
    wait on the device), over its ``block`` spans that start in the window
    (the ticks that launched); the mean over pools. Unlike ``tick_host_ms``
    it leaves out the checkpoint sweep, the gateway pump and waiting for
    work."""
    ticks = by_pool(select(run, r"repro/pool\d+/tick"))
    blocks = by_pool(select(run, r"repro/pool\d+/block"))
    v = []
    for pool, b in blocks.items():
        # a block begun before the window was clipped to start at lo
        n = sum(1 for _, a, _ in b if a > run.trace.lo)
        if n:
            host = seconds(ticks.get(pool, [])) - seconds(b)
            v.append(host / n * 1e3)
    return sum(v) / len(v) if v else None


def http_encode_ms(run):
    """Milliseconds in ``repro/http/encode`` (one result or preview body
    built on the HTTP event loop's thread) in the traced window, per
    answered request due in the window."""
    import measure

    enc = select(run, r"repro/http/encode")
    answered = sum(1 for r in measure.due(run) if r.get("ok"))
    if not enc or not answered:
        return None
    return seconds(enc) / answered * 1e3
