"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

Reads the trace with ``jax.profiler.ProfileData`` alone. The device
timelines are the ``XLA Ops`` lines of the ``/device:TPU:<n>`` planes; the
host spans are every event on the ``/host:CPU`` plane whose name starts
with ``chipbench/`` (the harness's own spans) or ``repro/`` (the program's
tick annotation). The measured window is the host span
``chipbench/window``.

- busy: the union of a device's op intervals inside the window;
- idle gaps: the window less that union, each piece of it put down to
  the innermost host span open in it (``host idle`` where none is);
- op time: the summed durations of the ops whose name matches, inside the
  window.

Every figure is the mean over the devices of the cell.
"""
from __future__ import annotations

import collections
import glob
import gzip
import re
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

WINDOW_SPAN = "chipbench/window"
HOST_PREFIXES = ("chipbench/", "repro/")
NO_SPAN = "host idle"


def union_length(starts: np.ndarray, ends: np.ndarray, lo: float,
                 hi: float) -> float:
    """Length of the union of [start, end) intervals clipped to [lo, hi)."""
    return float(sum(b - a for a, b in merged(starts, ends, lo, hi)))


def merged(starts, ends, lo, hi) -> List[Tuple[float, float]]:
    """The union of intervals clipped to [lo, hi), as sorted disjoint
    (start, end) pairs."""
    order = np.argsort(starts, kind="stable")
    out: List[List[float]] = []
    for s, e in zip(np.asarray(starts)[order], np.asarray(ends)[order]):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(a, b) for a, b in out]


class Device:
    """One device's op timeline: names and [start, end) in ns, and the
    program (``XLA Modules`` event) each op ran in."""

    def __init__(self, name: str, ops: List[Tuple[str, float, float]],
                 modules: List[Tuple[str, float, float]]):
        self.name = name
        self.op_names = [o[0] for o in ops]
        self.starts = np.asarray([o[1] for o in ops], np.float64)
        self.ends = np.asarray([o[2] for o in ops], np.float64)
        self.modules = sorted(modules, key=lambda m: m[1])
        self.op_modules = enclosing(self.modules, self.starts)


class Trace:
    def __init__(self, devices: List[Device],
                 host: List[Tuple[str, float, float]],
                 window: Tuple[float, float]):
        self.devices = devices
        self.host = host
        self.lo, self.hi = window

    # ------------------------------------------------------------ figures
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds some op ran, as the mean over the devices."""
        return float(np.mean([union_length(d.starts, d.ends, self.lo,
                                           self.hi)
                              for d in self.devices])) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, match: Callable[[str], bool],
                   module: Callable[[str], bool] = lambda m: True) -> float:
        """Seconds in ops whose name matches, inside programs whose name
        matches ``module``; the mean over the devices."""
        tot = []
        for d in self.devices:
            sel = np.fromiter((match(n) and module(m) for n, m in
                               zip(d.op_names, d.op_modules)), bool,
                              len(d.op_names))
            s = np.clip(d.starts[sel], self.lo, self.hi)
            e = np.clip(d.ends[sel], self.lo, self.hi)
            tot.append(float(np.sum(e - s)))
        return float(np.mean(tot)) / 1e9

    def op_count(self, match: Callable[[str], bool],
                 module: Callable[[str], bool] = lambda m: True) -> float:
        """Ops whose name matches that start in the window, inside programs
        whose name matches ``module``; the mean over the devices."""
        return float(np.mean([
            sum(1 for n, m, s in zip(d.op_names, d.op_modules, d.starts)
                if self.lo <= s < self.hi and match(n) and module(m))
            for d in self.devices]))

    def module_count(self, match: Callable[[str], bool]) -> float:
        """Program runs whose name matches that start in the window, mean
        over the devices."""
        return float(np.mean([
            sum(1 for n, s, _ in d.modules
                if self.lo <= s < self.hi and match(n))
            for d in self.devices]))

    def top_ops(self, k: int = 10) -> List[List]:
        """The k ops with the most device time in the window, by
        ``label``, seconds per device."""
        tot: Dict[str, float] = collections.Counter()
        for d in self.devices:
            s = np.clip(d.starts, self.lo, self.hi)
            e = np.clip(d.ends, self.lo, self.hi)
            for n, dur in zip(d.op_names, e - s):
                if dur > 0:
                    tot[label(n)] += dur
        n_dev = max(len(self.devices), 1)
        return [[n, float(v) / n_dev / 1e9] for n, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Idle time by the innermost host span open in it, seconds per
        device, the k largest. Each gap is cut at the host spans' edges
        that fall inside it, and each piece goes to the span open at its
        middle."""
        edges_all = np.unique(np.asarray(
            [x for _, a, b in self.host for x in (a, b)], np.float64))
        tot: Dict[str, float] = collections.Counter()
        for d in self.devices:
            busy = merged(d.starts, d.ends, self.lo, self.hi)
            edges = [self.lo] + [x for ab in busy for x in ab] + [self.hi]
            pieces = []
            for a, b in zip(edges[0::2], edges[1::2]):
                if b <= a:
                    continue
                i, j = np.searchsorted(edges_all, [a, b], side="right")
                cuts = [a] + edges_all[i:j].tolist() + [b]
                pieces.extend((u, v) for u, v in zip(cuts, cuts[1:])
                              if v > u)
            mids = [0.5 * (u + v) for u, v in pieces]
            for (u, v), name in zip(pieces, innermost(self.host, mids)):
                tot[name] += v - u
        n_dev = max(len(self.devices), 1)
        return [[n, float(v) / n_dev / 1e9] for n, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:k]]

    def breakdown(self) -> Dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def innermost(spans: List[Tuple[str, float, float]],
              times: List[float]) -> List[str]:
    """For each time (ascending), the name of the shortest span open at it
    (``host idle`` where none is): one sweep over spans sorted by start."""
    spans = sorted(spans, key=lambda h: h[1])
    out, active, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            active.append(spans[i])
            i += 1
        active = [h for h in active if h[2] > t]
        out.append(min(active, key=lambda h: h[2] - h[1])[0] if active
                   else NO_SPAN)
    return out


def enclosing(spans: List[Tuple[str, float, float]],
              times: np.ndarray) -> List[str]:
    """For each time, the name of the (non-overlapping, start-sorted) span
    that holds it, or "" where none does."""
    starts = np.asarray([s for _, s, _ in spans], np.float64)
    idx = np.searchsorted(starts, times, side="right") - 1
    out = []
    for i, t in zip(idx, times):
        out.append(spans[i][0] if i >= 0 and t < spans[i][2] else "")
    return out


HLO_TEXT = re.compile(r"^%?([\w.\-]+) = (.*?) ([\w\-]+)\(")


def label(name: str) -> str:
    """A short name for an op: from HLO text
    (``%fusion.43 = f32[32,32]{1,0:T(8,128)} fusion(...), ...``) its
    instance name, opcode and result type without layouts
    (``fusion.43 fusion f32[32,32]``); any other name as it is, cut to
    120 characters."""
    m = HLO_TEXT.match(name)
    if not m:
        return name[:120]
    inst, typ, op = m.groups()
    return f"{inst} {op} {re.sub(r'{[^}]*}', '', typ)}"[:120]


def load(path: str, n_devices: Optional[int] = None) -> Trace:
    """The trace in an ``.xplane.pb`` file, or in its gzip
    (``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData

    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    return reduce(ProfileData.from_serialized_xspace(data), n_devices,
                  str(path))


def reduce(pd, n_devices: Optional[int] = None, where: str = "trace"
           ) -> Trace:
    """A ``jax.profiler.ProfileData``'s device timelines, host spans and
    window."""
    devices, host, window = [], [], None
    for plane in pd.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            lines = {line.name: [(e.name, e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events]
                     for line in plane.lines}
            devices.append((int(m.group(1)), Device(
                plane.name, lines.get("XLA Ops", []),
                lines.get("XLA Modules", []))))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN and window is None:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(HOST_PREFIXES):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
    if window is None:
        raise ValueError(f"{where}: no {WINDOW_SPAN!r} span in the trace")
    devices = [d for _, d in sorted(devices, key=lambda x: x[0])]
    if n_devices is not None:
        devices = devices[:n_devices]
    if not devices:
        raise ValueError(f"{where}: no TPU device plane in the trace")
    return Trace(devices, host, window)


def load_dir(trace_dir, n_devices: Optional[int] = None) -> Trace:
    """The trace ``jax.profiler.start_trace(trace_dir)`` wrote."""
    paths = glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{trace_dir}: expected one .xplane.pb, found "
                         f"{len(paths)}")
    return load(paths[0], n_devices)
