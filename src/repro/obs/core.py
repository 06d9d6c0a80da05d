"""Observability — the handle a serving component hangs its telemetry on.

One :class:`Observability` bundles the three telemetry planes:

* ``registry`` — the metrics plane (obs/registry.py). ALWAYS live: the
  engine's ``stats()`` dict is a thin view over these instruments, so
  counters cost what the old plain-int counters cost.
* ``tracer`` — the span plane (obs/trace.py). Inert until a sink is
  attached (``add_sink``); every event emission is gated on
  ``tracer.sinks`` so un-traced serving pays ~nothing.
* ``profile`` — the profiler plane: when True, ``span(name)`` is a
  ``jax.profiler`` trace annotation (obs/profiling.py), so a device
  profile puts idle time down to the engine, supervisor, fleet, bridge
  and HTTP spans; when False it is one shared no-op.

Topology: each engine owns a PRIVATE registry (instruments never need a
pool label — identity attaches at render time), while a fleet shares ONE
tracer across tiers by construction: whichever tier first sees a request
creates its TraceContext from its own Observability, and every later tier
emits through that context. ``child()`` builds a pool's Observability
sharing this tracer (and profile flag) with a fresh registry.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

from .profiling import NO_SPAN, annotate
from .registry import MetricsRegistry, render_prometheus as _render
from .trace import TraceContext, Tracer


class Observability:
    """Telemetry handle: metrics registry + span tracer + profile flag."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 tracer: Optional[Tracer] = None, profile: bool = False):
        self.registry = registry if registry is not None else \
            MetricsRegistry()
        self.tracer = tracer if tracer is not None else Tracer()
        self.profile = bool(profile)

    # ----------------------------------------------------------- profiling
    def span(self, name: str):
        """A host span named ``name`` on the profiler's clock when
        ``profile`` is on; the shared no-op context when it is off."""
        return annotate(name) if self.profile else NO_SPAN

    # ------------------------------------------------------------- tracing
    @property
    def tracing(self) -> bool:
        return self.tracer.active

    def add_sink(self, sink):
        """Attach an event sink (JsonlSink / ListSink); returns it."""
        self.tracer.sinks.append(sink)
        return sink

    def trace_context(self, request_id) -> TraceContext:
        return TraceContext(self.tracer, request_id)

    def trace_submit(self, req, now: float, **fields
                     ) -> Optional[TraceContext]:
        """Front-door hook: ensure ``req`` carries a span and that exactly
        one ``submit`` event exists for it — whichever tier (fleet or
        engine) sees the request first creates the context; a later tier
        re-submitting it (fleet -> pool queue) finds ``submitted`` set and
        stays quiet."""
        if req.trace is None and self.tracing:
            req.trace = self.trace_context(req.request_id)
        ctx = req.trace
        if ctx is not None and not ctx.submitted:
            ctx.submitted = True
            ctx.emit("submit", now, **fields)
        return ctx

    def close(self) -> None:
        """Flush and close every sink that supports it."""
        for s in self.tracer.sinks:
            close = getattr(s, "close", None)
            if close is not None:
                close()

    # ------------------------------------------------------------ topology
    def child(self) -> "Observability":
        """A dependent component's handle: own metrics, shared tracer."""
        return Observability(tracer=self.tracer, profile=self.profile)

    # ----------------------------------------------------------- exporters
    def render_prometheus(self, **extra_labels) -> str:
        """Prometheus text snapshot of this registry (labels appended)."""
        return _render([(self.registry, extra_labels)])
