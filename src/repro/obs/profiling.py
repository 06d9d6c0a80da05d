"""Profiling hooks: host spans on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation``: it lands on the trace's
``/host:CPU`` plane, on the same clock as the device planes, so a
``jax.profiler.trace(...)`` capture can put each stretch of device idle
time down to what the host was doing in it. Serving components take their
spans through ``Observability.span``, which hands out :data:`NO_SPAN`
when profiling is off: then a span costs one method call and one branch,
and no annotation is built.

The names, built once per component (the trace reduction reads names
only, so a pool's identity is in the name; an engine outside a fleet uses
``repro/engine`` in place of ``repro/pool<i>``):

* ``repro/pool<i>/tick`` the whole engine tick, holding ``admit``,
  ``states`` (step coefficients gathered and copied to the device),
  ``launch`` (the jitted tick call, up to its return), ``block``
  (``block_until_ready`` on the new state), ``previews`` and ``retire``
  (one per finished slot's read-back);
* ``repro/pool<i>/checkpoint`` a supervisor checkpoint sweep of the pool;
* ``repro/fleet/dispatch`` the global EDF queue handed to pools;
* ``repro/gateway/pump`` one serving round and ``repro/gateway/deliver``
  its terminal events;
* ``repro/bridge/commands`` submits, cancels and stats calls run on the
  engine thread;
* ``repro/http/encode`` one result or preview body built on the event
  loop's thread.
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

#: the one shared no-op context every disabled span returns
NO_SPAN = contextlib.nullcontext()

#: the spans of one engine, under ``repro/pool<i>`` or ``repro/engine``
ENGINE_SPANS = ("tick", "admit", "states", "launch", "block", "previews",
                "retire", "checkpoint")
FLEET_DISPATCH = "repro/fleet/dispatch"
GATEWAY_PUMP = "repro/gateway/pump"
GATEWAY_DELIVER = "repro/gateway/deliver"
BRIDGE_COMMANDS = "repro/bridge/commands"
HTTP_ENCODE = "repro/http/encode"


def annotate(name: str):
    """Context manager marking a host-side region in profiler traces."""
    return TraceAnnotation(name)


def engine_span_names(pool_id) -> dict:
    """The engine's span names, keyed by :data:`ENGINE_SPANS`."""
    scope = "repro/engine" if pool_id is None else f"repro/pool{pool_id}"
    return {k: f"{scope}/{k}" for k in ENGINE_SPANS}
