"""Serving telemetry: metrics registry, trace spans, probes, flight data.

Host-side by contract — with ONE carve-out. No module in this package
issues a JAX op on the tick path (attaching telemetry cannot add traces
or perturb the one-compiled-tick / bit-identity guarantees; tests/
test_obs.py holds the line, benchmarks/obs_overhead.py bounds the
wall-clock cost at 2%) EXCEPT ``probes.py``: the opt-in device-probe
tier, which compiles a second, separately-gated tick variant
(<= 2 traces per engine, <= 5% overhead — see docs/observability.md and
scripts/lint_serving.py, which forbids JAX anywhere else in obs/).

Entry point is :class:`Observability`: pass one to
``ContinuousBatchingEngine`` / ``PoolFleet.build`` and the engine's
``stats()`` becomes a view over real instruments, ``add_sink`` turns on
per-request JSONL spans, and ``profile=True`` turns ``span(name)`` into
``jax.profiler`` annotations (obs/profiling.py names them). For
in-flight numerics, build the engine with ``probes=`` (a
:class:`ProbeSpec`) and optionally attach a :class:`FlightRecorder` for
postmortem dumps.
"""
from .core import Observability
from .dashboard import render_dashboard, render_summary, summarize_results
from .flight import (FlightRecorder, attribute_nonfinite,
                     detect_weight_corruption, read_flight)
from .probes import ProbeSpec
from .profiling import NO_SPAN, annotate
from .registry import (Counter, Gauge, Histogram, LATENCY_BUCKETS_S,
                       MetricsRegistry, SLACK_BUCKETS_S, render_prometheus)
from .schema import (ENGINE_STATS_KEYS, FLEET_STATS_KEYS, POOL_STATS_KEYS,
                     PROBE_COLUMNS)
from .trace import (EVENT_KINDS, JsonlSink, ListSink, TraceContext, Tracer,
                    check_spans, ordering, plan_digest, read_jsonl, spans)

__all__ = [
    "Observability",
    # metrics plane
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "LATENCY_BUCKETS_S", "SLACK_BUCKETS_S", "render_prometheus",
    # span plane
    "Tracer", "TraceContext", "JsonlSink", "ListSink", "EVENT_KINDS",
    "plan_digest", "read_jsonl", "spans", "check_spans", "ordering",
    # profiling plane
    "annotate", "NO_SPAN",
    # device-probe + flight-recorder tier
    "ProbeSpec", "PROBE_COLUMNS", "FlightRecorder",
    "attribute_nonfinite", "detect_weight_corruption", "read_flight",
    # exporter contracts
    "ENGINE_STATS_KEYS", "POOL_STATS_KEYS", "FLEET_STATS_KEYS",
    "render_dashboard", "summarize_results", "render_summary",
]
