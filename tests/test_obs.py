"""Tests for the serving telemetry layer (ISSUE 7).

Covers the acceptance criteria:
  * the metrics plane is host-side only: running the engine with
    telemetry FULLY enabled (sink attached) changes neither the sampled
    bits nor the one-compiled-tick / zero-retrace contracts;
  * trace spans: every replayed request produces a well-formed span
    (check_spans), and admit/retire event order reconstructs the
    engine's exact admission/retirement ordering;
  * registry semantics (counters/gauges/histograms, label identity,
    kind-mismatch errors) and the Prometheus text exposition (cumulative
    buckets, render-time pool labels, merged HELP/TYPE headers);
  * stats() key sets match the documented schemas exactly — engine,
    pool, and fleet (the exporter contract, obs/schema.py);
  * SampleResult latency decomposition: queue_wait_s + service_s ==
    latency_s for every result, completed or dropped;
  * fleet-wide reset_stats: pool engines and fleet aggregates zero,
    warm-up state (compiled ticks, tick EWMA) survives;
  * bank-selection outcome counters + select events, dashboard /
    summary rendering, and the profiler span helper.
"""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.autoplan import PlanBank
from repro.core import make_schedule
from repro.obs import (ENGINE_STATS_KEYS, FLEET_STATS_KEYS,
                       POOL_STATS_KEYS, Histogram, JsonlSink, ListSink,
                       NO_SPAN, MetricsRegistry, Observability, annotate,
                       check_spans, ordering, read_jsonl, render_dashboard,
                       render_prometheus, render_summary, spans,
                       summarize_results)
from repro.sampling import SamplerPlan, TauSpec
from repro.serving.fleet import PoolFleet
from repro.serving.scheduler import ContinuousBatchingEngine, SampleRequest
from repro.serving.scheduler.queue import AdmissionQueue

SCH = make_schedule("linear", T=1000)
DIM, SLOTS = 8, 2


def analytic_eps(sch, mu=2.0, s=0.5):
    def eps_fn(x, t):
        a = sch.alpha_bar[t].reshape((-1,) + (1,) * (x.ndim - 1))
        return (x - jnp.sqrt(a) * mu) * jnp.sqrt(1 - a) / (1 - a + a * s * s)
    return eps_fn


EPS = analytic_eps(SCH)


def _engine(obs=None, **kw):
    kw.setdefault("slots", SLOTS)
    return ContinuousBatchingEngine(SCH, EPS, (DIM,), obs=obs, **kw)


def _reqs(n, S=4, **kw):
    return [SampleRequest(request_id=i, S=S, eta=0.0, seed=i, **kw)
            for i in range(n)]


def _run_virtual(eng, reqs, t0=0.0):
    """Submit everything at t0 and drain on a virtual clock."""
    for r in reqs:
        eng.submit(r, now=t0)
    results, clock = [], t0
    while eng.active or len(eng.queue):
        clock += 0.001
        results.extend(eng.tick(now=clock))
    return results


# ------------------------------------------------------------ registry
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", "jobs seen")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    assert reg.counter("jobs_total") is c          # get-or-create identity
    g = reg.gauge("depth")
    g.set(7)
    assert g.value == 7.0
    h = reg.histogram("lat_s", edges=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 20.0):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(21.05)
    assert h.counts.tolist() == [1, 2, 0, 1]       # +Inf overflow bucket
    assert 0.1 <= h.percentile(50) <= 1.0
    assert h.percentile(99) == 10.0                # overflow reports last edge
    reg.reset()
    assert c.value == 0 and g.value == 0.0 and h.count == 0


def test_registry_label_identity_and_kind_mismatch():
    reg = MetricsRegistry()
    a = reg.counter("routed_total", reason="affinity")
    b = reg.counter("routed_total", reason="least-loaded")
    assert a is not b
    a.inc(3)
    assert reg.get("routed_total", reason="affinity").value == 3
    assert reg.get("routed_total", reason="least-loaded").value == 0
    assert reg.get("routed_total", reason="nope") is None
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("routed_total")
    with pytest.raises(ValueError, match="ascending"):
        Histogram("bad", edges=(1.0, 1.0))


def test_render_prometheus_merges_registries_with_labels():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("engine_ticks_total", "ticks").inc(5)
    b.counter("engine_ticks_total", "ticks").inc(7)
    h = a.histogram("tick_s", "tick wall", edges=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = render_prometheus([(a, {"pool": 0}), (b, {"pool": 1})])
    assert text.count("# TYPE engine_ticks_total counter") == 1
    assert text.count("# HELP engine_ticks_total ticks") == 1
    assert 'engine_ticks_total{pool="0"} 5' in text
    assert 'engine_ticks_total{pool="1"} 7' in text
    # cumulative buckets, +Inf == count
    assert 'tick_s_bucket{pool="0",le="0.1"} 1' in text
    assert 'tick_s_bucket{pool="0",le="1"} 2' in text
    assert 'tick_s_bucket{pool="0",le="+Inf"} 2' in text
    assert 'tick_s_count{pool="0"} 2' in text


# --------------------------------------------------------------- spans
def test_trace_context_accretes_identity_and_gates_on_sinks():
    obs = Observability()
    req = SampleRequest(request_id=9)
    # no sink: no context is created, nothing is emitted
    assert obs.trace_submit(req, 0.0) is None and req.trace is None
    sink = obs.add_sink(ListSink())
    ctx = obs.trace_submit(req, 0.0, deadline=None)
    assert ctx is req.trace and ctx.submitted
    obs.trace_submit(req, 1.0)              # second tier: no duplicate
    ctx.pool_id = 2
    ctx.nfe = 6
    ctx.emit("admit", 1.5, slot=0, wait_s=1.5, headroom_s=None)
    ctx.emit("retire", 2.0, service_s=0.5)
    kinds = [e["ev"] for e in sink.events]
    assert kinds == ["submit", "admit", "retire"]
    assert sink.events[0] == {"ev": "submit", "t": 0.0, "req": 9}
    # later events carry the identity learned since, None fields dropped
    assert sink.events[1]["pool"] == 2 and sink.events[1]["nfe"] == 6
    assert "headroom_s" not in sink.events[1]
    assert check_spans(sink.events) == []
    assert obs.tracer.emitted == 3


def test_check_spans_flags_malformed():
    def ev(req, kind, t):
        return {"ev": kind, "t": t, "req": req}
    errs = check_spans([ev(1, "submit", 0), ev(1, "retire", 1)])
    assert any("retire without admit" in e for e in errs)
    errs = check_spans([ev(2, "submit", 0), ev(2, "admit", 1),
                        ev(2, "retire", 2), ev(2, "drop", 3)])
    assert any("exactly one terminal" in e for e in errs)
    errs = check_spans([ev(3, "admit", 0), ev(3, "submit", 1),
                        ev(3, "retire", 2)])
    assert any("out-of-order" in e for e in errs)
    assert check_spans([ev(4, "reject", 0)]) == []     # back-pressure span


def test_jsonl_sink_roundtrip(tmp_path):
    path = str(tmp_path / "t.jsonl")
    obs = Observability()
    obs.add_sink(JsonlSink(path))
    for i in range(3):
        ctx = obs.trace_context(i)
        ctx.emit("submit", 0.1 * i)
        ctx.emit("admit", 0.1 * i + 0.05, slot=i)
        ctx.emit("retire", 1.0 + i)
    obs.close()
    events = read_jsonl(path)
    assert len(events) == 9 and check_spans(events) == []
    assert ordering(events, "retire") == [0, 1, 2]
    assert set(spans(events)) == {0, 1, 2}


def test_observability_child_topology():
    obs = Observability(profile=True)
    child = obs.child()
    assert child.tracer is obs.tracer          # one span plane
    assert child.registry is not obs.registry  # private metrics plane
    assert child.profile is True
    obs.add_sink(ListSink())
    assert child.tracing                       # sink visible to children


# ------------------------------------------------------ engine telemetry
def test_engine_bit_identical_and_single_trace_with_telemetry():
    """Full tracing changes no sampled bits and compiles no extra ticks."""
    plain = _engine()
    res_a = {r.request_id: r.x0 for r in _run_virtual(plain, _reqs(4))}
    obs = Observability()
    sink = obs.add_sink(ListSink())
    traced = _engine(obs=obs)
    res_b = {r.request_id: r.x0 for r in _run_virtual(traced, _reqs(4))}
    for i in res_a:
        np.testing.assert_array_equal(res_a[i], res_b[i])
    assert plain.stats()["compiled_ticks"] == 1
    assert traced.stats()["compiled_ticks"] == 1
    assert check_spans(sink.events) == []


def test_engine_spans_reconstruct_admission_and_retirement_order():
    obs = Observability()
    sink = obs.add_sink(ListSink())
    eng = _engine(obs=obs)
    # S descending: retirement order (3,2,1,0 interleaved by slots) must
    # come from the events, not from submission order
    reqs = [SampleRequest(request_id=i, S=8 - 2 * i, seed=i)
            for i in range(4)]
    results = _run_virtual(eng, reqs)
    assert check_spans(sink.events) == []
    assert ordering(sink.events, "submit") == [0, 1, 2, 3]
    # no deadlines -> EDF degrades to FIFO admission
    assert ordering(sink.events, "admit") == [0, 1, 2, 3]
    assert ordering(sink.events, "retire") == [r.request_id
                                               for r in results]
    by_req = spans(sink.events)
    for i, r in enumerate(reqs):
        kinds = [e["ev"] for e in by_req[i]]
        assert kinds[0] == "submit" and kinds[-1] == "retire"
        assert "first_tick" in kinds
        retire = by_req[i][-1]
        assert retire["nfe"] == r.S and "plan" in retire
        assert retire["service_s"] > 0


def test_engine_reject_and_expire_spans():
    obs = Observability()
    sink = obs.add_sink(ListSink())
    eng = _engine(obs=obs, max_queue=2)
    accepted = [eng.submit(r, now=0.0) for r in _reqs(3)]
    assert accepted == [True, True, False]    # depth bound: id 2 rejected
    results, clock = [], 0.0
    while eng.active or len(eng.queue):
        clock += 0.001
        results.extend(eng.tick(now=clock))
    # queue is empty again: submit one already-expired request
    expired = SampleRequest(request_id=100, S=4, deadline=clock - 0.1)
    assert eng.submit(expired, now=clock)
    results.extend(eng.tick(now=clock + 1.0))
    assert check_spans(sink.events) == []
    by_req = spans(sink.events)
    assert [e["ev"] for e in by_req[2]] == ["submit", "reject"]
    assert by_req[2][-1]["reason"] == "queue-full"
    assert [e["ev"] for e in by_req[100]] == ["submit", "expire", "drop"]
    assert by_req[100][-1]["reason"] == "expired"
    dropped = [r for r in results if r.dropped]
    assert {r.request_id for r in dropped} == {100}
    assert eng.stats()["queue_rejected"] == 1


def test_wait_plus_service_equals_latency():
    """Satellite: the SampleResult latency decomposition is exact for
    completed AND dropped requests."""
    obs = Observability()
    eng = _engine(obs=obs)
    reqs = _reqs(4) + [SampleRequest(request_id=50, S=4, deadline=0.0005)]
    results = _run_virtual(eng, reqs)
    assert len(results) == 5
    assert any(r.dropped for r in results)
    for r in results:
        assert r.queue_wait_s + r.service_s == pytest.approx(
            r.latency_s, abs=1e-12)
        if r.dropped:
            assert r.service_s == 0.0       # whole life was queue wait
        else:
            assert r.service_s > 0.0


def test_engine_reset_stats_keeps_warmup_state():
    obs = Observability()
    eng = _engine(obs=obs)
    _run_virtual(eng, _reqs(3))
    st = eng.stats()
    assert st["completed"] == 3 and st["ticks"] > 0
    ewma = st["tick_ewma_s"]
    eng.reset_stats()
    st = eng.stats()
    assert st["completed"] == 0 and st["ticks"] == 0
    assert st["slot_steps"] == 0 and st["tick_wall_s"] == 0.0
    # warm-up state survives: the compile count and the latency estimate
    # the deadline-selection policy needs
    assert st["compiled_ticks"] == 1
    assert st["tick_ewma_s"] == ewma
    # and the engine still serves without recompiling
    _run_virtual(eng, _reqs(2))
    assert eng.stats()["completed"] == 2
    assert eng.stats()["compiled_ticks"] == 1


def test_queue_requeue_preserves_arrival_counters():
    q = AdmissionQueue(obs=Observability())
    r = SampleRequest(request_id=0)
    q.submit(r, now=0.0)
    assert q.submitted == 1
    popped, missed = q.pop(1.0)
    assert popped is r and missed == []
    q.requeue(r, now=1.0)                  # a re-route, not a new arrival
    assert q.submitted == 1 and len(q) == 1
    assert r.submit_t == 0.0               # original stamp preserved


# -------------------------------------------------------- stats schemas
def test_stats_key_sets_match_documented_schema():
    """Satellite: the exporter contract — stats() keys are exactly the
    documented sets for all three tiers."""
    eng = _engine()
    _run_virtual(eng, _reqs(2))
    assert set(eng.stats()) == ENGINE_STATS_KEYS
    fleet = PoolFleet.build(SCH, EPS, (DIM,), n_pools=2, slots=SLOTS)
    fleet.serve(_reqs(3), now=0.0)
    fst = fleet.stats()
    assert set(fst) == FLEET_STATS_KEYS
    for ps in fst["pools"]:
        assert set(ps) == POOL_STATS_KEYS


# ------------------------------------------------------- fleet telemetry
def test_fleet_spans_route_through_shared_tracer():
    obs = Observability()
    sink = obs.add_sink(ListSink())
    fleet = PoolFleet.build(SCH, EPS, (DIM,), n_pools=2, slots=SLOTS,
                            obs=obs)
    results = fleet.serve(_reqs(5), now=0.0)
    assert len(results) == 5 and not any(r.dropped for r in results)
    assert check_spans(sink.events) == []
    by_req = spans(sink.events)
    assert set(by_req) == set(range(5))
    for i, evs in by_req.items():
        kinds = [e["ev"] for e in evs]
        # exactly one submit even though fleet AND pool engine both see it
        assert kinds.count("submit") == 1
        assert "route" in kinds and kinds[-1] == "retire"
        route = evs[kinds.index("route")]
        assert route["reason"] in ("affinity", "least-loaded")
        # the pool the span routed to is the pool that served it
        pool = next(r.pool_id for r in results if r.request_id == i)
        assert route["pool"] == pool


def test_fleet_reset_stats_is_fleet_wide():
    """Satellite: one call zeroes every pool engine AND the fleet-tier
    aggregates, keeping warm-up state everywhere."""
    fleet = PoolFleet.build(SCH, EPS, (DIM,), n_pools=2, slots=SLOTS)
    fleet.serve(_reqs(6), now=0.0)
    fleet.drain_pool(0)
    fleet.restore_pool(0)
    st = fleet.stats()
    assert st["completed"] == 6 and st["ticks"] > 0
    ewmas = {p.pool_id: p.tick_ewma_s for p in fleet.pools}
    fleet.reset_stats()
    st = fleet.stats()
    assert st["completed"] == 0 and st["ticks"] == 0
    assert st["dropped"] == 0 and st["drained_requests"] == 0
    assert st["slot_steps"] == 0
    for ps in st["pools"]:
        assert ps["completed"] == 0 and ps["ticks"] == 0
        assert ps["drained_requests"] == 0
        assert ps["compiled_ticks"] == 1              # warm-up survives
        assert ps["tick_ewma_s"] == ewmas[ps["pool_id"]]
    routed = fleet.obs.registry.get("fleet_routed_total",
                                    reason="least-loaded")
    assert routed is None or routed.value == 0


def test_fleet_prometheus_labels_pools_at_render_time():
    fleet = PoolFleet.build(SCH, EPS, (DIM,), n_pools=2, slots=SLOTS)
    fleet.serve(_reqs(4), now=0.0)
    text = fleet.render_prometheus()
    assert text.count("# TYPE engine_ticks_total counter") == 1
    for pid in (0, 1):
        assert f'pool="{pid}"' in text
    assert 'queue_submitted_total{tier="fleet"} 4' in text
    # engines never self-label: their own registries are pool-free
    assert 'pool=' not in fleet.pools[0].engine.obs.render_prometheus()


# ------------------------------------------------------- bank outcomes
def test_bank_selection_outcome_counters_and_select_events():
    bank = PlanBank(SCH)
    bank.add_plan(SamplerPlan.build(SCH, tau=TauSpec.explicit(
        [50, 300, 1000])), score=0.3)
    bank.add_plan(SamplerPlan.build(SCH, tau=TauSpec.explicit(
        [20, 60, 150, 400, 700, 1000])), score=0.2)
    obs = Observability()
    sink = obs.add_sink(ListSink())
    eng = _engine(obs=obs, plan_bank=bank)
    reqs = [SampleRequest(request_id=i, auto_plan=True) for i in range(3)]
    results = _run_virtual(eng, reqs)
    assert all(r.S in (3, 6) for r in results)
    st = eng.stats()
    assert st["bank_selected"] == 3
    reg = eng.obs.registry
    outcomes = {
        inst.labels[0][1]: inst.value
        for inst in reg.instruments()
        if inst.name == "engine_bank_outcome_total"}
    assert sum(outcomes.values()) == 3
    # no deadline -> infinite headroom -> the quality pick, every time
    assert outcomes == {"quality": 3}
    selects = [e for e in sink.events if e["ev"] == "select"]
    assert len(selects) == 3
    for e in selects:
        assert e["outcome"] == "quality" and e["nfe"] == 6 and "plan" in e


# ---------------------------------------------------- render-only layers
def test_dashboard_and_summary_render():
    eng = _engine()
    results = _run_virtual(eng, _reqs(3))
    dash = render_dashboard(eng.stats())
    assert eng.tick_variant in dash and " 3 " in dash
    fleet = PoolFleet.build(SCH, EPS, (DIM,), n_pools=2, slots=SLOTS)
    fresults = fleet.serve(_reqs(4), now=0.0)
    fdash = render_dashboard(fleet.stats())
    assert fdash.count("\n") >= 4 and "mega=" in fdash      # totals row
    summary = summarize_results(results + fresults)
    assert summary["requests"] == 7 and summary["completed"] == 7
    assert summary["dropped"] == 0 and summary["miss_rate"] == 0.0
    assert summary["p50_latency_s"] <= summary["p99_latency_s"]
    text = render_summary(summary, trace_path="/tmp/x.jsonl")
    assert "p95 latency" in text and "/tmp/x.jsonl" in text
    # all-dropped summary renders without latency figures
    empty = summarize_results([])
    assert empty["p50_latency_s"] is None
    assert "n/a" in render_summary(empty)


def test_summary_tolerates_untimed_and_dropped_only_results():
    """Postmortem hardening: warm-up results with no submit stamp drop
    out of the percentile population (not the completion counts), a
    drop-only replay summarizes with n/a percentiles, and a partial
    summary dict still renders."""
    import types
    timed = types.SimpleNamespace(
        dropped=False, submit_t=0.0, latency_s=0.5, deadline_missed=False,
        queue_wait_s=0.1, service_s=0.4, quality=None)
    untimed = types.SimpleNamespace(
        dropped=False, submit_t=None, latency_s=None,
        deadline_missed=False, queue_wait_s=None, service_s=None,
        quality={"defect_mean": 0.25})
    s = summarize_results([timed, untimed])
    assert s["completed"] == 2                    # both count as done...
    assert s["p50_latency_s"] == pytest.approx(0.5)   # ...one is timed
    assert s["defect_mean"] == pytest.approx(0.25)
    dropped = types.SimpleNamespace(
        dropped=True, submit_t=0.0, latency_s=None, deadline_missed=True,
        queue_wait_s=None, service_s=None, quality=None)
    d = summarize_results([dropped])
    assert d["completed"] == 0 and d["dropped"] == 1
    assert d["p99_latency_s"] is None and d["defect_mean"] is None
    assert "n/a" in render_summary(d)
    assert "=== replay summary ===" in render_summary({})  # partial dict


def test_dashboard_renders_probe_quality_columns():
    eng = _engine(probes=True)
    _run_virtual(eng, _reqs(3))
    dash = render_dashboard(eng.stats())
    assert "defect" in dash and "fin" in dash
    assert "1.00" in dash            # probe_finite_min on healthy traffic
    # a probe-less stats dict renders the same table with n/a cells
    assert "n/a" in render_dashboard(_engine().stats())


def test_span_helper_and_annotate():
    from jax.profiler import TraceAnnotation
    off = Observability()
    # profile off: every name gets the one shared no-op context
    assert off.span("repro/a") is off.span("repro/b") is NO_SPAN
    with off.span("repro/a"), off.span("repro/a"):   # re-entrant
        pass
    on = Observability(profile=True)
    assert isinstance(on.span("repro/a"), TraceAnnotation)
    assert isinstance(on.child().span("repro/a"), TraceAnnotation)
    with on.span("repro/test/region"):   # no capture running: a no-op
        pass
    with annotate("repro/test/region"):
        pass
