"""Fault-tolerant serving: deterministic fault plans, pool quarantine +
circuit breakers, trajectory checkpoint/migrate (bit-identical eta=0
resume), the gateway's NaN guard / cancellation / Retry-After surface,
and bridge survivability under pump faults.

Everything runs on a virtual clock (pump(now=t)) so breaker backoff and
EDF ordering are exact, not timing-dependent.
"""
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_schedule
from repro.obs import ListSink, check_spans
from repro.serving.errors import RejectCode, RequestError
from repro.serving.fleet import (PoolFleet, PoolState, SlotPool,
                                 make_trunk_params, pick_pool, trunk_apply)
from repro.serving.gateway import EngineBridge, GatewayCore
from repro.serving.resilience import (BreakerPolicy, BreakerState,
                                      CheckpointStore, Fault, FaultInjector,
                                      FaultPlan, InjectedFault,
                                      PoolSupervisor)
from repro.serving.scheduler import ContinuousBatchingEngine, SampleRequest
from repro.serving.scheduler.request import SlotCheckpoint

SCH = make_schedule("linear", T=100)
DIM, HIDDEN = 8, 32
PARAMS = make_trunk_params(SCH, DIM, HIDDEN, seed=0)
DT = 0.01


def _engine(slots=2, **kw):
    return ContinuousBatchingEngine(SCH, trunk_apply, (DIM,), slots,
                                    eps_params=PARAMS, **kw)


def _core(pools=1, injector=None, breaker=None, supervise=True, **kw):
    return GatewayCore.build(
        SCH, trunk_apply, (DIM,), models={"m": PARAMS},
        pools_per_model=pools, slots=2, supervise=supervise,
        injector=injector, breaker=breaker, **kw)


def _run(core, t=0.0, max_pumps=600):
    """Pump the core on a virtual clock until idle; returns final t."""
    n = 0
    while core.busy and n < max_pumps:
        core.pump(now=t)
        t += DT
        n += 1
    assert not core.busy, f"core still busy after {n} pumps"
    return t


def _submit(core, events, t=0.0, **spec):
    spec.setdefault("model", "m")
    return core.submit(spec, events.append, now=t)


# ----------------------------------------------------------- fault plans
def test_fault_kind_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault(kind="meteor-strike")


def test_fault_plan_rejects_colliding_cells():
    with pytest.raises(ValueError, match="same \\(pool, tick\\)"):
        FaultPlan([Fault(kind="tick-error", pool=1, tick=3),
                   Fault(kind="nan-eps", pool=1, tick=3)])


def test_fault_plan_seeded_is_deterministic():
    mk = lambda s: FaultPlan.seeded(s, n_pools=3, horizon_ticks=40,
                                    n_disconnects=2, n_requests=10)
    assert mk(7).faults == mk(7).faults
    assert mk(7).faults != mk(8).faults
    kinds = [f.kind for f in mk(7)]
    assert kinds.count("tick-error") == 2 and kinds.count("nan-eps") == 1
    assert all(f.tick >= 1 for f in mk(7) if f.kind != "sse-disconnect")
    with pytest.raises(ValueError, match="n_requests"):
        FaultPlan.seeded(0, n_pools=2, horizon_ticks=10, n_disconnects=1)


def test_injector_fires_only_scheduled_cells():
    inj = FaultInjector(FaultPlan([
        Fault(kind="tick-error", pool=0, tick=2),
        Fault(kind="tick-latency", pool=1, tick=1, delay_s=0.5)]))
    inj.before_tick(0, 0)
    inj.before_tick(1, 2)                       # wrong pool: no raise
    assert inj.after_tick(1, 1, engine=None) == 0.5
    with pytest.raises(InjectedFault) as ei:
        inj.before_tick(0, 2)
    assert ei.value.fault.pool == 0
    assert inj.fired() == 2 and inj.fired("tick-latency") == 1


def test_injector_disconnect_consumed_once():
    inj = FaultInjector(FaultPlan([
        Fault(kind="sse-disconnect", request_index=3)]))
    assert not inj.should_disconnect(0)
    assert inj.should_disconnect(3)
    assert not inj.should_disconnect(3)         # consumed
    assert inj.fired("sse-disconnect") == 1


def test_checkpoint_store_latest_wins_and_forgets():
    st = CheckpointStore()
    st.put(SlotCheckpoint(request_id=1, k=2, x_rows=None, hist_rows=None))
    st.put(SlotCheckpoint(request_id=1, k=5, x_rows=None, hist_rows=None))
    assert st.latest(1).k == 5 and len(st) == 1 and st.taken == 2
    st.forget(1)
    assert st.latest(1) is None and len(st) == 0


# ------------------------------------------- engine: checkpoint / resume
def test_snapshot_resume_is_bit_identical():
    # reference: uninterrupted eta=0 order-1 run
    ref = _engine().serve([SampleRequest(request_id=0, S=8, seed=4)])[0]
    # interrupted run: 3 ticks, snapshot, evict, resume on ANOTHER engine
    a = _engine()
    a.submit(SampleRequest(request_id=0, S=8, seed=4), now=0.0)
    for i in range(3):
        a.tick(now=i * DT)
    b, _ = a.resident_requests()[0]
    ck = a.snapshot_slot(b, now=3 * DT)
    assert ck.k == 3
    [req] = a.evict_residents()
    assert a.active == 0
    req.resume = ck
    out = _engine().serve([req])[0]
    assert np.array_equal(np.asarray(out.x0), np.asarray(ref.x0))
    assert out.S == 8


def _same_bits(a, b):
    if a is None or b is None:
        return a is None and b is None
    return a.dtype == b.dtype and a.shape == b.shape and \
        a.tobytes() == b.tobytes()


@pytest.mark.parametrize("kw,order", [
    (dict(), 1), (dict(max_order=2), 2), (dict(dtype=jnp.bfloat16), 1)],
    ids=["f32-order1", "f32-order2", "bf16"])
def test_snapshot_slots_one_fetch_matches_per_slot_reads(kw, order):
    eng = _engine(slots=4, **kw)
    fetches = eng.obs.registry.get("engine_checkpoint_fetches_total")
    assert eng.snapshot_slots() == [] and fetches.value == 0
    rid, t = 0, 0.0
    for n_resident in (1, 3, 4):
        while eng.active < n_resident:          # staggered admissions:
            eng.submit(SampleRequest(request_id=rid, S=20, seed=rid,
                                     solver_order=order), now=t)
            eng.tick(now=t)                     # residents differ in k
            rid, t = rid + 1, t + DT
        before = fetches.value
        cks = eng.snapshot_slots(now=t)
        assert fetches.value == before + 1
        resident = eng.resident_requests()
        assert [ck.request_id for ck in cks] == \
            [r.request_id for _, r in resident]
        for (b, _), ck in zip(resident, cks):
            one = eng.snapshot_slot(b, now=t)
            assert (ck.k, ck.previews, ck.pool_id, ck.taken_t) == \
                (one.k, one.previews, one.pool_id, one.taken_t)
            assert ck.k == eng.resident_step(b)
            assert _same_bits(ck.x_rows, one.x_rows)
            assert _same_bits(ck.hist_rows, one.hist_rows)
            assert (ck.hist_rows is None) == (eng.max_order == 1)
            for arr in (ck.x_rows, ck.hist_rows):   # owns one slot only
                if arr is not None:
                    assert arr.base is None or arr.base.size == arr.size
        assert fetches.value == before + 1      # per-slot reads: uncounted
    assert len({ck.k for ck in cks}) > 1


def test_resume_rejects_out_of_range_k():
    eng = _engine()
    bad = SampleRequest(request_id=1, S=4, seed=0)
    bad.resume = SlotCheckpoint(request_id=1, k=4, x_rows=None,
                                hist_rows=None)
    eng.submit(bad, now=0.0)
    with pytest.raises(ValueError, match="outside"):
        eng.tick(now=0.0)


def test_engine_cancel_frees_slot_and_counts():
    eng = _engine()
    eng.submit(SampleRequest(request_id=5, S=10, seed=0), now=0.0)
    eng.tick(now=0.0)
    assert eng.active == 1
    assert eng.cancel(5, now=DT)
    assert eng.active == 0 and eng.capacity == eng.slots
    assert not eng.cancel(5, now=DT)            # idempotent
    assert eng.stats()["cancelled"] == 1
    # the freed slot is reusable: a fresh request completes normally
    res = eng.serve([SampleRequest(request_id=6, S=4, seed=1)])
    assert len(res) == 1 and not res[0].dropped


# ------------------------------------------- supervisor: quarantine path
def test_quarantine_contains_fault_and_work_completes_elsewhere():
    inj = FaultInjector(FaultPlan([
        Fault(kind="tick-error", pool=0, tick=3)]))
    core = _core(pools=2, injector=inj, checkpoint_every=1,
                 breaker=BreakerPolicy(backoff_pumps=2, probe_ticks=1))
    sink = core.obs.add_sink(ListSink())
    events = []
    for i in range(4):
        _submit(core, events, S=8, seed=i)
    _run(core)
    # every accepted request got exactly ONE terminal event, all results
    assert [e["event"] for e in events] == ["result"] * 4
    assert check_spans(sink.events) == []
    sup = core.supervisor.stats()
    assert sup["quarantines"] == 1 and inj.fired("tick-error") == 1
    assert sup["migrated"] + sup["restarted"] >= 1   # residents moved
    # migrated requests finished on the surviving pool
    assert any(e["pool_id"] == 1 for e in events)


def test_quarantine_after_batched_sweep_migrates_bit_identically():
    # the quarantine scenario above with a sweep every 2 ticks: pool 0's
    # sweep fetches both residents at once, its tick 3 faults, and the
    # requests resumed from that sweep's snapshots finish elsewhere with
    # the uninterrupted run's exact bits (eta=0, order 1)
    ref = {}
    for i in range(4):
        ref_core, ev = _core(pools=1, supervise=False), []
        _submit(ref_core, ev, S=8, seed=i)
        _run(ref_core)
        ref[i] = np.asarray(ev[0]["x0"])
    inj = FaultInjector(FaultPlan([
        Fault(kind="tick-error", pool=0, tick=3)]))
    core = _core(pools=2, injector=inj, checkpoint_every=2,
                 breaker=BreakerPolicy(backoff_pumps=2, probe_ticks=1))
    events = []
    seed_of = {_submit(core, events, S=8, seed=i): i for i in range(4)}
    _run(core)
    assert [e["event"] for e in events] == ["result"] * 4
    sup = core.supervisor.stats()
    assert sup["quarantines"] == 1 and sup["migrated"] == 2
    fetches = sum(p.engine.obs.registry.get(
        "engine_checkpoint_fetches_total").value for p in core.fleet.pools)
    assert 1 <= fetches < sup["checkpoints_taken"]   # sweeps of 2 slots
    for e in events:
        assert np.array_equal(np.asarray(e["x0"]),
                              ref[seed_of[e["request_id"]]])


def test_supervised_happy_path_matches_unsupervised():
    outs = []
    for supervise in (False, True):
        core = _core(pools=1, supervise=supervise)
        events = []
        _submit(core, events, S=6, seed=9)
        _run(core)
        outs.append(np.asarray(events[0]["x0"]))
        assert (core.stats()["resilience"] is None) == (not supervise)
    assert np.array_equal(outs[0], outs[1])


def test_breaker_backoff_probe_and_close():
    inj = FaultInjector(FaultPlan([
        Fault(kind="tick-error", pool=0, tick=0)]))
    core = _core(pools=1, injector=inj,
                 breaker=BreakerPolicy(backoff_pumps=2, probe_ticks=1))
    sup = core.supervisor
    events = []
    _submit(core, events, S=4, seed=0)
    core.pump(now=0.0)                    # first busy tick -> quarantine
    br = sup.breaker(0)
    assert br.state is BreakerState.OPEN and br.trips == 1
    assert core.fleet.pools[0].state is PoolState.QUARANTINED
    assert core.fleet.pools[0].health < 1.0
    # while OPEN, the only pool is out: new submits refuse with 503
    with pytest.raises(RequestError) as ei:
        _submit(core, [], S=4, seed=1, t=DT)
    assert ei.value.code is RejectCode.MODEL_UNAVAILABLE
    assert ei.value.status == 503 and ei.value.retry_after_s >= 1
    # backoff elapses -> HALF_OPEN probe restores the pool, work resumes
    _run(core, t=DT)
    assert [e["event"] for e in events] == ["result"]
    assert br.state is BreakerState.CLOSED
    assert sup.stats()["probes"] == 1
    assert core.fleet.pools[0].state is PoolState.ACTIVE


def test_backoff_grows_exponentially_and_caps():
    sup = PoolSupervisor(
        _fleet(1), policy=BreakerPolicy(backoff_pumps=4, backoff_factor=2.0,
                                        max_backoff_pumps=24))
    assert [sup._backoff(n) for n in (1, 2, 3, 4)] == [4, 8, 16, 24]


def _fleet(n_pools):
    return PoolFleet([SlotPool(i, _engine()) for i in range(n_pools)])


def test_router_health_weights_choice():
    fleet = _fleet(2)
    fleet.pools[0].health = 0.1
    pool = pick_pool(fleet.pools, SampleRequest(request_id=0, S=4))
    assert pool.pool_id == 1                    # unhealthy pool avoided
    # affinity ignores a pool below the health floor
    for key in range(8):
        req = SampleRequest(request_id=1, S=4, affinity_key=key)
        assert pick_pool(fleet.pools, req).pool_id == 1


# -------------------------------------------------- gateway: guard rails
def test_nan_guard_turns_garbage_into_typed_5xx():
    inj = FaultInjector(FaultPlan([Fault(kind="nan-eps", pool=0, tick=1)]))
    core = _core(pools=1, injector=inj)
    events = []
    _submit(core, events, S=6, seed=0)
    _run(core)
    assert len(events) == 1
    ev = events[0]
    assert ev["event"] == "error"
    assert ev["code"] == "nonfinite-sample" and ev["status"] == 500
    assert core.stats()["nonfinite"] == 1
    assert inj.fired("nan-eps") == 1


def test_cancel_mid_trajectory_frees_slot_and_spans():
    core = _core(pools=1)
    sink = core.obs.add_sink(ListSink())
    events = []
    rid = _submit(core, events, S=12, seed=0, preview_every=1)
    t = 0.0
    for _ in range(4):
        core.pump(now=t)
        t += DT
    assert core.fleet.active == 1
    assert core.cancel(rid, now=t)
    assert core.fleet.active == 0               # slot freed immediately
    _run(core, t=t)
    # the client is gone: previews before the cancel, no terminal after
    assert all(e["event"] == "preview" for e in events)
    assert core.stats()["cancelled"] == 1
    kinds = [e["ev"] for e in sink.events if e["req"] == rid]
    assert kinds[-1] == "cancel"
    assert check_spans(sink.events) == []       # cancel closes the span
    assert not core.cancel(rid, now=t)          # idempotent


def test_queue_full_refusal_carries_retry_after():
    core = _core(pools=1, max_queue=2)
    for i in range(2):
        _submit(core, [], S=4, seed=i)
    with pytest.raises(RequestError) as ei:
        _submit(core, [], S=4, seed=9)
    e = ei.value
    assert e.code is RejectCode.QUEUE_FULL and e.status == 429
    assert isinstance(e.retry_after_s, int) and e.retry_after_s >= 1
    assert e.payload()["retry_after_s"] == e.retry_after_s


def test_shed_events_carry_retry_after():
    from repro.serving.gateway import OverloadPolicy
    core = _core(pools=1, policy=OverloadPolicy(shed_depth=1, margin=0.0))
    events = []
    for i in range(4):                          # deadline-free pile-up
        _submit(core, events, S=4, seed=i)
    _run(core)
    errs = [e for e in events if e["event"] == "error"]
    assert errs and all(e["code"].startswith("shed-") for e in errs)
    assert all(e["retry_after_s"] >= 1 for e in errs)


def test_healthz_degraded_detail_then_recovers():
    inj = FaultInjector(FaultPlan([
        Fault(kind="tick-error", pool=0, tick=1)]))
    core = _core(pools=2, injector=inj,
                 breaker=BreakerPolicy(backoff_pumps=1, probe_ticks=1))
    events = []
    for i in range(3):
        _submit(core, events, S=6, seed=i)
    t = 0.0
    while core.supervisor.stats()["quarantines"] == 0 and t < 1.0:
        core.pump(now=t)
        t += DT
    h = core.health()
    assert h["status"] == "degraded"
    assert h["quarantined"][0]["pool"] == 0
    assert "InjectedFault" in h["quarantined"][0]["last_error"]
    assert {p["state"] for p in h["pools"]} >= {"quarantined"}
    _run(core, t=t)
    assert core.health()["status"] == "ok"
    assert len([e for e in events if e["event"] == "result"]) == 3


# ------------------------------- satellite: requeue under drain/hot-swap
def test_requeue_under_drain_during_hot_swap():
    core = _core(pools=1)
    sink = core.obs.add_sink(ListSink())
    events = []
    # distinct deadlines make the EDF order observable
    rids = [_submit(core, events, S=4, seed=i, deadline_s=100.0 + i)
            for i in range(4)]
    q = core.fleet.queue
    assert q.submitted == 4
    stamps = {r.request_id: r.submit_t for r in q.pending_requests()}
    core.fleet.dispatch(0.0)   # 2 route to the pool's LOCAL queue
    assert len(q) == 2 and len(core.fleet.pools[0].engine.queue) == 2
    core.hot_swap("m", PARAMS, now=0.0)   # drain-for-swap requeues them
    # stamps preserved, arrival counter NOT double-incremented
    assert q.submitted == 4
    pend = q.pending_requests()
    assert [r.request_id for r in pend] == rids       # EDF order intact
    assert {r.request_id: r.submit_t for r in pend} == stamps
    _run(core)
    assert [e["event"] for e in events] == ["result"] * 4
    assert check_spans(sink.events) == []   # requeue resets the segment
    assert core.swapping is None and core.stats()["swaps"] == 1


def test_rollout_completes_when_draining_pool_quarantines():
    # quarantine strikes the pool MID-DRAIN: the rollout must still
    # finish (install on the evicted engine) without restoring the pool
    inj = FaultInjector(FaultPlan([
        Fault(kind="tick-error", pool=0, tick=2)]))
    core = _core(pools=2, injector=inj, checkpoint_every=1,
                 breaker=BreakerPolicy(backoff_pumps=4, probe_ticks=1))
    events = []
    for i in range(3):
        _submit(core, events, S=8, seed=i)
    core.pump(now=0.0)                          # residents land
    core.hot_swap("m", PARAMS, now=DT)          # pool 0 starts draining
    _run(core, t=2 * DT)
    assert core.swapping is None and core.stats()["swaps"] == 1
    assert [e["event"] for e in events] == ["result"] * 3
    assert core.supervisor.stats()["quarantines"] >= 1


# ------------------------------------------------ bridge survivability
def _await(pred, timeout=10.0):
    t0 = time.monotonic()
    while not pred():
        if time.monotonic() - t0 > timeout:
            raise AssertionError("condition not reached")
        time.sleep(0.01)


def test_bridge_survives_pump_fault_when_supervised():
    core = _core(pools=1)
    boom = {"armed": True}
    orig = core.pump

    def pump(now=None):
        if boom.pop("armed", False):
            raise RuntimeError("transient gateway-tier fault")
        return orig(now)

    core.pump = pump
    bridge = EngineBridge(core, idle_s=0.005).start()
    try:
        done = threading.Event()
        results = []

        def on_event(ev):
            results.append(ev)
            done.set()

        bridge.call(core.submit, {"model": "m", "S": 4},
                    on_event).result(10)
        _await(done.is_set)
        assert bridge.error is None             # absorbed, not poisoned
        assert results[0]["event"] == "result"
        assert core.health()["absorbed_pump_errors"] == 1
    finally:
        bridge.stop()


def test_bridge_poisons_without_supervisor():
    core = _core(pools=1, supervise=False)
    core.pump = lambda now=None: (_ for _ in ()).throw(
        RuntimeError("fatal"))
    bridge = EngineBridge(core, idle_s=0.005).start()
    try:
        bridge.call(core.submit, {"model": "m", "S": 4},
                    lambda ev: None).result(10)
        _await(lambda: bridge.error is not None)
        with pytest.raises(RuntimeError, match="engine thread failed"):
            bridge.call(core.stats)
    finally:
        bridge.stop()


# -------------------------------------------------- span segment checks
def _ev(req, kind, t, **kw):
    return dict({"ev": kind, "t": t, "req": req}, **kw)


def test_check_spans_requeue_resets_segment():
    ok = [_ev(1, "submit", 0), _ev(1, "route", 1), _ev(1, "requeue", 2),
          _ev(1, "route", 3), _ev(1, "admit", 4), _ev(1, "resume", 4),
          _ev(1, "first_tick", 5), _ev(1, "retire", 6)]
    assert check_spans(ok) == []
    # out-of-order WITHIN a segment is still flagged
    bad = [_ev(2, "submit", 0), _ev(2, "admit", 1), _ev(2, "route", 2),
           _ev(2, "retire", 3)]
    assert any("out-of-order" in e for e in check_spans(bad))


def test_check_spans_flags_resume_without_requeue():
    evs = [_ev(3, "submit", 0), _ev(3, "route", 1), _ev(3, "admit", 2),
           _ev(3, "resume", 2), _ev(3, "retire", 3)]
    assert any("resume without" in e for e in check_spans(evs))


def test_check_spans_cancel_is_terminal():
    evs = [_ev(4, "submit", 0), _ev(4, "cancel", 1)]
    assert check_spans(evs) == []
    dup = evs + [_ev(4, "retire", 2)]
    assert any("terminal" in e for e in check_spans(dup))
