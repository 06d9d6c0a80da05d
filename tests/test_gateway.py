"""Async serving gateway (ISSUE 8): typed admission, overload shedding
(lowest-deadline-headroom-first, BEFORE the tick), bounded-queue
back-pressure, drop spans, multi-model routing, rolling weight hot-swap
under live traffic (old weights for in-flight work, zero retrace), the
engine bridge, and — when aiohttp is present — the HTTP/SSE transport
end to end.
"""
import asyncio
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import make_schedule
from repro.obs import ListSink, Observability
from repro.obs.schema import GATEWAY_STATS_KEYS
from repro.serving.errors import RejectCode, RequestError
from repro.serving.fleet import make_trunk_params, trunk_apply
from repro.serving.gateway import (EngineBridge, GatewayCore,
                                   ModelRegistry, OverloadPolicy,
                                   parse_spec)
from repro.sampling import SamplerPlan
from repro.serving.scheduler.request import SampleRequest

SCH = make_schedule("linear", T=100)
DIM, HIDDEN = 8, 32
PARAMS_A = make_trunk_params(SCH, DIM, HIDDEN, seed=0)
PARAMS_B = make_trunk_params(SCH, DIM, HIDDEN, seed=1)
PARAMS_C = make_trunk_params(SCH, DIM, HIDDEN, seed=2)


def _gateway(models=None, **kw):
    models = models if models is not None else {"base": PARAMS_A}
    kw.setdefault("slots", 2)
    return GatewayCore.build(SCH, trunk_apply, (DIM,), models=models, **kw)


def _serve_one(core, spec, now=None):
    """Submit one spec and pump (virtually) until its terminal event."""
    events = []
    core.submit(spec, events.append, now=now)
    for _ in range(500):
        if events and events[-1]["event"] in ("result", "error"):
            break
        core.pump(now)
    return events


# ------------------------------------------------------------- parse_spec
def test_parse_spec_rejects_unknown_field():
    with pytest.raises(RequestError) as ei:
        parse_spec({"S": 4, "bogus": 1}, 0, now=0.0)
    assert ei.value.code is RejectCode.BAD_REQUEST
    assert ei.value.status == 400
    assert "bogus" in str(ei.value)


def test_parse_spec_rejects_wrong_type_and_non_dict():
    with pytest.raises(RequestError, match="field 'S'"):
        parse_spec({"S": "ten"}, 0, now=0.0)
    with pytest.raises(RequestError, match="JSON object"):
        parse_spec([1, 2], 0, now=0.0)


def test_parse_spec_rejects_bad_tau_and_negative_preview():
    with pytest.raises(RequestError, match="tau"):
        parse_spec({"tau": "cubic"}, 0, now=0.0)
    with pytest.raises(RequestError, match="preview_every"):
        parse_spec({"preview_every": -1}, 0, now=0.0)


def test_parse_spec_deadline_relative_to_now():
    req = parse_spec({"S": 4, "deadline_s": 2.5}, 7, now=10.0)
    assert req.request_id == 7 and req.deadline == 12.5
    assert parse_spec({"S": 4}, 0, now=10.0).deadline is None


def test_parse_spec_order_field():
    assert parse_spec({"S": 4, "order": 2}, 0, now=0.0).order == 2
    assert parse_spec({"S": 4}, 0, now=0.0).order == 1
    with pytest.raises(RequestError, match="order must be"):
        parse_spec({"order": 0}, 0, now=0.0)
    with pytest.raises(RequestError, match="field 'order'"):
        parse_spec({"order": 2.0}, 0, now=0.0)


def test_order_field_serves_the_multistep_plan():
    """``order`` in a request body selects the Adams-Bashforth plan of
    that order (eta = 0); its result matches the plan's jnp reference."""
    core = _gateway(max_order=2)
    events = _serve_one(core, {"S": 6, "order": 2, "seed": 3})
    assert events[-1]["event"] == "result"
    x_T = jax.random.normal(jax.random.PRNGKey(3), (1, DIM))
    want = SamplerPlan.build(SCH, tau=6, order=2).run(
        lambda x, t: trunk_apply(PARAMS_A, x, t), x_T, backend="jnp")
    # the engine tick and the jnp scan are two compiled programs that
    # round the update differently: a few float32 ulp apart
    np.testing.assert_allclose(events[-1]["x0"], np.asarray(want)[0],
                               rtol=1e-5, atol=1e-6)
    order1 = _serve_one(core, {"S": 6, "seed": 3})[-1]["x0"]
    assert np.abs(order1 - events[-1]["x0"]).max() > 1e-3


def test_order_beyond_pools_or_with_noise_is_typed_400():
    core = _gateway(max_order=2, stochastic=True)
    with pytest.raises(RequestError) as ei:
        core.submit({"S": 6, "order": 3}, lambda e: None)
    assert ei.value.code is RejectCode.ORDER_UNSUPPORTED
    assert ei.value.status == 400
    with pytest.raises(RequestError, match="deterministic") as ei:
        core.submit({"S": 6, "order": 2, "eta": 1.0}, lambda e: None)
    assert ei.value.code is RejectCode.BAD_REQUEST
    with pytest.raises(RequestError, match="auto_plan") as ei:
        core.submit({"S": 6, "order": 2, "auto_plan": True}, lambda e: None)
    assert ei.value.code is RejectCode.AUTO_PLAN_CONFLICT
    assert ei.value.status == 400
    assert core.stats()["rejected"] == 3


# --------------------------------------------------------- OverloadPolicy
def _pending(deadlines, S=10, auto_plan=False, t0=0.0):
    reqs = []
    for i, d in enumerate(deadlines):
        r = SampleRequest(request_id=i, S=S, seed=i, deadline=d,
                          auto_plan=auto_plan)
        r.submit_t = t0 + i
        reqs.append(r)
    return reqs


def test_policy_depth_shed_evicts_lowest_headroom_first():
    pol = OverloadPolicy(shed_depth=2, margin=0.0)
    reqs = _pending([10.0, 1.0, 20.0, 5.0])
    plan = pol.plan_shed(reqs, now=0.0, tick_s=None)
    assert [r.deadline for r, _ in plan] == [1.0, 5.0]   # ascending headroom
    assert all(c is RejectCode.SHED_OVERLOAD for _, c in plan)


def test_policy_feasibility_shed_exempts_auto_plan():
    pol = OverloadPolicy(margin=1.0)
    doomed = _pending([5.0], S=50)          # 50 steps * 1s/tick >> 5s left
    assert [c for _, c in pol.plan_shed(doomed, 0.0, tick_s=1.0)] == \
        [RejectCode.SHED_INFEASIBLE]
    exempt = _pending([5.0], S=50, auto_plan=True)  # bank degrades NFE
    assert pol.plan_shed(exempt, 0.0, tick_s=1.0) == []
    # no tick measurement yet -> no feasibility guess either
    assert pol.plan_shed(doomed, 0.0, tick_s=None) == []


def test_policy_deadline_free_shed_last_newest_first():
    pol = OverloadPolicy(shed_depth=1, margin=0.0)
    free = _pending([None, None, None])     # submit_t = 0, 1, 2
    plan = pol.plan_shed(free, now=5.0, tick_s=None)
    assert [r.request_id for r, _ in plan] == [2, 1]  # newest arrivals shed


# ------------------------------------------------------ core: happy paths
def test_gateway_result_event_round_trip():
    core = _gateway()
    events = _serve_one(core, {"model": "base", "S": 4, "seed": 3})
    assert [e["event"] for e in events] == ["result"]
    ev = events[0]
    assert np.asarray(ev["x0"]).shape == (DIM,)
    assert ev["S"] == 4 and not ev["deadline_missed"]
    st = core.stats()
    assert st["requests"] == 1 and st["results_streamed"] == 1
    assert st["streams"] == 0               # terminal closed the stream


def test_gateway_previews_stream_before_result():
    core = _gateway()
    events = _serve_one(core, {"S": 6, "seed": 0, "preview_every": 2})
    kinds = [e["event"] for e in events]
    assert kinds[-1] == "result" and kinds.count("preview") >= 2
    steps = [e["step"] for e in events if e["event"] == "preview"]
    assert steps == sorted(steps)
    assert core.stats()["previews_streamed"] == kinds.count("preview")
    assert events[-1]["previews"] == kinds.count("preview")


def test_gateway_stats_schema_frozen():
    assert set(_gateway().stats()) == GATEWAY_STATS_KEYS


# --------------------------------------------------- core: typed refusals
def test_unknown_model_is_typed_404():
    core = _gateway()
    with pytest.raises(RequestError) as ei:
        core.submit({"model": "nope", "S": 4}, lambda e: None)
    assert ei.value.code is RejectCode.UNKNOWN_MODEL
    assert ei.value.status == 404
    assert core.stats()["rejected"] == 1


def test_parse_failures_count_as_rejects():
    core = _gateway()
    with pytest.raises(RequestError):
        core.submit({"bogus": 1}, lambda e: None)
    assert core.stats()["rejected"] == 1
    counts = {dict(i.labels).get("code"): int(i.value)
              for i in core.obs.registry.instruments()
              if i.name == "gateway_rejected_total"}
    assert counts == {RejectCode.BAD_REQUEST.value: 1}


def test_bounded_queue_rejects_queue_full():
    core = _gateway(slots=1, max_queue=2)
    sink = []
    core.submit({"S": 30, "seed": 0}, sink.append, now=0.0)
    core.pump(now=0.0)                      # occupy the single slot
    core.submit({"S": 4, "seed": 1}, sink.append, now=0.0)
    core.submit({"S": 4, "seed": 2}, sink.append, now=0.0)
    with pytest.raises(RequestError) as ei:
        core.submit({"S": 4, "seed": 3}, sink.append, now=0.0)
    assert ei.value.code is RejectCode.QUEUE_FULL
    assert ei.value.status == 429
    st = core.stats()
    assert st["rejected"] == 1 and st["queue_depth"] == 2


# ------------------------------------------------------- core: overload
def test_shed_before_tick_lowest_headroom_first():
    """The depth sweep runs BEFORE dispatch: victims get typed 503
    terminals + audit records (lowest headroom first) and never reach a
    pool; survivors keep their queue slots."""
    obs = Observability()
    sink = obs.add_sink(ListSink())
    core = _gateway(slots=1, obs=obs,
                    policy=OverloadPolicy(shed_depth=2, margin=0.0))
    by_rid = {}

    def cb_for(rid_box):
        return lambda ev: by_rid.setdefault(rid_box[0], []).append(ev)

    box = [None]
    box[0] = core.submit({"S": 40, "seed": 0}, lambda ev: None, now=0.0)
    core.pump(now=0.0)                      # resident fills the only slot
    for d in (10.0, 1.0, 20.0, 5.0):
        b = [None]
        b[0] = core.submit({"S": 4, "deadline_s": d, "seed": 1},
                           cb_for(b), now=0.0)
        by_rid[b[0]] = []
    core.pump(now=0.0)                      # sweep: depth 4 > shed_depth 2
    shed_evs = [evs[0] for evs in by_rid.values() if evs]
    assert len(shed_evs) == 2
    assert all(e["event"] == "error"
               and e["code"] == RejectCode.SHED_OVERLOAD.value
               and e["status"] == 503 for e in shed_evs)
    # audit log: lowest headroom evicted first, every victim at or below
    # the lowest headroom among the kept requests
    assert [rec["headroom_s"] for rec in core.shed_log] == [1.0, 5.0]
    assert all(rec["kept_min_headroom_s"] == 10.0
               for rec in core.shed_log)
    # survivors still queued (the slot is occupied), victims gone
    assert core.stats()["queue_depth"] == 2
    assert core.stats()["shed"] == 2
    # every shed closed its span with a terminal drop(reason="shed")
    drops = [e for e in sink.events if e["ev"] == "drop"]
    assert [e["reason"] for e in drops] == ["shed", "shed"]
    assert sorted(e["code"] for e in drops) == ["shed-overload"] * 2


def test_expired_requests_get_504():
    # margin=0 disables the feasibility sweep so the deadline genuinely
    # passes IN the queue and the dispatch pop drops it as expired
    core = _gateway(slots=1, policy=OverloadPolicy(margin=0.0))
    events = []
    core.submit({"S": 4, "deadline_s": 0.5, "seed": 1}, events.append,
                now=0.0)
    core.pump(now=1.0)                      # deadline passed in the queue
    assert events and events[0]["event"] == "error"
    assert events[0]["code"] == RejectCode.EXPIRED.value
    assert events[0]["status"] == 504
    assert core.stats()["expired"] == 1


# ------------------------------------------------------- core: hot swap
def _result_x0(core, spec):
    events = _serve_one(core, spec)
    assert events[-1]["event"] == "result", events[-1]
    return np.asarray(events[-1]["x0"])


def test_hot_swap_serves_inflight_on_old_weights_without_retrace():
    """A rollout started mid-request: the resident finishes on the OLD
    weights, work submitted during the walk runs on the NEW ones, the
    version bumps, and the pool's compiled tick count stays 1."""
    spec = {"model": "base", "S": 6, "seed": 7}
    want_old = _result_x0(_gateway({"base": PARAMS_A}), spec)
    want_new = _result_x0(_gateway({"base": PARAMS_C}), spec)
    assert not np.allclose(want_old, want_new)

    core = _gateway({"base": PARAMS_A, "alt": PARAMS_B})
    inflight, during = [], []
    core.submit(spec, inflight.append)
    core.pump()                             # resident on the base pool
    assert core.hot_swap("base", PARAMS_C) == 1
    assert core.swapping == "base"
    core.submit(spec, during.append)        # lands after the restore
    for _ in range(500):
        if core.swapping is None and during \
                and during[-1]["event"] in ("result", "error"):
            break
        core.pump()
    assert core.swapping is None
    np.testing.assert_allclose(np.asarray(inflight[-1]["x0"]), want_old)
    np.testing.assert_allclose(np.asarray(during[-1]["x0"]), want_new)
    assert core.registry.version("base") == 2
    base_pool = next(p for p in core.fleet.pools if p.model == "base")
    assert base_pool.weight_swaps == 1
    assert base_pool.engine.stats()["compiled_ticks"] == 1  # zero retrace
    assert core.stats()["swaps"] == 1


def test_hot_swap_requires_staged_checkpoint_and_known_model():
    core = _gateway({"base": PARAMS_A})
    with pytest.raises(ValueError, match="no staged"):
        core.hot_swap("base")
    with pytest.raises(RequestError) as ei:
        core.hot_swap("ghost")
    assert ei.value.code is RejectCode.UNKNOWN_MODEL


def test_registry_stage_rejects_shape_mismatch():
    reg = ModelRegistry()
    reg.register("m", PARAMS_A)
    bad = make_trunk_params(SCH, DIM, HIDDEN * 2, seed=3)
    with pytest.raises(ValueError, match="rollout must preserve"):
        reg.stage("m", bad)
    reg.stage("m", PARAMS_C)
    assert reg.describe()["m"] == {"version": 1, "staged": True}
    assert reg.promote("m") == 2


# ------------------------------------------------------------- routing
def test_multi_model_requests_route_to_their_pools():
    core = _gateway({"base": PARAMS_A, "alt": PARAMS_B})
    pool_of = {p.model: p.pool_id for p in core.fleet.pools}
    for model in ("base", "alt", "base"):
        events = _serve_one(core, {"model": model, "S": 3, "seed": 0})
        assert events[-1]["pool_id"] == pool_of[model]


# -------------------------------------------------------------- bridge
def test_bridge_runs_commands_and_traffic_on_engine_thread():
    core = _gateway()
    bridge = EngineBridge(core, idle_s=0.01).start()
    try:
        assert bridge.call(lambda: 41 + 1).result(timeout=5) == 42
        done = threading.Event()
        events = []

        def on_event(ev):
            events.append(ev)
            if ev["event"] in ("result", "error"):
                done.set()

        bridge.call(core.submit, {"S": 4, "seed": 0},
                    on_event).result(timeout=5)
        assert done.wait(timeout=30)
        assert events[-1]["event"] == "result"
        with pytest.raises(RequestError):
            bridge.call(core.submit, {"model": "ghost", "S": 4},
                        lambda e: None).result(timeout=5)
    finally:
        bridge.stop()


def test_bridge_pump_failure_poisons_future_calls():
    class Exploding:
        busy = True

        def pump(self):
            raise RuntimeError("tick went sideways")

    bridge = EngineBridge(Exploding(), idle_s=0.01).start()
    try:
        deadline = time.monotonic() + 5
        while bridge.error is None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert isinstance(bridge.error, RuntimeError)
        with pytest.raises(RuntimeError, match="engine thread failed"):
            bridge.call(lambda: 1)
    finally:
        bridge.stop()


# ------------------------------------------------------------ HTTP / SSE
def test_http_sse_end_to_end_with_rollout():
    """One live server: JSON + SSE sampling across both models, typed
    HTTP errors, metrics/stats/health, and a rollout driven entirely
    over the wire."""
    import aiohttp
    from repro.serving.gateway import start_gateway, stop_gateway

    core = _gateway({"base": PARAMS_A, "alt": PARAMS_B})

    async def scenario():
        runner, bridge, port = await start_gateway(core, port=0)
        url = f"http://127.0.0.1:{port}"
        try:
            async with aiohttp.ClientSession() as sess:
                async with sess.get(f"{url}/v1/models") as r:
                    models = await r.json()
                assert sorted(models) == ["alt", "base"]
                # plain JSON round-trip
                async with sess.post(f"{url}/v1/sample", json={
                        "model": "base", "S": 4, "seed": 0}) as r:
                    assert r.status == 200
                    body = await r.json()
                assert body["event"] == "result"
                assert body["x0"]["shape"] == [DIM]
                # SSE: accepted -> preview* -> result
                kinds = []
                async with sess.post(f"{url}/v1/sample", json={
                        "model": "alt", "S": 6, "seed": 1,
                        "stream": True, "preview_every": 2}) as r:
                    assert r.headers["Content-Type"].startswith(
                        "text/event-stream")
                    async for raw in r.content:
                        line = raw.decode().strip()
                        if line.startswith("event: "):
                            kinds.append(line.split(": ", 1)[1])
                assert kinds[0] == "accepted" and kinds[-1] == "result"
                assert kinds.count("preview") >= 2
                # typed refusals map to HTTP statuses
                async with sess.post(f"{url}/v1/sample", json={
                        "model": "ghost", "S": 4}) as r:
                    assert r.status == 404
                    assert (await r.json())["error"] == "unknown-model"
                async with sess.post(f"{url}/v1/sample", json={
                        "S": "ten"}) as r:
                    assert r.status == 400
                # rollout over the wire: 409 bare, then staged + rolled
                async with sess.post(
                        f"{url}/v1/models/base/rollout") as r:
                    assert r.status == 409
                await bridge.acall(core.registry.stage, "base", PARAMS_C)
                async with sess.post(
                        f"{url}/v1/models/base/rollout") as r:
                    assert r.status == 200
                    assert (await r.json())["status"] == "rolling"
                for _ in range(200):
                    async with sess.get(f"{url}/v1/models") as r:
                        models = await r.json()
                    if models["base"]["version"] == 2:
                        break
                    await asyncio.sleep(0.02)
                assert models["base"]["version"] == 2
                # the swapped model still serves; no retrace anywhere
                async with sess.post(f"{url}/v1/sample", json={
                        "model": "base", "S": 3, "seed": 2}) as r:
                    assert r.status == 200
                async with sess.get(f"{url}/v1/stats") as r:
                    st = await r.json()
                assert set(st) == set(GATEWAY_STATS_KEYS)
                assert all(p["compiled_ticks"] == 1
                           for p in st["fleet"]["pools"])
                async with sess.get(f"{url}/metrics") as r:
                    text = await r.text()
                assert "gateway_requests_total" in text
                assert 'tier="gateway"' in text
                async with sess.get(f"{url}/healthz") as r:
                    assert (await r.json())["status"] == "ok"
        finally:
            await stop_gateway(runner, bridge)

    asyncio.run(scenario())
