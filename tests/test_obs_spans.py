"""The program's host spans on the profiler's clock (obs/profiling.py).

A small two-pool gateway serves JSON and SSE requests over live HTTP under
``jax.profiler.trace``; the trace is read back through ``ProfileData``
from its ``/host:CPU`` plane, as the chip benchmark's reduction reads it:

  * every span of the table in docs/observability.md is there;
  * the tick's phases nest inside their pool's ``tick`` span, and pool
    ticks inside the gateway pump;
  * both pools of the fleet are named;
  * with ``profile`` off, serving builds no ``TraceAnnotation`` at all.
"""
import asyncio
import glob
from collections import defaultdict
from pathlib import Path

import jax
import pytest

from repro.core import make_schedule
from repro.obs import Observability
from repro.obs import profiling
from repro.serving.fleet import make_trunk_params, trunk_apply
from repro.serving.gateway import GatewayCore, start_gateway, stop_gateway
from repro.serving.scheduler import ContinuousBatchingEngine

SCH = make_schedule("linear", T=100)
DIM = 8
PARAMS = make_trunk_params(SCH, DIM, 32, seed=0)
PHASES = ("admit", "states", "launch", "block", "previews", "retire")
TOP = ("repro/fleet/dispatch", "repro/gateway/pump",
       "repro/gateway/deliver", "repro/bridge/commands",
       "repro/http/encode")


def _core(profile: bool) -> GatewayCore:
    return GatewayCore.build(SCH, trunk_apply, (DIM,),
                             models={"base": PARAMS}, pools_per_model=2,
                             slots=2, checkpoint_every=2,
                             obs=Observability(profile=profile))


def _serve(core: GatewayCore) -> None:
    """Four JSON requests and two SSE streams with previews, over HTTP."""
    import aiohttp

    async def scenario():
        runner, bridge, port = await start_gateway(core, port=0)
        url = f"http://127.0.0.1:{port}/v1/sample"
        try:
            async with aiohttp.ClientSession() as sess:
                async def json_one(seed):
                    async with sess.post(url, json={"S": 6,
                                                    "seed": seed}) as r:
                        assert r.status == 200
                        assert (await r.json())["event"] == "result"

                async def sse_one(seed):
                    async with sess.post(url, json={
                            "S": 6, "seed": seed, "stream": True,
                            "preview_every": 2}) as r:
                        body = (await r.read()).decode()
                    assert "event: preview" in body
                    assert "event: result" in body

                await asyncio.gather(*[json_one(s) for s in range(4)],
                                     sse_one(10), sse_one(11))
        finally:
            await stop_gateway(runner, bridge)

    asyncio.run(scenario())


@pytest.fixture(scope="module")
def host_spans(tmp_path_factory):
    """(name, start_ns, end_ns, thread) of every ``repro/`` host event,
    traced as the chip benchmark traces: host spans, no Python tracer."""
    from jax.profiler import ProfileData

    core = _core(profile=True)
    out = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        _serve(core)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(Path(out) / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = ProfileData.from_file(path)
    spans = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for thread, line in enumerate(plane.lines):   # a line a thread
            for e in line.events:
                if e.name.startswith("repro/"):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, thread))
    return spans


def _by_name(spans):
    out = defaultdict(list)
    for s in spans:
        out[s[0]].append(s)
    return out


def _inside(inner, outers) -> bool:
    """``inner`` lies within one of ``outers`` on the same thread."""
    _, a, b, th = inner
    return any(th == o[3] and o[1] <= a and b <= o[2] for o in outers)


@pytest.mark.parametrize("name", [f"repro/pool0/{p}" for p in
                                  ("tick", "checkpoint") + PHASES] + list(TOP))
def test_every_span_is_on_the_host_plane(host_spans, name):
    assert _by_name(host_spans)[name], sorted(_by_name(host_spans))


@pytest.mark.parametrize("phase", PHASES)
def test_tick_phases_nest_inside_their_pool_tick(host_spans, phase):
    by = _by_name(host_spans)
    for pool in (0, 1):
        ticks = by[f"repro/pool{pool}/tick"]
        for s in by[f"repro/pool{pool}/{phase}"]:
            assert _inside(s, ticks), s


def test_a_two_pool_fleet_names_both_pools(host_spans):
    by = _by_name(host_spans)
    for pool in (0, 1):
        assert by[f"repro/pool{pool}/tick"]
        assert by[f"repro/pool{pool}/block"]
    # the gateway's thread ticks both pools inside its pumps
    pumps = by["repro/gateway/pump"]
    for pool in (0, 1):
        assert all(_inside(s, pumps) for s in by[f"repro/pool{pool}/tick"])
    assert not any(n.startswith("repro/engine/") for n in by)


def test_http_encode_runs_on_the_event_loop_thread(host_spans):
    by = _by_name(host_spans)
    engine_threads = {s[3] for s in by["repro/gateway/pump"]}
    encode_threads = {s[3] for s in by["repro/http/encode"]}
    assert encode_threads and not encode_threads & engine_threads
    # 4 JSON bodies + 2 SSE results + their previews (S 6, every 2: 2 each)
    assert len(by["repro/http/encode"]) == 4 + 2 + 2 * 2


def test_profile_off_builds_no_annotation(monkeypatch):
    made = []

    class Counting(profiling.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(profiling, "TraceAnnotation", Counting)
    _serve(_core(profile=False))
    assert made == []
    _serve(_core(profile=True))        # the same counter sees them on
    assert "repro/gateway/pump" in made and "repro/http/encode" in made


def test_span_names_follow_the_engine_identity():
    eng = ContinuousBatchingEngine(SCH, lambda x, t: x * 0.0, (DIM,),
                                   slots=2)
    assert eng._span["tick"] == "repro/engine/tick"
    eng.pool_id = 3               # a fleet's SlotPool renumbers it
    assert eng._span["block"] == "repro/pool3/block"
    assert set(eng._span) == set(profiling.ENGINE_SPANS)
