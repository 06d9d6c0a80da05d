"""Serving driver: batched AR generation over any assigned architecture
(reduced configs on CPU), or DDIM sampling from a U-Net checkpoint — in
lockstep batches or through the continuous-batching scheduler.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --batch 4 --new-tokens 16
  PYTHONPATH=src python -m repro.launch.serve --arch unet \
      --ckpt results/unet/ckpt_00000300.npz --S 20 --eta 0.0
  PYTHONPATH=src python -m repro.launch.serve --arch unet --scheduler \
      --slots 4 --s-mix 10,20,50 --n-samples 12
  PYTHONPATH=src python -m repro.launch.serve --arch unet --gateway \
      --port 8807       # async HTTP/SSE front door (docs/gateway.md)
  PYTHONPATH=src python -m repro.launch.serve --arch unet --gateway \
      --unet cifar10    # the paper's CIFAR10 U-Net (32x32, 35.7M params)

``--gateway`` serves the U-Net fleet behind the async front door
(serving/gateway): POST /v1/sample with ``"stream": true`` streams x0
previews + the terminal result over SSE, /v1/models lists the routable
models, and POST /v1/models/{name}/rollout hot-swaps staged weights
without dropping in-flight work. ``--gateway --smoke`` round-trips a
live client and exits (the tier-1 launch-path guard).

``--scheduler`` serves a mixed-step-budget request stream through
serving/scheduler: each request samples at its OWN S (--s-mix cycles),
slots refill mid-flight, and per-request latency is reported alongside
engine occupancy/throughput stats (docs/serving.md). Telemetry flags
(docs/observability.md): ``--dash`` live per-pool dashboard,
``--trace-out`` per-request JSONL spans, ``--prom-out`` Prometheus
snapshot, ``--profile`` jax.profiler host spans; every replay ends
with a p50/p95/p99 latency + miss/drop summary table.
"""
from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import make_schedule
from repro.launch.compile_cache import enable_compile_cache
from repro.models import get_api, unet
from repro.obs import (JsonlSink, Observability, render_dashboard,
                       render_summary, summarize_results)
from repro.sampling import SamplerPlan, SigmaSpec, TauSpec
from repro.serving import (ARGenerator, DiffusionSampler, GenRequest,
                           SampleRequest)
from repro.training import checkpoint


def _make_obs(args) -> tuple:
    """The CLI's telemetry handle + the JSONL trace path (or None)."""
    obs = Observability(profile=args.profile)
    trace_path = args.trace_out or None
    if trace_path:
        obs.add_sink(JsonlSink(trace_path))
    return obs, trace_path


def _drain(server, dash: bool, every: int = 25):
    """Drain a scheduler engine or fleet, optionally live-dashboarding.

    ``server`` is anything with tick()/stats() and a busy predicate
    (PoolFleet has ``.busy``; the engine is busy while queued + resident
    work remains). With ``dash`` the per-pool table re-renders every
    ``every`` ticks and once at exit.
    """
    busy = ((lambda: server.busy) if hasattr(server, "busy")
            else (lambda: len(server.queue) > 0 or server.active > 0))
    results = []
    n = 0
    while busy():
        results.extend(server.tick())
        n += 1
        if dash and n % every == 0:
            print(render_dashboard(server.stats()))
    if dash:
        print(render_dashboard(server.stats()))
    return results


def _finish_replay(results, server, obs, trace_path, args) -> None:
    """Replay exit: summary table (+ dashboard), flush trace, exporters."""
    if not args.dash:               # --dash already rendered the table
        print(render_dashboard(server.stats()))
    obs.close()                     # flush + close the JSONL sink
    print(render_summary(summarize_results(results), trace_path))
    if args.prom_out:
        render = getattr(server, "render_prometheus", None)
        text = (render() if render is not None
                else server.obs.render_prometheus())
        with open(args.prom_out, "w") as f:
            f.write(text)
        print(f"metrics    {args.prom_out}")
    if getattr(args, "flight_dir", None):
        # replay postmortems on request: dump every recorder's ring so a
        # clean run's trajectory-quality history is inspectable too
        engines = ([p.engine for p in server.pools]
                   if hasattr(server, "pools") else [server])
        for eng in engines:
            flight = getattr(eng, "flight", None)
            if flight is not None:
                path = flight.dump("replay-end")
                if path is not None:
                    print(f"flight     {path}")
    if args.out:
        done = [r for r in sorted(results, key=lambda r: r.request_id)
                if r.x0 is not None]
        np.save(args.out, np.stack([r.x0 for r in done]))
        print(f"saved -> {args.out}")


def serve_lm(args):
    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(args.seed), cfg)
    if args.ckpt:
        ref = {"params": params}
        restored, _ = checkpoint.restore(args.ckpt, ref)
        params = restored["params"]
    embeds = None
    if cfg.family in ("vlm", "audio"):
        embeds = jax.random.normal(jax.random.PRNGKey(9),
                                   (args.batch, cfg.n_ctx_embeds,
                                    cfg.d_model)) * 0.02
    gen = ARGenerator(cfg, params, batch_size=args.batch,
                      max_len=args.prompt_len + args.new_tokens +
                      (cfg.n_ctx_embeds if cfg.family == "vlm" else 0))
    rng = np.random.RandomState(args.seed)
    reqs = [GenRequest(prompt=rng.randint(0, cfg.vocab, args.prompt_len)
                       .astype(np.int32),
                       max_new_tokens=args.new_tokens,
                       temperature=args.temperature)
            for _ in range(args.batch)]
    results = gen.generate(reqs, embeds=embeds)
    for i, r in enumerate(results):
        print(f"req{i}: {r.tokens[:16]}...")
    print(f"prefill={results[0].prefill_ms:.1f}ms "
          f"decode={results[0].decode_ms:.1f}ms "
          f"throughput={results[0].tokens_per_s:.1f} tok/s")


# the two U-Net configurations serve.py can select, with their image size
UNETS = {"toy": (configs.TOY_UNET, 16), "cifar10": (configs.CIFAR10_UNET, 32)}


def build_unet_gateway(ucfg: unet.UNetConfig, image_size: int,
                       models: Dict[str, object], *, T: int = 1000,
                       pools_per_model: int = 1, slots: int = 4,
                       devices: Optional[Sequence] = None, obs=None,
                       probes=None, flight_dir: Optional[str] = None,
                       **engine_kw):
    """The U-Net gateway ``--gateway`` serves: a GatewayCore over
    ``pools_per_model`` slot pools per named weight set in ``models``.

    When the pools divide ``devices`` (default ``jax.devices()``) evenly,
    each pool gets its own mesh slice (launch.mesh.make_fleet_mesh) and
    keeps its state and weights there — one chip per pool when there are
    as many chips as pools. Otherwise every pool shares the default
    device. ``engine_kw`` reaches every ContinuousBatchingEngine.
    """
    from repro.launch.mesh import make_fleet_mesh
    from repro.serving.gateway import GatewayCore, OverloadPolicy

    n_pools = len(models) * pools_per_model
    devices = list(jax.devices() if devices is None else devices)
    meshes = (make_fleet_mesh(n_pools, devices=devices)
              if len(devices) % n_pools == 0 else None)
    return GatewayCore.build(
        make_schedule("linear", T=T),
        lambda p, x, t: unet.forward(p, ucfg, x, t),
        (image_size, image_size, 3), models=models,
        pools_per_model=pools_per_model, slots=slots,
        policy=OverloadPolicy(), obs=obs, probes=probes,
        flight_dir=flight_dir, meshes=meshes, **engine_kw)


def parse_sse(lines: List[str]) -> List[Tuple[str, Dict]]:
    """SSE text lines -> [(event name, decoded data payload)]."""
    out, name = [], None
    for line in lines:
        if line.startswith("event: "):
            name = line[len("event: "):]
        elif line.startswith("data: ") and name is not None:
            out.append((name, json.loads(line[len("data: "):])))
            name = None
    return out


async def sample_request(sess, url: str, spec: Dict) -> Dict:
    """POST one spec to the gateway at base ``url``; returns {status,
    events, terminal, result, previews, latency_s}. ``events`` lists
    every event name the client saw; ``terminal`` is "result", "error"
    or None (the stream closed without one); ``result`` is the terminal
    payload (x0 as a numpy array)."""
    t0 = time.perf_counter()
    async with sess.post(f"{url}/v1/sample", json=spec) as r:
        if spec.get("stream"):
            lines = [raw.decode("utf-8").rstrip("\n")
                     async for raw in r.content]
            events = parse_sse(lines)
        else:
            body = await r.json()
            events = [("result" if r.status == 200 else "error", body)]
        status = r.status
    latency = time.perf_counter() - t0
    terminal = [(name, data) for name, data in events
                if name in ("result", "error")]
    name, result = terminal[-1] if terminal else (None, None)
    if result is not None and "x0" in result:
        x0 = result["x0"]
        result = dict(result, x0=np.reshape(
            np.asarray(x0["data"], np.float32), x0["shape"]))
    return {"status": status, "events": [n for n, _ in events],
            "terminal": name, "result": result,
            "previews": sum(n == "preview" for n, _ in events),
            "latency_s": latency}


async def gateway_round_trip(core, specs: Sequence[Dict]):
    """Serve ``core`` on an ephemeral port, send every spec at once
    through a live aiohttp client, then stop the gateway.

    Returns (outcomes in spec order, the /v1/stats body, the bridge —
    whose ``.error`` says whether the engine thread died).
    """
    import asyncio

    import aiohttp
    from repro.serving.gateway import start_gateway, stop_gateway

    runner, bridge, port = await start_gateway(core, port=0)
    url = f"http://127.0.0.1:{port}"
    try:
        async with aiohttp.ClientSession() as sess:
            outcomes = await asyncio.gather(
                *[sample_request(sess, url, dict(s)) for s in specs])
            async with sess.get(f"{url}/v1/stats") as r:
                stats = await r.json()
    finally:
        await stop_gateway(runner, bridge)
    return list(outcomes), stats, bridge


def serve_unet_gateway(args):
    """--gateway: serve the U-Net through the async HTTP/SSE front door.

    Builds a multi-model GatewayCore (serving/gateway) over slot pools:
    with --ckpt the checkpoint's 'ema' and 'raw' weight sets become two
    routable models (same trunk, hot-swap-compatible); without one, two
    differently-seeded inits stand in ('base'/'alt'). Serves on --port
    until Ctrl-C. --smoke round-trips one JSON and one streaming SSE
    request per model through a live aiohttp client, prints a one-line
    verdict, and exits non-zero on failure — the tier-1 guard that this
    launch path can't rot.
    """
    import asyncio

    from repro.serving.gateway import start_gateway, stop_gateway

    ucfg, image_size = UNETS[args.unet]
    base = unet.init_params(jax.random.PRNGKey(args.seed), ucfg)
    if args.ckpt:
        ref = {"params": base, "ema": base}
        restored, _ = checkpoint.restore(args.ckpt, ref)
        models = {"ema": restored["ema"], "raw": restored["params"]}
    else:
        models = {"base": base,
                  "alt": unet.init_params(jax.random.PRNGKey(args.seed + 1),
                                          ucfg)}
    obs, _ = _make_obs(args)
    core = build_unet_gateway(
        ucfg, image_size, models, T=args.T,
        pools_per_model=max(1, args.pools), slots=args.slots, obs=obs,
        probes=args.probes or None, flight_dir=args.flight_dir)

    if args.smoke:
        names = sorted(models)
        # JSON round-trip on one model, SSE previews on the other
        specs = [{"model": names[0], "S": 4, "seed": args.seed},
                 {"model": names[-1], "S": 6, "seed": args.seed + 1,
                  "stream": True, "preview_every": 2}]
        outcomes, st, bridge = asyncio.run(gateway_round_trip(core, specs))
        js, sse = outcomes
        ok = (js["status"] == 200 and js["events"] == ["result"]
              and sse["events"].count("result") == 1
              and sse["previews"] > 0 and bridge.error is None)
        print(f"gateway smoke: models={names} json+sse round-trips "
              f"previews={sse['previews']} requests={st['requests']} "
              f"({'OK' if ok else 'FAIL'})")
        if not ok:
            raise SystemExit(1)
        return

    async def _serve() -> None:
        runner, bridge, port = await start_gateway(core, port=args.port)
        print(f"gateway listening on http://127.0.0.1:{port} "
              f"(models: {sorted(models)}; Ctrl-C to stop)")
        try:
            await asyncio.Event().wait()
        finally:
            await stop_gateway(runner, bridge)

    try:
        asyncio.run(_serve())
    except KeyboardInterrupt:
        pass


def serve_unet(args):
    if args.gateway:
        return serve_unet_gateway(args)
    ucfg, image_size = UNETS[args.unet]
    schedule = make_schedule("linear", T=args.T)
    params = unet.init_params(jax.random.PRNGKey(args.seed), ucfg)
    if args.ckpt:
        ref = {"params": params, "ema": params}
        restored, _ = checkpoint.restore(args.ckpt, ref)
        params = restored["ema"]            # sample from the EMA model
    eps_fn = unet.make_eps_fn(params, ucfg)
    bank = None
    if args.plan_bank:
        from repro.autoplan import PlanBank
        bank = PlanBank.load(args.plan_bank, schedule)
        print(f"plan bank: {len(bank)} rows, NFE frontier {bank.nfes}")
    svc = DiffusionSampler(schedule, eps_fn,
                           (image_size, image_size, 3),
                           batch_size=args.batch, plan_bank=bank)
    if args.scheduler:
        return serve_unet_continuous(args, svc)
    if bank is not None:
        # budget-bounded bank row: the best searched trajectory <= --S NFE
        plan = svc.bank_plan(max_nfe=args.S)
        if plan.S > args.S:
            # bank_plan falls back to the smallest row when nothing fits
            print(f"warning: no bank row fits --S {args.S}; serving the "
                  f"smallest searched row (S={plan.S})")
    else:
        plan = SamplerPlan.build(
            schedule, tau=(TauSpec.quadratic(args.S)
                           if args.tau == "quadratic"
                           else TauSpec.uniform(args.S)),
            sigma=args.eta, order=args.order)
    samples, stats = svc.serve(args.n_samples, plan, seed=args.seed)
    print(f"sampled {samples.shape} in {stats['batches']} batches; "
          f"steady={stats['steady_batch_s']:.2f}s/batch "
          f"({stats['samples_per_s']:.2f} samples/s, {plan})")
    if args.out:
        np.save(args.out, np.asarray(samples))
        print(f"saved -> {args.out}")


def serve_unet_continuous(args, svc: DiffusionSampler):
    """Mixed-PLAN request stream through the continuous-batching scheduler.

    Each request carries its own frozen SamplerPlan: the S mix cycles,
    tau spacing alternates uniform/quadratic, and (with --order > 1) every
    third request upgrades to the multistep solver — all multiplexed
    through ONE compiled tick.
    """
    s_mix = [int(s) for s in args.s_mix.split(",")]
    stochastic = args.eta > 0.0
    max_order = args.order
    clip_x0 = None
    if svc.plan_bank is not None:
        # size the engine to the whole bank frontier: refined rows may be
        # stochastic (eta schedules), multistep, or clipped, and an engine
        # only serves bank rows within its own caps
        bank = svc.plan_bank
        stochastic = stochastic or any(bank.plan(n).stochastic
                                       for n in bank.nfes)
        max_order = max([max_order] + [e.order for e in bank.entries])
        clips = [e.clip for e in bank.entries]
        uniq = set(clips)
        if len(uniq) == 1:
            clip_x0 = uniq.pop()
        elif len(uniq) > 1:
            # an engine compiles ONE clip; serve the biggest bank subset
            clip_x0 = max(uniq, key=clips.count)
            print(f"warning: bank mixes clip values "
                  f"{sorted(map(str, uniq))}; engine serves only its "
                  f"clip_x0={clip_x0} rows")
    schedule = svc.schedule
    if args.pools > 1:
        return serve_unet_fleet(args, svc, stochastic=stochastic,
                                max_order=max_order, clip_x0=clip_x0)
    obs, trace_path = _make_obs(args)
    flight = None
    if args.probes:
        from repro.obs import FlightRecorder
        flight = FlightRecorder(pool_id=0, out_dir=args.flight_dir)
    eng = svc.continuous(slots=args.slots, stochastic=stochastic,
                         max_order=max_order, clip_x0=clip_x0, obs=obs,
                         probes=args.probes or None, flight=flight)

    def plan_for(i: int) -> SamplerPlan:
        S = s_mix[i % len(s_mix)]
        tau = (TauSpec.quadratic(S) if (args.tau == "quadratic"
                                        or (args.tau == "mix" and i % 2))
               else TauSpec.uniform(S))
        order = args.order if (args.order > 1 and i % 3 == 0
                               and args.eta == 0.0) else 1
        return SamplerPlan.build(schedule, tau=tau,
                                 sigma=SigmaSpec.from_eta(args.eta),
                                 order=order)

    deadlines = [float(d) for d in args.deadlines.split(",")] \
        if args.deadlines else [None]
    import time as _time

    # warm the tick before stamping any deadline: the one-off XLA trace
    # (seconds on CPU) must neither eat the requests' headroom nor — on
    # the bank path — poison the EWMA the selection policy consults
    if svc.plan_bank is not None:
        eng.submit(SampleRequest(request_id=-1, auto_plan=True, seed=0))
        eng.run()
        eng.reset_stats()        # keep the compiled tick + measured EWMA
    elif args.deadlines:
        eng.submit(SampleRequest(request_id=-1, plan=plan_for(0), seed=0))
        eng.run()
        eng.reset_stats()
    now = _time.perf_counter()

    def deadline_for(i: int):
        d = deadlines[i % len(deadlines)]
        return None if d is None else now + d

    if svc.plan_bank is not None:
        # deadline-aware bank selection: every request lets the ENGINE
        # pick its plan at admission; the cycled relative deadlines make
        # the policy choose different NFE rows across one trace
        reqs = [SampleRequest(request_id=i, auto_plan=True,
                              deadline=deadline_for(i), seed=args.seed + i)
                for i in range(args.n_samples)]
    else:
        reqs = [SampleRequest(request_id=i, plan=plan_for(i),
                              deadline=deadline_for(i), seed=args.seed + i)
                for i in range(args.n_samples)]
    if args.dash:
        for r in reqs:
            eng.submit(r)
        results = _drain(eng, dash=True)
    else:
        results = eng.serve(reqs)
    by_id = {r.request_id: r for r in results}
    for i in sorted(by_id):
        r = by_id[i]
        sel = (f" nfe={r.nfe} headroom="
               + (f"{r.deadline_headroom_s*1e3:.0f}ms"
                  if r.deadline_headroom_s is not None else "inf")
               if r.auto_plan else "")
        print(f"req{r.request_id}: {reqs[i].plan} "
              f"wait={r.queue_wait_s*1e3:.1f}ms "
              f"service={r.service_s*1e3:.1f}ms "
              f"latency={r.latency_s*1e3:.1f}ms{sel}")
    _finish_replay(results, eng, obs, trace_path, args)


def serve_unet_fleet(args, svc: DiffusionSampler, *, stochastic,
                     max_order, clip_x0):
    """--pools N: the mixed-S stream through a slot-pool fleet.

    N continuous-batching pools behind the global EDF queue with
    least-loaded dispatch (serving/fleet). When the local device count
    divides evenly, each pool runs on its own disjoint mesh slice
    (launch.mesh.make_fleet_mesh) — force host devices with
    XLA_FLAGS=--xla_force_host_platform_device_count=8 to see sharded
    pools on CPU. Requests cycle an affinity key to exercise sticky
    routing; per-pool stats print at the end.
    """
    from repro.serving.fleet import PoolFleet

    s_mix = [int(s) for s in args.s_mix.split(",")]
    meshes = None
    n_dev = len(jax.devices())
    if n_dev % args.pools == 0:
        from repro.launch.mesh import make_fleet_mesh
        meshes = make_fleet_mesh(args.pools)
    obs, trace_path = _make_obs(args)
    fleet = PoolFleet.build(
        svc.schedule, svc.eps_fn, svc.shape, n_pools=args.pools,
        slots=args.slots, meshes=meshes, dtype=svc.dtype,
        stochastic=stochastic, max_order=max_order, clip_x0=clip_x0,
        plan_bank=svc.plan_bank, obs=obs,
        probes=args.probes or None, flight_dir=args.flight_dir)
    # warm every pool's tick before stamping latencies
    fleet.serve([SampleRequest(request_id=-1 - p, S=min(s_mix), seed=0)
                 for p in range(args.pools)], now=0.0)
    fleet.reset_stats()
    reqs = [SampleRequest(request_id=i, S=s_mix[i % len(s_mix)],
                          eta=args.eta, seed=args.seed + i,
                          affinity_key=i % (2 * args.pools))
            for i in range(args.n_samples)]
    if args.dash:
        for r in reqs:
            fleet.submit(r)
        results = _drain(fleet, dash=True)
    else:
        results = fleet.serve(reqs)
    for r in sorted(results, key=lambda r: r.request_id):
        print(f"req{r.request_id}: S={r.S} pool={r.pool_id} "
              f"wait={r.queue_wait_s*1e3:.1f}ms "
              f"latency={r.latency_s*1e3:.1f}ms")
    _finish_replay(results, fleet, obs, trace_path, args)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--n-samples", type=int, default=8)
    ap.add_argument("--unet", choices=sorted(UNETS), default="toy",
                    help="unet: the U-Net configuration to serve (toy: "
                    "the CPU-trainable demo; cifar10: the paper's 35.7M-"
                    "parameter CIFAR10 model), with its image size "
                    "(16 and 32)")
    ap.add_argument("--T", type=int, default=1000)
    ap.add_argument("--S", type=int, default=20)
    ap.add_argument("--eta", type=float, default=0.0)
    ap.add_argument("--tau", choices=["uniform", "quadratic", "mix"],
                    default="uniform",
                    help="tau spacing; 'mix' alternates per request "
                    "(--scheduler)")
    ap.add_argument("--order", type=int, default=1,
                    help="Adams-Bashforth solver order (1..4); with "
                    "--scheduler every 3rd request upgrades to it")
    ap.add_argument("--scheduler", action="store_true",
                    help="serve through the continuous-batching scheduler")
    ap.add_argument("--gateway", action="store_true",
                    help="unet: serve through the async HTTP/SSE gateway "
                    "(serving/gateway) instead of a local replay; with "
                    "--smoke, round-trip a live client and exit")
    ap.add_argument("--port", type=int, default=8807,
                    help="--gateway: TCP port to bind (--smoke always "
                    "uses an ephemeral port)")
    ap.add_argument("--slots", type=int, default=4,
                    help="resident scheduler slots (--scheduler; per pool "
                    "with --pools)")
    ap.add_argument("--pools", type=int, default=1,
                    help="with --scheduler: serve through a fleet of N "
                    "slot pools (global EDF queue + least-loaded/affinity "
                    "routing; disjoint pool meshes when the device count "
                    "divides)")
    ap.add_argument("--s-mix", default="10,20,50",
                    help="comma list of per-request step budgets to cycle")
    ap.add_argument("--plan-bank", default=None,
                    help="PlanBank JSON (repro.autoplan): lockstep serves "
                    "the best bank row <= --S; --scheduler switches every "
                    "request to deadline-aware bank selection")
    ap.add_argument("--deadlines", default="",
                    help="comma list of relative deadlines in seconds to "
                    "cycle across --scheduler requests (with --plan-bank: "
                    "drives the per-request NFE selection)")
    ap.add_argument("--dash", action="store_true",
                    help="with --scheduler: live per-pool console "
                    "dashboard re-rendered during the replay")
    ap.add_argument("--trace-out", default=None,
                    help="with --scheduler: write per-request trace spans "
                    "(structured JSONL, repro.obs) to this path")
    ap.add_argument("--prom-out", default=None,
                    help="with --scheduler: write a Prometheus text "
                    "metrics snapshot at replay exit")
    ap.add_argument("--probes", action="store_true",
                    help="enable the device-probe tier (obs/probes.py): "
                         "per-slot eps/x0/finite/defect reductions fused "
                         "into the tick, quality columns in --dash, and "
                         "per-request quality summaries")
    ap.add_argument("--flight-dir", default=None,
                    help="directory for flight-recorder JSONL postmortems "
                         "(implies an in-memory ring even when faults "
                         "never fire; requires --probes)")
    ap.add_argument("--profile", action="store_true",
                    help="jax.profiler host spans on every engine tick and "
                    "its phases (repro/pool<i>/{tick,admit,states,launch,"
                    "block,previews,retire,checkpoint}, repro/engine/... "
                    "outside a fleet), the fleet dispatch, gateway pump, "
                    "bridge commands and HTTP encode, so a device profile "
                    "puts idle time down to host work; off, a span costs "
                    "one call and one branch (docs/observability.md)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    enable_compile_cache()
    if args.gateway and args.arch != "unet":
        ap.error("--gateway serves the diffusion fleet; use --arch unet")
    if args.order > 1 and args.eta > 0.0 and not args.scheduler:
        # multistep integrates the deterministic ODE view; the scheduler
        # path downgrades per request, the lockstep path must reject
        ap.error("--order > 1 requires --eta 0 (multistep plans are "
                 "deterministic); drop --order or use --eta 0")
    if args.arch == "unet":
        serve_unet(args)
    else:
        serve_lm(args)


if __name__ == "__main__":
    main()
