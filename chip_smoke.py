#!/usr/bin/env python3
"""Smoke run of the DDIM serving path on a TPU (a smoke run, not a benchmark).

Serves the paper's CIFAR10 U-Net (32x32x3, 35.7M parameters, float32,
random weights from --seed) through the normal entry points — the same
``build_unet_gateway`` + ``gateway_round_trip`` code that
``python -m repro.launch.serve --arch unet --gateway`` runs: GatewayCore ->
fleet -> scheduler tick -> the ``sampler_step_rows`` Pallas kernel, behind
the aiohttp HTTP/SSE front door, driven by a live client.

  python3 chip_smoke.py                 # one chip
  python3 chip_smoke.py --four-chips    # four pools, one per chip

One chip: 8 requests (JSON and SSE with previews; S in {10, 20, 50}; solver
order 1 and 2 at eta = 0; eta = 1). Each eta = 0 result is compared on the
chip with a plain float32 reference (``SamplerPlan.run(backend="jnp")``
under "highest" matmul precision); eta = 1 results must be finite, must
differ between seeds, and must differ from the same seed at eta = 0.

--four-chips runs only the multi-chip phase and what it is compared with:
the eta = 0 requests through one pool on chip 0, then through a gateway of
four pools, one per chip (state and weights on each pool's own device);
the two must agree bit for bit.

Exits non-zero without a result line unless JAX reports a TPU, every
kernel is compiled (no interpret mode), the stochastic engines use the
hardware PRNG, each pool compiled its tick once, nothing was quarantined
or absorbed, and every request got exactly one ``result`` event. The last
line of a passing run is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# The served tick runs at the TPU's default matmul precision, where float32
# convolutions and matmuls take one bf16 pass; the reference runs at
# "highest". Rounding every conv/matmul operand of this U-Net to bf16 in a
# CPU run of the same S = 10, 20, 50 trajectories moved the final samples
# by 2.7e-3 of their RMS (worst element: 3.7e-3 of their largest |x|). The
# bounds leave about 7x room; a wrong coefficient, slot mapping or solver
# order moves a sample by O(1) of its scale.
REL_RMS_TOL = 2e-2
REL_MAX_TOL = 3e-2
# an eta = 1 sample must sit at least this far (relative RMS) from the
# eta = 0 sample of the same seed, or the injected noise did nothing
MIN_NOISE_EFFECT = 0.1


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


def rescale_heads(params):
    """Give the U-Net's zero-init heads (conv2 of every res block, attention
    wo, conv_out; std 1e-10) the fan-in scale of every other layer, so that
    eps is O(1) and a comparison with the reference checks the network."""
    def fix(path, w):
        name = jax.tree_util.keystr(path)
        if name.endswith(("['conv2']", "['conv_out']")):
            fan_in = w.shape[0] * w.shape[1] * w.shape[2]
        elif name.endswith("['wo']"):
            fan_in = w.shape[0]
        else:
            return w
        return w * np.float32(fan_in ** -0.5 / 1e-10)

    return jax.tree_util.tree_map_with_path(fix, params)


def one_chip_specs(seed: int):
    """8 requests: S in {10, 20, 50}, order 1 and 2 at eta 0, and eta 1;
    JSON and SSE with previews. Two eta-0 requests share each (S, order)
    so the reference runs them as one batch."""
    def spec(S, order, eta, k, stream_every=0):
        s = {"S": S, "order": order, "eta": eta, "seed": seed + k}
        if stream_every:
            s.update(stream=True, preview_every=stream_every)
        return s
    return [spec(10, 1, 0.0, 1), spec(10, 1, 0.0, 2, stream_every=3),
            spec(20, 2, 0.0, 3), spec(20, 2, 0.0, 4, stream_every=5),
            spec(50, 1, 0.0, 5, stream_every=10),
            spec(20, 1, 1.0, 6), spec(20, 1, 1.0, 7, stream_every=5),
            spec(10, 1, 1.0, 1)]          # seed of request 0, eta 1


def four_chip_specs(seed: int):
    return [s for s in one_chip_specs(seed) if s["eta"] == 0.0] + [
        {"S": 50, "order": 2, "eta": 0.0, "seed": seed + 9},
        {"S": 10, "order": 2, "eta": 0.0, "seed": seed + 10, "stream": True,
         "preview_every": 4},
        {"S": 20, "order": 1, "eta": 0.0, "seed": seed + 11}]


def serve(ucfg, size, params, specs, *, slots, devices, pools):
    """Build the gateway (timing its warm tick compile), send the specs
    through a live client, check the serving invariants; returns the
    outcomes and the core."""
    from repro.launch.serve import build_unet_gateway, gateway_round_trip

    t0 = time.perf_counter()
    core = build_unet_gateway(ucfg, size, {"cifar10": params},
                              pools_per_model=pools, slots=slots,
                              devices=devices, stochastic=True, max_order=2)
    log(f"compile+warm gateway ({pools} pool(s), tick compiled per pool): "
        f"{time.perf_counter() - t0:.2f} s")
    outcomes, stats, bridge = asyncio.run(gateway_round_trip(core, specs))
    check(bridge.error is None, f"engine thread died: {bridge.error!r}")
    for p in core.fleet.pools:
        eng = p.engine
        check(eng.interpret is False,
              f"pool {p.pool_id} runs its kernels in interpret mode")
        check(eng.stochastic and eng.hw_prng is True,
              f"pool {p.pool_id} does not use the hardware PRNG")
        check(eng.stats()["compiled_ticks"] == 1,
              f"pool {p.pool_id} compiled its tick "
              f"{eng.stats()['compiled_ticks']} times")
        check(not eng.use_mega, f"pool {p.pool_id} picked the megakernel")
    sup = stats["resilience"]
    check(sup is not None and sup["quarantines"] == 0,
          f"supervisor quarantines: {sup and sup['quarantines']}")
    absorbed = core.health()["absorbed_pump_errors"]
    check(absorbed == 0, f"{absorbed} pump errors absorbed")
    check(stats["requests"] == len(specs),
          f"gateway accepted {stats['requests']} of {len(specs)} requests")
    for i, (s, o) in enumerate(zip(specs, outcomes)):
        check(o["status"] == 200, f"request {i}: HTTP {o['status']} "
              f"{o['result']}")
        check(o["events"].count("result") == 1 and "error" not in o["events"],
              f"request {i}: events {o['events']}")
        if s.get("stream"):
            check(o["events"][0] == "accepted" and o["previews"] > 0,
                  f"request {i}: SSE events {o['events']}")
        check(bool(np.isfinite(o["result"]["x0"]).all()),
              f"request {i}: non-finite sample")
        log(f"request {i}: S={s['S']} order={s['order']} eta={s['eta']} "
            f"{'sse' if s.get('stream') else 'json'} "
            f"previews={o['previews']} pool={o['result']['pool_id']} "
            f"latency={o['latency_s']:.3f} s")
    return outcomes, core


def rel_err(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return (float(np.sqrt(np.mean((a - b) ** 2)) / np.sqrt(np.mean(b ** 2))),
            float(np.abs(a - b).max() / np.abs(b).max()))


def compare_with_reference(ucfg, params, specs, outcomes):
    """Every eta = 0 result against a float32 jnp reference on the chip."""
    from repro.core import make_schedule
    from repro.models import unet
    from repro.sampling import SamplerPlan

    schedule = make_schedule("linear", T=1000)
    groups = {}
    for i, s in enumerate(specs):
        if s["eta"] == 0.0:
            groups.setdefault((s["S"], s["order"]), []).append(i)
    shape = outcomes[0]["result"]["x0"].shape
    for (S, order), idx in sorted(groups.items()):
        plan = SamplerPlan.build(schedule, tau=S, order=order)
        x_T = jnp.concatenate([jax.random.normal(
            jax.random.PRNGKey(specs[i]["seed"]), (1,) + shape, jnp.float32)
            for i in idx])

        def run(p, x, plan=plan):
            return plan.run(lambda xx, t: unet.forward(p, ucfg, xx, t), x,
                            backend="jnp")

        t0 = time.perf_counter()
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(jax.jit(run)(params, x_T))
        log(f"reference S={S} order={order} (compile+run, batch "
            f"{len(idx)}): {time.perf_counter() - t0:.2f} s")
        for j, i in enumerate(idx):
            rms, mx = rel_err(outcomes[i]["result"]["x0"], ref[j])
            log(f"request {i}: vs float32 reference rel_rms={rms:.3e} "
                f"rel_max={mx:.3e} (tolerance {REL_RMS_TOL:g}/"
                f"{REL_MAX_TOL:g})")
            check(rms <= REL_RMS_TOL and mx <= REL_MAX_TOL,
                  f"request {i}: rel_rms={rms:.3e} rel_max={mx:.3e} "
                  "outside the tolerance")


def check_stochastic(specs, outcomes):
    sto = [i for i, s in enumerate(specs) if s["eta"] > 0.0]
    x = {i: outcomes[i]["result"]["x0"] for i in range(len(specs))}
    a, b = [i for i in sto if specs[i]["S"] == 20][:2]
    check(not np.array_equal(x[a], x[b]),
          f"eta=1 requests {a} and {b} (different seeds) are equal")
    for i in sto:
        twins = [j for j, s in enumerate(specs)
                 if s["eta"] == 0.0 and s["seed"] == specs[i]["seed"]
                 and s["S"] == specs[i]["S"] and s["order"] == 1]
        for j in twins:
            d = rel_err(x[i], x[j])[0]
            log(f"request {i} (eta=1) vs request {j} (eta=0, same seed): "
                f"rel_rms={d:.3e}")
            check(d >= MIN_NOISE_EFFECT,
                  f"eta=1 request {i} barely differs from eta=0 request {j}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-pool, one-chip-per-pool phase "
                    "and its comparison with one pool on chip 0")
    args = ap.parse_args()
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: FAIL: the repository's src/ is not next to this "
              f"script ({e})", file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: FAIL: JAX finds no TPU (platform "
              f"{dev.platform!r}); this smoke run needs the chip",
              file=sys.stderr)
        return 1
    from repro.launch.serve import UNETS
    from repro.models import unet

    log("a smoke run, not a benchmark: timings below include compilation")
    log(f"device kind={dev.device_kind!r} count={len(devs)} "
        f"compile cache={cache_dir}")
    ucfg, size = UNETS["cifar10"]
    params = rescale_heads(unet.init_params(jax.random.PRNGKey(args.seed),
                                            ucfg))
    n_params = sum(int(np.prod(w.shape)) for w in jax.tree.leaves(params))
    log(f"CIFAR10 U-Net {size}x{size}x3, {n_params:,} parameters (float32)")
    try:
        if args.four_chips:
            check(len(devs) >= 4, f"--four-chips needs 4 chips, JAX finds "
                  f"{len(devs)}")
            specs = four_chip_specs(args.seed)
            one, _ = serve(ucfg, size, params, specs, slots=args.slots,
                           devices=devs[:1], pools=1)
            four, core = serve(ucfg, size, params, specs, slots=args.slots,
                               devices=devs[:4], pools=4)
            placed = [p.engine.devices() for p in core.fleet.pools]
            log(f"pool devices: {[sorted(str(d) for d in s) for s in placed]}")
            check(all(len(s) == 1 for s in placed)
                  and len(set().union(*placed)) == 4,
                  f"pools are not on four distinct chips: {placed}")
            used = {o["result"]["pool_id"] for o in four}
            check(used == {0, 1, 2, 3}, f"only pools {sorted(used)} served")
            for i, (a, b) in enumerate(zip(one, four)):
                check(np.array_equal(a["result"]["x0"], b["result"]["x0"]),
                      f"request {i}: four-pool result differs from the "
                      f"one-chip result (max |d| = "
                      f"{np.abs(a['result']['x0'] - b['result']['x0']).max()})")
            log(f"all {len(specs)} eta=0 results on four pools equal the "
                "one-chip results bit for bit")
        else:
            specs = one_chip_specs(args.seed)
            outcomes, _ = serve(ucfg, size, params, specs, slots=args.slots,
                                devices=devs[:1], pools=1)
            compare_with_reference(ucfg, params, specs, outcomes)
            check_stochastic(specs, outcomes)
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
