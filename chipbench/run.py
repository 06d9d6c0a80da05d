#!/usr/bin/env python3
"""Chip benchmark of the DDIM gateway: one cell, one run.

  python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \
      --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``); its correctness limits are in
``chipbench/checks/<workload>.json`` and every metric has a reader in
``chipbench/metrics/<metric>.py``. Nothing here names a cell.

A run: weights from ``--seed`` on the device in one jitted call; the
program's gateway (HTTP/SSE front door -> GatewayCore -> fleet ->
scheduler tick -> eps trunk + step kernel) on the cell's chips; a load
generator in a process of its own (``client.py``) serves one full warm
round, then the window. Set-up, compiles included, ends when the window
opens; a compile inside the window fails the run. After the window: the
metrics, the device's memory peak, then the program is freed and the
eta = 0 samples of a draw of the window's requests are compared with the
plain float32 reference (``reference.py``). ``--trace 1`` records a
profiler trace of the window and reports the per-layer metrics instead of
the end-to-end ones.

The eps trunk is resolved by name as well: a configuration's ``"trunk"``
key (``"unet"`` where it has none) names ``chipbench/trunks/<trunk>.py``,
which provides

  make_params(cfg, seed)   the weights, on the device in one jitted call
                           from the seed, in the program's key layout
  build_gateway(cfg, params, *, devices, pools, slots, obs, **engine_kw)
                           the program's GatewayCore for the cell: ``pools``
                           slot pools of ``slots`` on ``devices``, serving
                           ``params`` under ``cfg["name"]``; ``engine_kw``
                           reaches every engine
  sample_shape(cfg)        one request's sample shape
  eps_net(params, cfg, x, t)
                           the plain reference eps, x (N, *sample_shape),
                           t (N,) integer timesteps; imports nothing of the
                           program
  eps_flops(cfg)           operations of one eps evaluation of one sample

``reference.py`` runs the sampler over ``eps_net`` (float64 update on the
host), ``flops.slot_rows`` lays out ``sample_shape``, and
``measure.slot_step_flops`` counts ``eps_flops``. So a configuration of a
new trunk is new files only:

  chipbench/trunks/<trunk>.py               the module above
  chipbench/configs/<config>.json           its sizes, with "trunk"
  chipbench/traffic/<traffic>.json          its traffic mix
  chipbench/checks/<workload>.json          its correctness limits
  chipbench/metrics/<metric>.py             a reader per new metric
  BENCHMARK.json                            entries in configs, workloads
                                            and the metrics' workloads

(``metrics/trunk_roofline.py`` counts U-Net operations; a new trunk's
roofline is a reader of its own over ``eps_flops``.)

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` with
``--trace 1``, and ``compared`` last). Exits non-zero with no such line
when JAX finds no TPU, or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import traffic as traffic_gen  # noqa: E402


class RunFailure(Exception):
    """The run cannot produce a result (no chip, a bad cell, a dead
    server); main() exits non-zero without a result line."""


def log(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------ the cell
def load_cell(workload: str, root: Path = ROOT) -> SimpleNamespace:
    """Everything one workload names, resolved by name from the files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise RunFailure(f"unknown workload {workload!r} (known: "
                         f"{sorted(cells)})")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    return SimpleNamespace(
        name=workload, chips=int(w["chips"]),
        config=json.loads((root / cfg_entry["file"]).read_text()),
        traffic=traffic_gen.load(HERE / "traffic" / f"{w['traffic']}.json"),
        check=json.loads((HERE / "checks" / f"{workload}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if listed(m)],
        per_layer=[m for m in bench["per_layer"] if listed(m)])


def reader(name: str) -> Callable:
    """The ``read(run)`` function of chipbench/metrics/<name>.py."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def engine_options(traffic: Dict) -> Dict:
    """What the tick must support, from what the traffic sends."""
    mix = [s for s, _ in traffic_gen.joint_mix(traffic)]
    return dict(stochastic=any(float(s.get("eta", 0)) > 0 for s in mix),
                max_order=max(int(s.get("order", 1)) for s in mix),
                preview=any(int(s.get("preview_every", 0)) > 0
                            for s in mix))


# ------------------------------------------------------------ the chip
def find_chips(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise RunFailure(f"JAX finds no TPU (platform {devs[0].platform!r})"
                         "; this benchmark measures the chip only")
    if len(devs) < chips:
        raise RunFailure(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs


def use_compile_cache(root: Path) -> str:
    """JAX's persistent cache at the checkout's fixed ``.jax_cache/``, for
    the program and this harness alike; every program goes in it."""
    import jax

    path = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter(logging.Handler):
    """Counts JAX compile events between ``arm()`` and ``disarm()``, and
    keeps what JAX logs about them meanwhile (its own handlers are muted)."""

    def __init__(self):
        import jax

        super().__init__(logging.DEBUG)
        self.armed, self.count, self.names = False, 0, []
        self._muted = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        logging.getLogger("jax").addHandler(self)

    def arm(self) -> None:
        import jax

        lg = logging.getLogger("jax")
        self._muted = [(h, h.level) for h in lg.handlers if h is not self]
        for h, _ in self._muted:
            h.setLevel(logging.CRITICAL)
        jax.config.update("jax_log_compiles", True)
        self.armed = True

    def disarm(self) -> None:
        import jax

        self.armed = False
        jax.config.update("jax_log_compiles", False)
        for h, level in self._muted:
            h.setLevel(level)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.armed and event.startswith("/jax/core/compile/"):
            self.count += 1

    def emit(self, record) -> None:
        if self.armed:
            self.names.append(record.getMessage()[:200])


# ------------------------------------------------------ host spans (trace)
def install_host_spans(core) -> List[str]:
    """Profiler annotations around the calls into each layer, named
    ``chipbench/<layer>/<call>``: the gateway pump and submit, the fleet's
    dispatch, each pool's tick and checkpoint sweep, and inside the engine
    tick its admission, state build, preview delivery and read-back.

    The calls are the program's own, some of them private; a call the
    program no longer has is left unwrapped (its host time then falls
    under the enclosing span, or none) and its span's name is returned."""
    from jax.profiler import TraceAnnotation

    missing: List[str] = []

    def wrap(obj, attr, name):
        fn = getattr(obj, attr, None)
        if not callable(fn):
            missing.append(name)
            return

        def spanned(*a, **kw):
            with TraceAnnotation(name):
                return fn(*a, **kw)

        setattr(obj, attr, spanned)

    wrap(core, "pump", "chipbench/gateway/pump")
    wrap(core, "submit", "chipbench/gateway/submit")
    fleet = getattr(core, "fleet", None)
    wrap(fleet, "dispatch", "chipbench/fleet/dispatch")
    for i, p in enumerate(getattr(fleet, "pools", ())):
        i = getattr(p, "pool_id", i)
        eng = getattr(p, "engine", None)
        wrap(p, "tick", f"chipbench/pool{i}/tick")
        wrap(eng, "snapshot_slots", f"chipbench/pool{i}/checkpoint")
        wrap(eng, "_admit", f"chipbench/pool{i}/admit")
        wrap(eng, "_states", f"chipbench/pool{i}/states")
        wrap(eng, "_deliver_previews", f"chipbench/pool{i}/previews")
        wrap(eng, "_read_slot", f"chipbench/pool{i}/read_slot")
    return missing


def counters(core) -> List[Dict]:
    """Per-pool engine counters (read on the engine thread)."""
    out = []
    for p in core.fleet.pools:
        st = p.engine.stats()
        out.append({k: st[k] for k in ("ticks", "tick_wall_s", "slot_steps",
                                       "compiled_ticks", "completed")})
    return out


# ---------------------------------------------------------- the window
async def serve_window(core, plan: Dict, work: Path, trace_dir,
                       compiles: CompileCounter) -> SimpleNamespace:
    from repro.serving.gateway import start_gateway, stop_gateway

    runner, bridge, port = await start_gateway(core, port=0)
    plan = dict(plan, url=f"http://127.0.0.1:{port}")
    (work / "plan.json").write_text(json.dumps(plan))
    proc = await asyncio.create_subprocess_exec(
        sys.executable, str(HERE / "client.py"), str(work / "plan.json"),
        str(work), stdin=asyncio.subprocess.PIPE,
        stdout=asyncio.subprocess.PIPE)
    out = SimpleNamespace(bridge_error=None)
    window_span = None
    async def expect(word: str) -> List[str]:
        line = (await proc.stdout.readline()).decode().split()
        if not line or line[0] != word:
            raise RunFailure(f"load generator failed before {word} "
                             f"({' '.join(line)!r})")
        return line

    try:
        await expect("READY")
        if trace_dir is not None:        # before the ramp, not in the window
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # host spans, not every call
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        proc.stdin.write(b"GO\n")
        await proc.stdin.drain()
        await expect("OPEN")
        out.open_t = time.perf_counter()
        log(f"window open at {out.open_t - T_START:.1f} s")
        compiles.arm()
        if trace_dir is not None:
            window_span = jax.profiler.TraceAnnotation("chipbench/window")
            window_span.__enter__()
        out.c0 = await bridge.acall(counters, core)
        line = await expect("WINDOW")
        out.c1 = await bridge.acall(counters, core)
        compiles.disarm()
        if trace_dir is not None:
            window_span.__exit__(None, None, None)
        out.window = (float(line[1]), float(line[2]))
        out.compiles = compiles.count
        await expect("DONE")
        log(f"every request answered at {time.perf_counter() - T_START:.1f}"
            " s")
        if trace_dir is not None:
            # only once every request is answered: writing the trace out
            # holds this event loop, which delivers the answers
            jax.profiler.stop_trace()
            log(f"trace written at {time.perf_counter() - T_START:.1f} s")
    finally:
        if proc.returncode is None:
            try:
                await asyncio.wait_for(proc.wait(), 30)
            except asyncio.TimeoutError:
                proc.kill()
                await proc.wait()
        out.bridge_error = bridge.error
        out.health = await bridge.acall(core.health) \
            if bridge.error is None else None
        await stop_gateway(runner, bridge)
    if proc.returncode != 0:
        raise RunFailure(f"load generator exited with {proc.returncode}")
    return out


def make_plan(cell, seed: int, seconds: float, slots: int, pools: int
              ) -> Dict:
    tr = cell.traffic
    plan = {"seed": seed, "seconds": seconds, "loop": tr["loop"],
            "drain_s": float(cell.check.get("drain_s", 60.0)),
            "compare": int(cell.check["compare"]),
            "warm": traffic_gen.warm_specs(tr, slots * pools, seed)}
    if tr["loop"] == "open":
        plan["schedule"] = traffic_gen.open_schedule(tr, seconds, seed)
    else:
        plan.update(clients=int(tr["clients"]),
                    ramp_completions=int(tr.get("ramp_completions",
                                                slots * pools)),
                    specs=traffic_gen.closed_specs(tr, seed))
    return plan


# ----------------------------------------------------------- the check
def compare(cell, seed: int, records: List[Dict], sample) -> Dict:
    """The compared numbers: the worst sampled request's relative RMS and
    relative max error against the float32 reference, and every window
    request answered with a finite sample."""
    import reference

    cfg = cell.config
    picked = [(i, r) for i, r in enumerate(records) if r.get("compared")]
    due = [r for r in records if r["phase"] == "window"]
    unanswered = sum(1 for r in due if not r.get("ok"))
    nonfinite = sum(1 for r in due if r.get("ok") and not r.get("finite"))
    rms = mx = float("nan")
    if picked:
        params = reference.make_params(cfg, seed)
        reqs = [{"S": r["spec"]["S"], "tau": r["spec"].get("tau", "linear"),
                 "order": r["spec"].get("order", 1),
                 "seed": r["spec"]["seed"]} for _, r in picked]
        t0 = time.perf_counter()
        refs = reference.sample(params, cfg, reqs,
                                batch=int(cell.check.get("batch", 8)))
        log(f"reference: {len(reqs)} requests, longest S="
            f"{max(q['S'] for q in reqs)}, {time.perf_counter() - t0:.2f} s")
        errs = [reference.rel_errors(sample[f"x0_{i}"], ref)
                for (i, _), ref in zip(picked, refs)]
        rms, mx = max(e[0] for e in errs), max(e[1] for e in errs)
    lim = cell.check["limits"]
    return {
        "x0_rel_rms": {"value": rms, "limit": lim["x0_rel_rms"]},
        "x0_rel_max": {"value": mx, "limit": lim["x0_rel_max"]},
        "compared_requests": {"value": len(picked),
                              "limit": int(cell.check["compare"])},
        "unanswered": {"value": unanswered, "limit": 0},
        "nonfinite": {"value": nonfinite, "limit": 0},
    }


def verdict(checks: Dict) -> bool:
    c = checks
    return (c["x0_rel_rms"]["value"] <= c["x0_rel_rms"]["limit"]
            and c["x0_rel_max"]["value"] <= c["x0_rel_max"]["limit"]
            and c["compared_requests"]["value"]
            >= c["compared_requests"]["limit"]
            and c["unanswered"]["value"] == 0
            and c["nonfinite"]["value"] == 0
            and c["window_compiles"]["value"] == 0)


# ------------------------------------------------------------- one run
def build(cell, seed: int, trace: bool, *, require_tpu: bool = True,
          root: Path = ROOT, cache_root: Optional[Path] = None
          ) -> SimpleNamespace:
    """Chips, compile cache, weights from ``seed`` and the program's
    gateway for the cell (its tick compiled and warmed by the program)."""
    cache = use_compile_cache(cache_root or root)
    devs = find_chips(cell.chips, require_tpu)[:cell.chips]
    sys.path.insert(0, str(root / "src"))
    try:
        from repro.obs import Observability
    except ImportError as e:
        raise RunFailure(f"the program (src/repro) is not in this checkout "
                         f"({e})") from e
    import reference

    cfg = cell.config
    trunk = reference.trunk(cfg)
    slots = int(cfg["slots_per_pool"])
    pools = int(cfg["pools_per_chip"]) * cell.chips
    opts = engine_options(cell.traffic)
    log(f"device {devs[0].device_kind!r} x{len(devs)}, cache {cache}, "
        f"{cfg['name']} ({cfg.get('trunk', 'unet')} trunk): {pools} "
        f"pool(s) x {slots} slots, {opts}")
    core = trunk.build_gateway(
        cfg, trunk.make_params(cfg, seed), devices=devs, pools=pools,
        slots=slots, obs=Observability(profile=trace), **opts)
    for p in core.fleet.pools:
        if p.engine.interpret and require_tpu:
            raise RunFailure(f"pool {p.pool_id} runs its kernels in "
                             "interpret mode")
    missing = install_host_spans(core) if trace else []
    if missing:
        log(f"spans left out, the program has no such call: {missing}")
    return SimpleNamespace(core=core, devs=devs, slots=slots, pools=pools,
                           opts=opts, missing_spans=missing)


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, root: Path = ROOT,
             cache_root: Optional[Path] = None,
             after_build: Optional[Callable] = None) -> Dict:
    """One run of one cell; returns the result line's object.

    ``after_build(core)`` runs once the gateway is built (the tests use it
    to break the timed path underneath and see ``correct`` turn false);
    ``cache_root`` holds the compile cache (default: the checkout).
    """
    import jax
    import numpy as np

    b = build(cell, seed, trace, require_tpu=require_tpu, root=root,
              cache_root=cache_root)
    compiles = CompileCounter()
    core, devs, slots, pools, opts = b.core, b.devs, b.slots, b.pools, \
        b.opts
    cfg = cell.config
    if after_build is not None:
        after_build(core)
    plan = make_plan(cell, seed, seconds, slots, pools)
    with tempfile.TemporaryDirectory(prefix="chipbench-") as tmp:
        work = Path(tmp)
        tdir = work / "trace" if trace else None
        win = asyncio.run(serve_window(core, plan, work, tdir, compiles))
        if win.bridge_error is not None:
            raise RunFailure(f"engine thread died: {win.bridge_error!r}")
        records = json.loads((work / "records.json").read_text())
        sample = dict(np.load(work / "sample.npz"))
        tick_compiles = sum(b["compiled_ticks"] - a["compiled_ticks"]
                            for a, b in zip(win.c0, win.c1))
        run = SimpleNamespace(
            records=records, window=win.window, seconds=seconds,
            setup_s=win.open_t - T_START, c0=win.c0, c1=win.c1,
            config=cfg, traffic=cell.traffic, chips=cell.chips,
            slots=slots, pools=pools, engine=opts,
            peaks=peak_row(devs[0].device_kind, require_tpu),
            trace=None)
        if trace:
            import trace_reduce
            run.trace = trace_reduce.load_dir(tdir, len(devs))
            log(f"trace read at {time.perf_counter() - T_START:.1f} s")
        metrics = {}
        for m in (cell.per_layer if trace else cell.end_to_end):
            v = reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(jax.devices()),
                  "memory_peak_bytes": memory_peak(devs)}
        result = {"attempted": sum(r["phase"] == "window" for r in records),
                  "failed": sum(r["phase"] == "window" and not r.get("ok")
                                for r in records),
                  "metrics": metrics, "device": device}
        if trace:
            device["busy_s"] = run.trace.busy_s
            device["window_s"] = run.trace.window_s
            result["breakdown"] = run.trace.breakdown()
        lag = [r["send_t"] - r["sched_t"] for r in records
               if r["phase"] == "window" and "send_t" in r]
        served = [r["done_t"] - r["sched_t"] for r in records
                  if r["phase"] == "window" and r.get("ok")]
        print(json.dumps({"load_generator_lag_s": {
            "p50": float(np.percentile(lag, 50)) if lag else None,
            "p99": float(np.percentile(lag, 99)) if lag else None,
            "max": float(max(lag)) if lag else None},
            "latency_mean_s": float(np.mean(served)) if served else None,
            "window_compiles": win.compiles,
            "window_compile_log": compiles.names[:10],
            "tick_compiles": tick_compiles,
            "missing_spans": b.missing_spans}), flush=True)
    window_compiles = win.compiles + tick_compiles
    # free the program before the reference runs on the chip
    del core, win, run, b
    gc.collect()
    checks = compare(cell, seed, records, sample)
    checks["window_compiles"] = {"value": window_compiles, "limit": 0}
    result["correct"] = verdict(checks)
    for name, c in checks.items():
        log(f"compared {name} = {c['value']!r} (limit {c['limit']!r})")
    out = {"correct": result.pop("correct")}
    out.update(result)
    out["compared"] = checks
    return out


def memory_peak(devs) -> Optional[int]:
    peaks = [d.memory_stats().get("peak_bytes_in_use")
             for d in devs if d.memory_stats()]
    return max(peaks) if peaks else None


def peak_row(kind: str, required: bool) -> Optional[Dict]:
    table = json.loads((HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        if required:
            raise RunFailure(f"no peaks for device kind {kind!r} in "
                             "chipbench/peaks.json")
        return None
    return table[kind]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = load_cell(args.workload)
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except (RunFailure, FileNotFoundError, KeyError) as e:
        log(f"FAIL: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
