"""Benchmark harness — one module per paper table/figure (+ beyond-paper).

  PYTHONPATH=src python -m benchmarks.run                  # full paper suite
  PYTHONPATH=src python -m benchmarks.run --budget quick
  PYTHONPATH=src python -m benchmarks.run --suite sampler    # hot-path bench
  PYTHONPATH=src python -m benchmarks.run --suite scheduler  # serving bench
  PYTHONPATH=src python -m benchmarks.run --suite sampler --check    # CI gate
  PYTHONPATH=src python -m benchmarks.run --suite scheduler --check  # CI gate
  PYTHONPATH=src python -m benchmarks.run --suite all --record  # re-baseline

``--check`` runs the suite's benchmark WITHOUT rewriting its committed
BENCH_*.json and exits non-zero on regression:

  sampler    any growth of the modeled HBM-bytes-per-step, or a >25%
             regression of a kernel path's wall-clock relative to the same
             run's 'jnp' reference (machine speed cancels in the ratio);
  scheduler  a >25% drop of the continuous/lockstep samples-per-second
             ratio, or >25% growth of continuous net evals per completed
             sample, against a replay of the committed trace;
  autoplan   the committed BENCH_autoplan.json no longer claiming that
             the searched plans beat uniform/quadratic tau at equal NFE,
             or a fresh smoke-scale search violating the DP-optimality /
             bank-roundtrip / plan-cache-reuse invariants;
  fleet      a >25% drop of any aggregate samples-per-second scaling
             ratio (2 pools / 1 pool, 4 pools / 1 pool) against a replay
             of the committed mixed-S Poisson trace (run under
             XLA_FLAGS=--xla_force_host_platform_device_count=8 for the
             sharded pool meshes);
  obs        telemetry (full JSONL span tracing vs the registry-only
             default) costing more than 2% of a steady tick's wall-clock
             on a replay of the committed trace, device probes costing
             more than 5% of total tick wall, any of the three engines
             (plain / traced / probed) recompiling its tick, the traced
             replay's JSONL failing the span schema / retirement-order
             reconstruction, or the probed replay's flight-recorder
             smoke failing to round-trip its frozen dump schema;
  gateway    the committed BENCH_gateway.json no longer demonstrating
             the acceptance bar (overload goodput >= 0.90x the
             no-overload ceiling, sheds present, zero shed-ordering
             violations), or a fresh live-HTTP replay losing steady
             traffic, never shedding under the overload wave, violating
             lowest-deadline-headroom-first shed ordering, retracing a
             pool tick, or its goodput ratio regressing >25% below the
             committed one;
  chaos      a deterministic virtual-clock replay of the committed
             seeded fault plan losing work (any accepted non-cancelled
             request without exactly one terminal event), goodput under
             faults below 0.75x the fault-free run, breakers not
             recovering within the bounded pump budget, a migrated
             eta=0 trajectory not bit-identical to the uninterrupted
             one, any pool retracing its tick, the goodput ratio
             drifting >0.10 from the committed (deterministic) value,
             a nan-eps flight dump failing to name the exact poisoned
             (pool, slot, step), a corrupted-weights fault escaping
             probe-frame detection, or the fault-free replay producing
             any detection / dump (false positive).

All gates are wired into scripts/tier1.sh so hot-path and serving
regressions can't land silently.

``--record`` re-runs the recording suites (sampler + scheduler + autoplan
+ fleet + obs + gateway — with ``--suite all`` exactly those, the paper
modules don't write BENCH files), REWRITES the committed BENCH_*.json
baselines
in one command, and
appends a dated summary entry to BENCH_HISTORY.md so the perf trajectory
is tracked across PRs.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract).
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import traceback

from repro.launch.compile_cache import enable_compile_cache

PAPER_MODULES = [
    "benchmarks.table1_quality",
    "benchmarks.table2_reconstruction",
    "benchmarks.fig4_timing",
    "benchmarks.fig5_consistency",
    "benchmarks.fig6_interpolation",
    "benchmarks.beyond_paper",
    "benchmarks.roofline_table",
]

SUITES = {
    "paper": PAPER_MODULES,
    "sampler": ["benchmarks.sampler_overhead"],
    "scheduler": ["benchmarks.scheduler_throughput"],
    "autoplan": ["benchmarks.autoplan_search"],
    "fleet": ["benchmarks.fleet_throughput"],
    "obs": ["benchmarks.obs_overhead"],
    "gateway": ["benchmarks.gateway_load"],
    "chaos": ["benchmarks.chaos_recovery"],
    "all": PAPER_MODULES + ["benchmarks.sampler_overhead",
                            "benchmarks.scheduler_throughput",
                            "benchmarks.autoplan_search",
                            "benchmarks.fleet_throughput",
                            "benchmarks.obs_overhead",
                            "benchmarks.gateway_load",
                            "benchmarks.chaos_recovery"],
}

# suites whose run() rewrites a committed BENCH_*.json (and so support
# --check against it / --record of it)
RECORDING = {"sampler": ("benchmarks.sampler_overhead", "BENCH_sampler.json"),
             "scheduler": ("benchmarks.scheduler_throughput",
                           "BENCH_scheduler.json"),
             "autoplan": ("benchmarks.autoplan_search",
                          "BENCH_autoplan.json"),
             "fleet": ("benchmarks.fleet_throughput", "BENCH_fleet.json"),
             "obs": ("benchmarks.obs_overhead", "BENCH_obs.json"),
             "gateway": ("benchmarks.gateway_load", "BENCH_gateway.json"),
             "chaos": ("benchmarks.chaos_recovery", "BENCH_chaos.json")}


def _history_entry(root: str) -> str:
    """One dated BENCH_HISTORY.md block from the committed BENCH files."""
    import datetime
    lines = [f"## {datetime.date.today().isoformat()}"]
    sp = os.path.join(root, "BENCH_sampler.json")
    if os.path.exists(sp):
        with open(sp) as f:
            bench = json.load(f)
        best = {}
        for r in bench["results"]:
            if r["eta"] == 0.0:
                cur = best.get(r["path"])
                if cur is None or r["per_step_ms"] < cur["per_step_ms"]:
                    best[r["path"]] = r
        for path_name, r in sorted(best.items()):
            lines.append(
                f"- sampler/{path_name}: best {r['per_step_ms']:.3f} "
                f"ms/step (eta=0, S={r['S']}), modeled HBM "
                f"{r['modeled_hbm_bytes_per_step']} B/step")
    cp = os.path.join(root, "BENCH_scheduler.json")
    if os.path.exists(cp):
        with open(cp) as f:
            bench = json.load(f)
        for p in ("lockstep", "continuous"):
            r = bench[p]
            lines.append(
                f"- scheduler/{p}: {r['samples_per_s']:.2f} samples/s, "
                f"p95 {r['p95_s']:.3f} s, net evals {r['net_evals']}")
    fp = os.path.join(root, "BENCH_fleet.json")
    if os.path.exists(fp):
        with open(fp) as f:
            bench = json.load(f)
        for n, r in sorted(bench["fleets"].items(), key=lambda kv:
                           int(kv[0])):
            lines.append(
                f"- fleet/pools={n}: {r['samples_per_s']:.2f} samples/s, "
                f"p95 {r['p95_s']:.3f} s"
                + (f" (x{r['samples_per_s'] / bench['fleets']['1']['samples_per_s']:.2f} vs 1 pool)"
                   if n != "1" else ""))
    ap_ = os.path.join(root, "BENCH_autoplan.json")
    if os.path.exists(ap_):
        with open(ap_) as f:
            bench = json.load(f)
        for r in bench["budgets"]:
            lines.append(
                f"- autoplan/S={r['S']}: searched MMD^2 "
                f"{min(r['dp_mmd'], r['refined_mmd']):.5f} vs uniform "
                f"{r['uniform_mmd']:.5f} / quadratic "
                f"{r['quadratic_mmd']:.5f} at equal NFE")
        lines.append(f"- autoplan/search: {bench['search_wall_s']:.1f} s "
                     f"wall, grid {bench['grid_size']}, "
                     f"{bench['executor_traces']} executor traces / "
                     f"{bench['executor_calls']} rollouts")
    op = os.path.join(root, "BENCH_obs.json")
    if os.path.exists(op):
        with open(op) as f:
            bench = json.load(f)
        lines.append(
            f"- obs/telemetry: {bench['overhead_pct']:.2f}% of tick "
            f"wall-clock (host {bench['traced']['host_per_tick_ms']:.3f} "
            f"traced vs {bench['plain']['host_per_tick_ms']:.3f} plain "
            f"ms/tick on a {bench['plain']['per_tick_ms']:.3f} ms tick, "
            f"{bench['traced']['events']} span events)")
        if "probe_overhead_pct" in bench:
            lines.append(
                f"- obs/probes: {bench['probe_overhead_pct']:.2f}% of "
                f"total tick wall "
                f"({bench['probed']['per_tick_ms']:.3f} probed vs "
                f"{bench['plain']['per_tick_ms']:.3f} plain ms/tick, "
                f"{bench['probed']['probe_frames']} probe frames)")
    gw = os.path.join(root, "BENCH_gateway.json")
    if os.path.exists(gw):
        with open(gw) as f:
            bench = json.load(f)
        ov = bench["overload"]
        lines.append(
            f"- gateway/overload: goodput {bench['goodput_ratio']:.2f}x "
            f"the no-overload ceiling under a "
            f"{bench['config']['overload_base_factor'] * bench['config']['peak_ratio']:.1f}x-peak diurnal wave "
            f"(shed {ov['shed']}/{ov['offered']}, "
            f"{bench['ordering_violations']} ordering violations, "
            f"p95 {ov['p95_s']:.3f} s over live HTTP/SSE)")
    ch = os.path.join(root, "BENCH_chaos.json")
    if os.path.exists(ch):
        with open(ch) as f:
            bench = json.load(f)
        sup = bench["chaos"]["supervisor"]
        lines.append(
            f"- chaos/recovery: goodput {bench['goodput_ratio']:.2f}x "
            f"fault-free under {len(bench['fault_plan'])} injected "
            f"faults ({sup['quarantines']} quarantines, "
            f"{sup['migrated']} migrations, recovery in "
            f"{bench['chaos']['recovery_pumps']} extra pumps, migration "
            f"bit-identical={bench['migration']['identical']})")
    return "\n".join(lines) + "\n"


def _append_history(root: str) -> None:
    hist = os.path.join(root, "BENCH_HISTORY.md")
    entry = _history_entry(root)
    if not os.path.exists(hist):
        with open(hist, "w") as f:
            f.write("# Benchmark history\n\n"
                    "Appended by `benchmarks.run --record` — one dated "
                    "entry per re-baseline, newest last, so the perf "
                    "trajectory across PRs stays on the record.\n\n")
    with open(hist, "a") as f:
        f.write(entry + "\n")
    print(f"# appended {hist}", file=sys.stderr)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", choices=["quick", "full"], default="full")
    ap.add_argument("--suite", choices=sorted(SUITES), default="paper",
                    help="module group to run (sampler = hot-path microbench)")
    ap.add_argument("--only", default=None,
                    help="substring filter on module names")
    ap.add_argument("--check", action="store_true",
                    help="sampler/scheduler suites: compare a fresh run "
                    "against the committed BENCH_*.json (no rewrite); "
                    "fail on regression (see module docstring)")
    ap.add_argument("--record", action="store_true",
                    help="re-run the recording suites, rewrite their "
                    "BENCH_*.json baselines and append a dated entry to "
                    "BENCH_HISTORY.md")
    args = ap.parse_args()
    enable_compile_cache()

    if args.check and args.record:
        ap.error("--check and --record are mutually exclusive")

    if args.check:
        if args.suite not in RECORDING:
            ap.error("--check is defined for --suite "
                     + "/".join(sorted(RECORDING)))
        modname, bench_file = RECORDING[args.suite]
        mod = importlib.import_module(modname)
        failures = mod.check(args.budget)
        if failures:
            for fmsg in failures:
                print(f"CHECK FAIL: {fmsg}", file=sys.stderr)
            sys.exit(1)
        print(f"{args.suite} benchmark check OK (vs committed "
              f"{bench_file})")
        return

    if args.record and args.suite not in tuple(RECORDING) + ("all",):
        ap.error("--record is defined for --suite "
                 + "/".join(sorted(RECORDING)) + "/all")

    if args.record:
        modules = [RECORDING[s][0] for s in sorted(RECORDING)
                   if args.suite in ("all", s)]
    else:
        modules = SUITES[args.suite]

    print("name,us_per_call,derived")
    failed, ran = [], 0
    for modname in modules:
        if args.only and args.only not in modname:
            continue
        t0 = time.time()
        try:
            mod = importlib.import_module(modname)
            rows = mod.run(args.budget)
            ran += 1
            for row in rows:
                print(row.csv(), flush=True)
            print(f"# {modname} done in {time.time()-t0:.1f}s",
                  file=sys.stderr, flush=True)
        except Exception:
            failed.append(modname)
            print(f"# {modname} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr, flush=True)
    if failed:
        sys.exit(1)
    if args.record:
        if ran == 0:   # e.g. --only filtered everything: nothing fresh to
            print("# --record: no recording suite ran, history untouched",
                  file=sys.stderr)
            return     # baseline, so don't log a re-baseline that wasn't
        from benchmarks._common import ROOT
        _append_history(ROOT)


if __name__ == "__main__":
    main()
