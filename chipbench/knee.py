#!/usr/bin/env python3
"""Find an open-loop cell's knee once, on the chip: the highest arrival
rate the system sustains.

  python3 chipbench/knee.py --workload <name> --rates 20,40,60 \
      --seconds 8 --seed 1

Builds the cell's gateway once, then offers each rate in turn for
``--seconds`` (the traffic file's mix, at that rate) and prints one JSON
line per rate: the answered rate, latency p50/p95 over the window's
requests, and the ratio of the median latency of the window's last third
to that of its first third. A sustained rate answers what it is offered
and keeps that ratio near 1; past the knee the queue grows all through
the window and the ratio climbs. The cell's traffic file then takes about
four fifths of the knee.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import tempfile
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import measure  # noqa: E402
import run  # noqa: E402


def sweep_point(b, cell, rate: float, seconds: float, seed: int) -> dict:
    cell_at = SimpleNamespace(**vars(cell))
    cell_at.traffic = dict(cell.traffic, rate_per_s=rate)
    plan = run.make_plan(cell_at, seed, seconds, b.slots, b.pools)
    with tempfile.TemporaryDirectory(prefix="chipbench-knee-") as tmp:
        work = Path(tmp)
        win = asyncio.run(run.serve_window(b.core, plan, work, None,
                                           run.CompileCounter()))
        records = json.loads((work / "records.json").read_text())
    due = [r for r in records if r["phase"] == "window"]
    lat = sorted((r["sched_t"], r["done_t"] - r["sched_t"]) for r in due
                 if r.get("ok"))
    third = max(1, len(lat) // 3)
    first = measure.percentile([v for _, v in lat[:third]], 50)
    last = measure.percentile([v for _, v in lat[-third:]], 50)
    t0, t1 = win.window
    answered = sum(1 for r in records if r.get("ok")
                   and t0 <= r["done_t"] <= t1) / (t1 - t0)
    ok = [v for _, v in lat]
    backlog = sum(1 for r in due if not r.get("ok") or r["done_t"] > t1)
    return {"rate_per_s": rate, "offered": len(due),
            "failed": len(due) - len(ok), "answered_per_s": answered,
            "open_at_close": backlog,
            "p50_s": measure.percentile(ok, 50),
            "p95_s": measure.percentile(ok, 95),
            "late_over_early": (last / first) if first and last else None}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    try:
        cell = run.load_cell(args.workload)
        if cell.traffic["loop"] != "open":
            raise run.RunFailure("a knee is found for open-loop cells")
        b = run.build(cell, args.seed, False)
    except run.RunFailure as e:
        print(f"knee: FAIL: {e}", file=sys.stderr)
        return 1
    for rate in (float(r) for r in args.rates.split(",")):
        point = sweep_point(b, cell, rate, args.seconds, args.seed)
        print(json.dumps(point), flush=True)
        if point["failed"] or (point["late_over_early"] or 0) > 2.0:
            break               # past the knee: higher rates only queue
    return 0


if __name__ == "__main__":
    sys.exit(main())
