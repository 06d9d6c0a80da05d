#!/usr/bin/env python3
"""The control of a cell's correctness check, on the chip.

  python3 chipbench/control.py --workload <name> --seeds 11,12,13

For each seed: the requests a run of the cell compares (the eta = 0
requests of the seed's own traffic, the longest among them), the float32
reference at "highest" precision, and the same reference computed in
bfloat16 (weights, activations and the carried state), as a program that
served in the next precision down would. Prints one JSON line per seed
with the compared numbers (``x0_rel_rms``, ``x0_rel_max``) of the
bfloat16 run against the float32 one: the upper readings of the cell's
limits. A sound program reads below them; this control must read above.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import traffic as traffic_gen  # noqa: E402


def compared_requests(cell, seed: int, seconds: float):
    """The eta = 0 requests one run compares, picked by
    ``client.pick_sample`` from the seed's window as if every request were
    answered (by one pool)."""
    from client import pick_sample

    tr = cell.traffic
    if tr["loop"] == "open":
        specs = [r["spec"] for r in
                 traffic_gen.open_schedule(tr, seconds, seed)["window"]]
    else:
        specs = traffic_gen.closed_specs(tr, seed, blocks=4)
    records = [{"phase": "window", "ok": True, "x0": True, "spec": s}
               for s in specs]
    pick = pick_sample(records, {"seed": seed,
                                 "compare": cell.check["compare"]})
    return [{"S": specs[i]["S"], "tau": specs[i].get("tau", "linear"),
             "order": specs[i].get("order", 1), "seed": specs[i]["seed"]}
            for i in pick]


def control_reading(cell, seed: int, seconds: float) -> dict:
    import reference

    cfg = cell.config
    reqs = compared_requests(cell, seed, seconds)
    params = reference.make_params(cfg, seed)
    batch = int(cell.check.get("batch", 8))
    t0 = time.perf_counter()
    ref = reference.sample(params, cfg, reqs, batch=batch)
    low = reference.sample(params, cfg, reqs, dtype="bfloat16", batch=batch)
    errs = [reference.rel_errors(a, b) for a, b in zip(low, ref)]
    return {"seed": seed, "requests": len(reqs),
            "longest_S": max(r["S"] for r in reqs),
            "x0_rel_rms": max(e[0] for e in errs),
            "x0_rel_max": max(e[1] for e in errs),
            "seconds": time.perf_counter() - t0}


def main() -> int:
    import run

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    cell = run.load_cell(args.workload)
    run.use_compile_cache(run.ROOT)
    try:
        run.find_chips(1, require_tpu=True)   # the reference runs on one chip
    except run.RunFailure as e:
        print(f"control: FAIL: {e}", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(control_reading(cell, seed, args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
