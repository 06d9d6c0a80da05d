"""Load generator: drives POST /v1/sample from a process of its own.

  python3 chipbench/client.py <plan.json> <out_dir>

It never imports JAX, so it shares no interpreter lock with the server's
engine thread. The plan (written by run.py) holds the gateway's URL, the
warm-up round, and either an open-loop schedule or closed-loop specs.
Protocol on stdin/stdout with the harness:

  client -> "READY"            warm round served
  harness -> "GO"              start the load (its ramp first)
  client -> "OPEN"             the measured window opens (the open loop's
                               ramp is sent, the closed loop's clients
                               have finished a round of requests)
  client -> "WINDOW <t0> <t1>" the measured window has closed
  client -> "DONE"             every request answered or given up;
                               records.json and sample.npz are written

Every request's record holds its scheduled send, its actual send, its first
preview and its final answer on the shared monotonic clock
(``time.perf_counter``), with the engine's own timings from the result.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import aiohttp
import numpy as np


def parse_sse(lines: List[str]):
    """SSE text lines -> [(event name, raw data text)]."""
    out, name = [], None
    for line in lines:
        if line.startswith("event: "):
            name = line[len("event: "):]
        elif line.startswith("data: ") and name is not None:
            out.append((name, line[len("data: "):]))
            name = None
    return out


def _decode(result: Dict) -> Dict:
    x0 = result.pop("x0", None)
    if x0 is not None:
        result["x0"] = np.reshape(np.asarray(x0["data"], np.float32),
                                  x0["shape"])
    return result


async def sample_request(sess, url: str, spec: Dict, rec: Dict) -> None:
    """POST one spec; fills ``rec`` with times, status and the answer."""
    rec["send_t"] = time.perf_counter()
    async with sess.post(f"{url}/v1/sample", json=spec) as r:
        rec["status"] = r.status
        if spec.get("stream"):
            buf, lines = b"", []
            async for chunk in r.content.iter_any():
                buf += chunk
                *done, buf = buf.split(b"\n")
                for raw in done:
                    line = raw.decode("utf-8")
                    if (line == "event: preview"
                            and rec.get("first_preview_t") is None):
                        rec["first_preview_t"] = time.perf_counter()
                    if line.startswith("event: ") or (
                            lines and lines[-1] in ("event: result",
                                                    "event: error")):
                        lines.append(line)
            events = parse_sse(lines)
            term = [(n, d) for n, d in events if n in ("result", "error")]
            name, body = (term[-1][0], json.loads(term[-1][1])) if term \
                else (None, None)
        else:
            body = await r.json()
            name = "result" if r.status == 200 else "error"
    rec["done_t"] = time.perf_counter()
    rec["terminal"] = name
    rec["ok"] = name == "result" and rec["status"] == 200
    if body is not None and name == "result":
        body = _decode(body)
        rec["x0"] = body.pop("x0", None)
        for k in ("latency_s", "queue_wait_s", "service_s", "pool_id",
                  "previews"):
            rec[k] = body.get(k)
        rec["finite"] = bool(rec["x0"] is not None
                             and np.isfinite(rec["x0"]).all())
    elif body is not None:
        rec["error"] = str(body)[:300]


async def guarded(sess, url, spec, rec) -> None:
    try:
        await sample_request(sess, url, spec, rec)
    except (aiohttp.ClientError, asyncio.TimeoutError, ValueError) as e:
        rec["ok"] = False
        rec["error"] = repr(e)[:300]


async def readline(reader) -> str:
    return (await reader.readline()).decode().strip()


async def main(plan_path: str, out_dir: str) -> int:
    plan = json.loads(Path(plan_path).read_text())
    url, seconds = plan["url"], float(plan["seconds"])
    loop = asyncio.get_running_loop()
    stdin = asyncio.StreamReader()
    await loop.connect_read_pipe(
        lambda: asyncio.StreamReaderProtocol(stdin), sys.stdin)
    records: List[Dict] = []
    conn = aiohttp.TCPConnector(limit=0)
    timeout = aiohttp.ClientTimeout(total=None)
    async with aiohttp.ClientSession(connector=conn,
                                     timeout=timeout) as sess:
        warm = [{"phase": "warm", "spec": s} for s in plan["warm"]]
        await asyncio.gather(*(guarded(sess, url, r["spec"], r)
                               for r in warm))
        bad = [r for r in warm if not r.get("ok")]
        if bad:
            print(f"client: warm round failed: {bad[0].get('error')}",
                  file=sys.stderr, flush=True)
            return 1
        tasks: List[asyncio.Task] = []
        if plan["loop"] == "open":
            t1 = await run_open(sess, url, plan, seconds, stdin, records,
                                tasks)
        else:
            t1 = await run_closed(sess, url, plan, seconds, stdin, records,
                                  tasks)
        t0 = t1 - seconds
        print(f"WINDOW {t0!r} {t1!r}", flush=True)
        _, pending = await asyncio.wait(tasks, timeout=plan["drain_s"]) \
            if tasks else (None, [])
        for t in pending:
            t.cancel()
        await asyncio.gather(*pending, return_exceptions=True)
    write_out(Path(out_dir), records, plan)
    print("DONE", flush=True)
    return 0


async def run_open(sess, url, plan, seconds, stdin, records, tasks):
    sched = [dict(r, phase="ramp") for r in plan["schedule"]["ramp"]] + [
        dict(r, phase="window") for r in plan["schedule"]["window"]]
    print("READY", flush=True)
    if await readline(stdin) != "GO":
        raise SystemExit("client: no GO from the harness")
    ramp = -min([r["t"] for r in sched] + [0.0])
    t0 = time.perf_counter() + ramp
    opened = False
    for r in sched:
        due = t0 + r["t"]
        if not opened and r["t"] >= 0:
            await asyncio.sleep(max(0.0, t0 - time.perf_counter()))
            print("OPEN", flush=True)
            opened = True
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = {"phase": r["phase"], "sched_t": due, "spec": r["spec"]}
        records.append(rec)
        tasks.append(asyncio.create_task(guarded(sess, url, r["spec"],
                                                 rec)))
    if not opened:
        print("OPEN", flush=True)
    end = t0 + seconds
    await asyncio.sleep(max(0.0, end - time.perf_counter()))
    return end


async def run_closed(sess, url, plan, seconds, stdin, records, tasks):
    specs = iter(plan["specs"])
    window: Dict[str, Optional[float]] = {"t0": None, "t1": None}
    done_count = [0]
    ready = asyncio.Event()

    async def one_client():
        for spec in specs:
            t1 = window["t1"]
            if t1 is not None and time.perf_counter() >= t1:
                return
            rec = {"spec": spec, "phase": ("window" if window["t0"]
                                           is not None else "ramp")}
            rec["sched_t"] = time.perf_counter()
            records.append(rec)
            await guarded(sess, url, spec, rec)
            done_count[0] += 1
            if done_count[0] >= plan["ramp_completions"]:
                ready.set()

    print("READY", flush=True)
    if await readline(stdin) != "GO":
        raise SystemExit("client: no GO from the harness")
    tasks.extend(asyncio.create_task(one_client())
                 for _ in range(plan["clients"]))
    await ready.wait()
    print("OPEN", flush=True)
    window["t0"] = time.perf_counter()
    window["t1"] = window["t0"] + seconds
    await asyncio.sleep(seconds)
    return window["t1"]


def write_out(out: Path, records: List[Dict], plan: Dict) -> None:
    """records.json without the samples; sample.npz with the x0 of the
    eta = 0 window requests picked for the reference."""
    picked = pick_sample(records, plan)
    arrays = {}
    for i, rec in enumerate(records):
        x0 = rec.pop("x0", None)
        if i in picked:
            arrays[f"x0_{i}"] = x0
            rec["compared"] = True
    (out / "records.json").write_text(json.dumps(records))
    np.savez(out / "sample.npz", **arrays)


def pick_sample(records: List[Dict], plan: Dict) -> List[int]:
    """Indices of the answered eta = 0 window requests to compare: the one
    with the most steps, then one drawn from the seed for each pool not yet
    covered, then a draw from the seed for the rest."""
    ok = [i for i, r in enumerate(records)
          if r["phase"] == "window" and r.get("ok")
          and r.get("x0") is not None
          and float(r["spec"].get("eta", 0.0)) == 0.0]
    if not ok:
        return []
    rng = np.random.default_rng([int(plan["seed"]) % (2 ** 64), 4])
    n = int(plan["compare"])
    picked = [max(ok, key=lambda i: (records[i]["spec"]["S"], -i))]
    for pool in sorted({records[i].get("pool_id") for i in ok} - {None}):
        left = [i for i in ok if records[i].get("pool_id") == pool
                and i not in picked]
        if left and len(picked) < n and pool not in {
                records[i].get("pool_id") for i in picked}:
            picked.append(int(rng.choice(left)))
    rest = [i for i in ok if i not in picked]
    k = min(len(rest), n - len(picked))
    return sorted(picked + rng.choice(rest, k, replace=False).tolist())


if __name__ == "__main__":
    sys.exit(asyncio.run(main(sys.argv[1], sys.argv[2])))
