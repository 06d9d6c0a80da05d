"""The harness is data: every cell resolves by name to its files, and the
benchmark's names and units keep to the contract's alphabet."""
import json
import os
import re
import subprocess
import sys
from collections import Counter
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def metrics_of(cell, kind):
    return [m for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    import run
    c = run.load_cell(cell)
    assert c.config["name"] == next(w["config"] for w in BENCH["workloads"]
                                    if w["name"] == cell)
    assert c.traffic["loop"] in ("open", "closed")
    assert set(c.check["limits"]) == {"x0_rel_rms", "x0_rel_max"}
    for m in c.end_to_end + c.per_layer:
        assert callable(run.reader(m["name"])), m["name"]
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


@pytest.mark.parametrize("cell", CELLS)
def test_each_layer_metric_moves_a_metric_its_cells_report(cell):
    reported = {m["name"] for m in metrics_of(cell, "end_to_end")}
    for m in metrics_of(cell, "per_layer"):
        assert m["moves"] in reported, (cell, m["name"])


def test_names_units_and_files_keep_to_the_contract():
    seen = Counter()
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[kind]:
            assert NAME.match(e["name"]), e["name"]
            seen[e["name"]] += 1
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    assert max(seen.values()) == 1
    layers = {}
    for m in BENCH["per_layer"]:
        assert "\n" not in m["layer"] and m["source"] in (
            "device_trace", "program_span", "program_counter", "host_clock")
        layers.setdefault(m["layer"], m["layer"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        f = ROOT / c["file"]
        assert f.is_file() and c["file"].startswith(tuple(BENCH["paths"]))
        assert json.loads(f.read_text())["reduced"] == c["reduced"] == []
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_no_tpu_exits_nonzero_without_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", CELLS[0],
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (HERE / "traffic").glob("*.json")))
def test_every_seed_gets_the_same_work_in_another_order(name):
    tr = traffic.load(HERE / "traffic" / f"{name}.json")

    def work(seed):
        if tr["loop"] == "open":
            s = traffic.open_schedule(tr, 10.0, seed)["window"]
            specs = [r["spec"] for r in s]
            t = [r["t"] for r in s] + [10.0]
            gaps = sorted(b - a for a, b in zip(t, t[1:]))
        else:
            specs, gaps = traffic.closed_specs(tr, seed, blocks=2), []
        kinds = Counter(json.dumps({k: v for k, v in sp.items()
                                    if k != "seed"}, sort_keys=True)
                        for sp in specs)
        return kinds, gaps, [sp["seed"] for sp in specs]

    k1, g1, s1 = work(2 ** 40 + 3)
    k2, g2, s2 = work(-5)
    assert k1 == k2
    assert s1 != s2
    assert g1 == pytest.approx(g2, rel=1e-9, abs=1e-12)


def test_images_per_s_counts_each_samples_share_of_service_in_window():
    import run
    from types import SimpleNamespace

    rec = lambda done, service, ok=True: {
        "phase": "window", "ok": ok, "finite": ok, "done_t": done,
        "service_s": service}
    r = SimpleNamespace(window=(10.0, 20.0), records=[
        rec(12.0, 2.0),            # wholly inside: 1
        rec(11.0, 4.0),            # started 7.0: 1/4 of it before
        rec(21.0, 2.0),            # half after the close
        rec(30.0, 2.0),            # wholly after: 0
        rec(15.0, 1.0, ok=False),  # failed: 0
    ])
    assert run.reader("images_per_s")(r) == pytest.approx(
        (1 + 0.25 + 0.5) / 10.0)


def test_host_spans_leave_out_calls_the_program_does_not_have():
    import run
    from types import SimpleNamespace

    calls = []
    eng = SimpleNamespace(snapshot_slots=lambda: calls.append("snap"),
                          _admit=lambda: calls.append("admit"))
    pool = SimpleNamespace(pool_id=0, engine=eng,
                           tick=lambda: calls.append("tick"))
    core = SimpleNamespace(pump=lambda: calls.append("pump"),
                           fleet=SimpleNamespace(pools=[pool]))
    missing = run.install_host_spans(core)
    assert missing == ["chipbench/gateway/submit",
                       "chipbench/fleet/dispatch",
                       "chipbench/pool0/states",
                       "chipbench/pool0/previews",
                       "chipbench/pool0/read_slot"]
    core.pump(), pool.tick(), eng._admit(), eng.snapshot_slots()
    assert calls == ["pump", "tick", "admit", "snap"]
