"""The readers of the program's own spans (``chipbench/spans.py`` and the
metrics that call it), on traces made by hand where every figure can be
counted, built as ``test_chipbench_trace.py`` builds its own."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import run  # noqa: E402
import spans  # noqa: E402
import trace_reduce  # noqa: E402

US = 1_000_000          # picoseconds in a microsecond


def event(meta, start_us, dur_us):
    return (f"events {{ metadata_id: {meta} offset_ps: {start_us * US} "
            f"duration_ps: {dur_us * US} }}")


def plane(pid, name, lines, names):
    body = "".join(
        f"lines {{ id: {i + 1} name: {ln!r} timestamp_ns: 0 "
        + " ".join(event(*e) for e in evs) + " }"
        for i, (ln, evs) in enumerate(lines))
    meta = "".join(f"event_metadata {{ key: {k} value {{ id: {k} "
                   f"name: {v!r} }} }}" for k, v in names.items())
    return f"planes {{ id: {pid} name: {name!r} {body} {meta} }}"


def host_trace(engine, http=(), names=None):
    """One chip with one op, and a 1000 us window; ``engine`` and ``http``
    are (name, start_us, dur_us) on two host threads."""
    names = dict(names or {})
    ids = {n: i + 2 for i, n in enumerate(sorted(
        {n for n, _, _ in list(engine) + list(http)}))}
    names.update({i: n for n, i in ids.items()})
    names[1] = "chipbench/window"
    return "".join([
        plane(1, "/device:TPU:0", [("XLA Ops", [(1, 150, 80)])],
              {1: "fusion.1"}),
        plane(2, "/host:CPU", [
            ("main", [(1, 0, 1000)]),
            ("engine", [(ids[n], a, d) for n, a, d in engine]),
            ("http", [(ids[n], a, d) for n, a, d in http]),
        ], names),
    ])


# Two pools ticked in turn by one gateway thread. Pump A (100-400 us):
# pool 0 ticks 110-250 with its block 150-230, pool 1 ticks 250-390 with
# its block 270-370, pool 0's checkpoint sweep 392-398. Pump B (500-700):
# pool 0 ticks 510-600 (block 530-580), pool 1 600-690 (block 610-680),
# pool 1's sweep 690-698. Pump C (800-900) only admits on pool 0 (tick
# 810-820, no block). A sweep of pool 0 straddles the window's start.
TWO_POOLS = [
    ("repro/pool0/checkpoint", -10, 20),
    ("repro/gateway/pump", 100, 300),
    ("repro/fleet/dispatch", 101, 5),
    ("repro/pool0/tick", 110, 140), ("repro/pool0/admit", 110, 10),
    ("repro/pool0/states", 120, 20), ("repro/pool0/launch", 140, 10),
    ("repro/pool0/block", 150, 80), ("repro/pool0/retire", 235, 10),
    ("repro/pool1/tick", 250, 140), ("repro/pool1/launch", 260, 10),
    ("repro/pool1/block", 270, 100),
    ("repro/pool0/checkpoint", 392, 6),
    ("repro/gateway/pump", 500, 200),
    ("repro/pool0/tick", 510, 90), ("repro/pool0/block", 530, 50),
    ("repro/pool1/tick", 600, 90), ("repro/pool1/block", 610, 70),
    ("repro/pool1/checkpoint", 690, 8),
    ("repro/gateway/pump", 800, 100),
    ("repro/pool0/tick", 810, 10), ("repro/pool0/admit", 810, 10),
]
# bodies encoded on the event loop's thread; the last one runs past the
# window's end (990-1010) and counts 10 us
ENCODE = [("repro/http/encode", 300, 10), ("repro/http/encode", 720, 15),
          ("repro/http/encode", 990, 20)]


def traced(text, records=()):
    t = trace_reduce.reduce(ProfileData.from_text_proto(text))
    return SimpleNamespace(trace=t, records=list(records))


def rec(phase="window", ok=True):
    return {"phase": phase, "ok": ok}


@pytest.fixture(scope="module")
def two_pools():
    return traced(host_trace(TWO_POOLS, ENCODE), [
        rec(), rec(), rec(), rec(), rec(ok=False), rec(phase="warm")])


def test_spans_are_selected_by_name_and_clipped_to_the_window(two_pools):
    sweeps = spans.select(two_pools, r"repro/pool\d+/checkpoint")
    assert [(a, b) for _, a, b in sweeps] == [
        (0, 10_000), (392_000, 398_000), (690_000, 698_000)]
    by = spans.by_pool(sweeps)
    assert sorted(by) == [0, 1] and len(by[0]) == 2
    assert spans.seconds(spans.select(two_pools, r"repro/http/encode")) \
        == pytest.approx(35e-6)


@pytest.mark.parametrize("metric", ["checkpoint_share",
                                    "checkpoint_share.latency"])
def test_checkpoint_share_sums_the_pools_sweeps(two_pools, metric):
    # pool 0: 10 (clipped) + 6, pool 1: 8, of a 1000 us window
    assert run.reader(metric)(two_pools) == pytest.approx(2.4)


@pytest.mark.parametrize("metric", ["engine_host_ms",
                                    "engine_host_ms.latency"])
def test_engine_host_ms_is_tick_less_block_per_launched_tick(two_pools,
                                                             metric):
    # pool 0: ticks 140 + 90 + 10, blocks 80 + 50, 2 launches: 55 us;
    # pool 1: ticks 140 + 90, blocks 100 + 70, 2 launches: 30 us
    assert run.reader(metric)(two_pools) == pytest.approx((55 + 30) / 2e3)


@pytest.mark.parametrize("metric", ["http_encode_ms",
                                    "http_encode_ms.throughput"])
def test_http_encode_ms_per_answered_request_due_in_the_window(two_pools,
                                                               metric):
    # 10 + 15 + 10 us over the 4 answered window requests
    assert run.reader(metric)(two_pools) == pytest.approx(35 / 4e3)


def test_fleet_serial_wait_sums_every_pools_block_inside_a_pump(two_pools):
    # pump A waits 80 + 100 us, pump B 50 + 70; pump C launched nothing
    assert run.reader("fleet_serial_wait_ms")(two_pools) == pytest.approx(
        (180 + 120) / 2e3)


def test_overlapping_blocks_inside_one_pump_are_each_counted():
    # pools dispatched before any sync: two blocks overlap in one pump,
    # and the serial wait still sums them (each chip's own wait)
    run_ = traced(host_trace([
        ("repro/gateway/pump", 100, 200),
        ("repro/pool0/tick", 100, 150), ("repro/pool0/block", 150, 60),
        ("repro/pool1/tick", 110, 180), ("repro/pool1/block", 160, 80),
    ]))
    assert run.reader("fleet_serial_wait_ms")(run_) == pytest.approx(0.14)


NEW = ["checkpoint_share", "checkpoint_share.latency", "engine_host_ms",
       "engine_host_ms.latency", "http_encode_ms",
       "http_encode_ms.throughput", "fleet_serial_wait_ms"]


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_spans_reads_nothing(metric):
    # the harness's wrappers alone, as a program before its own spans
    old = traced(host_trace([("chipbench/gateway/pump", 100, 300),
                             ("chipbench/pool0/tick", 110, 200),
                             ("chipbench/pool0/checkpoint", 320, 50)]),
                 [rec()])
    assert run.reader(metric)(old) is None
    assert run.reader(metric)(SimpleNamespace(trace=None,
                                              records=[rec()])) is None
