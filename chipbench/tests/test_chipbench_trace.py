"""The reduction from a profiler trace to device metrics: on a trace made
by hand, where every figure can be counted, and on a short window of the
CIFAR10 cell recorded on a TPU v5e."""
import gzip
import sys
from pathlib import Path

import pytest
from jax.profiler import ProfileData

HERE = Path(__file__).resolve().parents[1]
FIX = Path(__file__).resolve().parent / "fixtures"
sys.path.insert(0, str(HERE))

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


# One chip, a 100 us window. The tick program runs 20-35 us (a fusion,
# then the step kernel) and 50-60 us (a fusion); the host builds states at
# 10-20 us inside its tick span 10-40 us, then reads back at 55-70 us.
HAND = "".join([
    plane(1, "/device:TPU:0", [
        ("XLA Modules", [(1, 20, 15), (1, 50, 10)]),
        ("XLA Ops", [(2, 20, 10), (3, 30, 5), (4, 50, 10)]),
    ], {1: "jit_tick(7)", 2: "fusion.1", 3: "_row_det_kernel",
        4: "fusion.2"}),
    plane(2, "/host:CPU", [
        ("main", [(1, 0, 100)]),
        ("engine", [(2, 10, 30), (3, 10, 10), (4, 55, 15)]),
    ], {1: "chipbench/window", 2: "chipbench/pool0/tick",
        3: "chipbench/pool0/states", 4: "chipbench/pool0/read_slot"}),
])


@pytest.fixture(scope="module")
def hand():
    return trace_reduce.reduce(ProfileData.from_text_proto(HAND))


def test_busy_and_idle_share_by_hand(hand):
    assert hand.window_s == pytest.approx(100e-6)
    assert hand.busy_s == pytest.approx(25e-6)
    assert hand.idle_share == pytest.approx(0.75)


def test_op_time_and_program_runs_by_hand(hand):
    kernel = hand.op_seconds(lambda n: "kernel" in n)
    assert kernel == pytest.approx(5e-6)
    assert hand.op_seconds(lambda n: True,
                           module=lambda m: m.startswith("jit_tick")) \
        == pytest.approx(25e-6)
    assert hand.op_count(lambda n: n.startswith("fusion")) == 2
    assert hand.module_count(lambda m: m.startswith("jit_tick")) == 2


def test_idle_gaps_go_to_the_innermost_host_span(hand):
    gaps = dict((n, v) for n, v in hand.idle_gaps())
    # 0-10 no span; 10-20 states; 35-40 tick; 40-50 no span;
    # 60-70 read_slot; 70-100 no span
    assert gaps == pytest.approx({
        trace_reduce.NO_SPAN: 50e-6, "chipbench/pool0/states": 10e-6,
        "chipbench/pool0/tick": 5e-6, "chipbench/pool0/read_slot": 10e-6})
    assert sum(gaps.values()) == pytest.approx(hand.window_s
                                               - hand.busy_s)


def test_a_trace_without_the_window_span_is_refused():
    bad = HAND.replace("chipbench/window", "chipbench/other")
    with pytest.raises(ValueError, match="window"):
        trace_reduce.reduce(ProfileData.from_text_proto(bad))


# A 1 s window of `cifar10.poisson-mixed` traced on one TPU v5e, cut from
# the profiler's .xplane.pb to the device's ops and programs and the
# harness's host spans (`chipbench/...`, `repro/...`) in that window.
@pytest.fixture(scope="module")
def chip():
    raw = gzip.decompress((FIX / "cifar10-window.textproto.gz").read_bytes())
    return trace_reduce.reduce(ProfileData.from_text_proto(raw.decode()))


def kernel(name):
    return 'custom_call_target="tpu_custom_call"' in name


def in_tick(module):
    return module.startswith("jit_tick")


def test_busy_share_of_the_chip_trace_is_the_union_of_its_ops(chip):
    d = chip.devices[0]
    spans = sorted(zip(d.starts, d.ends))
    busy, end = 0.0, chip.lo
    for s, e in spans:                 # the union, counted by hand
        s, e = max(s, end), min(e, chip.hi)
        if e > s:
            busy += e - s
            end = e
    assert chip.window_s == pytest.approx(1.0)
    assert chip.busy_s == pytest.approx(busy / 1e9, rel=1e-9)
    assert 0.05 < chip.busy_s < 0.2    # the host-bound cell idles


def test_step_kernel_time_and_calls_in_the_chip_trace(chip):
    d = chip.devices[0]
    mine = [(e - s) for n, m, s, e in zip(d.op_names, d.op_modules,
                                          d.starts, d.ends)
            if kernel(n) and in_tick(m) and chip.lo <= s < chip.hi]
    assert chip.op_count(kernel, module=in_tick) == len(mine) \
        == chip.module_count(in_tick) == 23       # one call per tick
    assert chip.op_seconds(kernel, module=in_tick) == pytest.approx(
        sum(mine) / 1e9, rel=1e-6)
    assert chip.op_seconds(kernel, module=in_tick) == pytest.approx(
        118.592e-6, rel=1e-4)


def test_idle_gaps_of_the_chip_trace_add_up_and_name_the_host_work(chip):
    gaps = dict(chip.idle_gaps(k=100))
    assert sum(gaps.values()) == pytest.approx(chip.window_s - chip.busy_s,
                                               rel=1e-6)
    top = chip.idle_gaps(k=1)[0]
    assert top[0] == "chipbench/pool0/checkpoint"
    assert top[1] == pytest.approx(0.397362724, rel=1e-6)
    assert all(isinstance(v, float) for _, v in chip.top_ops())


@pytest.mark.parametrize("metric,share", [("sampler_step_roofline", 50.10),
                                          ("trunk_roofline", 42.17)])
def test_roofline_readers_find_their_ops_in_the_chip_trace(chip, metric,
                                                           share):
    import json
    from types import SimpleNamespace

    import run
    cfg = json.loads((HERE / "configs" / "cifar10-unet.json").read_text())
    peaks = run.peak_row("TPU v5 lite", True)
    traced = SimpleNamespace(
        trace=chip, peaks=peaks, config=cfg, slots=32,
        engine={"stochastic": True, "max_order": 2, "preview": True})
    assert run.reader(metric)(traced) == pytest.approx(share, abs=0.01)
