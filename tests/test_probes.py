"""Tests for the device-probe tier + fault flight recorder (ISSUE 10).

Covers the probe contract and the flight-recorder forensics:
  * bit-identity: an engine with probes compiled (on OR toggled off)
    produces byte-identical samples to a probe-less engine, and each
    program compiles exactly one tick trace;
  * trace budget: toggling probes off and back on costs exactly ONE
    extra compiled tick (two total) — the probed program replaces the
    plain one per tick, it never stacks;
  * the probed tick program contains zero PRNG ops (the reductions are
    deterministic arithmetic over state the tick already owns);
  * frozen frame schema: (slots, 6) float32 in PROBE_COLUMNS order,
    disabled probes filling NaN ("not computed"), slot->request map
    recording the step index the frame measured;
  * per-request quality summaries on SampleResult (None without probes);
  * mega + probes is a loud ctor error (the fused kernel's eps never
    materializes), and use_mega=False + probes composes;
  * FlightRecorder ring/dump/read round-trip, NaN->null cleaning,
    nonfinite attribution, and the silent-weight-corruption detector.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_schedule
from repro.obs import (PROBE_COLUMNS, FlightRecorder, ProbeSpec,
                       attribute_nonfinite, detect_weight_corruption,
                       read_flight)
from repro.obs.schema import FLIGHT_FRAME_KEYS, FLIGHT_HEADER_KEYS
from repro.serving.scheduler import ContinuousBatchingEngine, SampleRequest

SCH = make_schedule("linear", T=1000)
DIM, SLOTS = 8, 2
COL = {c: i for i, c in enumerate(PROBE_COLUMNS)}


def analytic_eps(sch, mu=2.0, s=0.5):
    def eps_fn(x, t):
        a = sch.alpha_bar[t].reshape((-1,) + (1,) * (x.ndim - 1))
        return (x - jnp.sqrt(a) * mu) * jnp.sqrt(1 - a) / (1 - a + a * s * s)
    return eps_fn


EPS = analytic_eps(SCH)


def _engine(**kw):
    kw.setdefault("slots", SLOTS)
    return ContinuousBatchingEngine(SCH, EPS, (DIM,), **kw)


def _reqs(n, S=4, **kw):
    return [SampleRequest(request_id=i, S=S, eta=0.0, seed=i, **kw)
            for i in range(n)]


def _run_virtual(eng, reqs, t0=0.0):
    for r in reqs:
        eng.submit(r, now=t0)
    results, clock = [], t0
    while eng.active or len(eng.queue):
        clock += 0.001
        results.extend(eng.tick(now=clock))
    return results


def _collect_prims(jaxpr, acc):
    for eqn in jaxpr.eqns:
        acc.append(eqn.primitive.name)
        for v in eqn.params.values():
            if hasattr(v, "jaxpr"):
                _collect_prims(v.jaxpr, acc)
            if isinstance(v, (list, tuple)):
                for vv in v:
                    if hasattr(vv, "jaxpr"):
                        _collect_prims(vv.jaxpr, acc)
    return acc


# ------------------------------------------------------- probe contract
def test_probe_columns_frozen():
    assert PROBE_COLUMNS == ("eps_rms", "x0_min", "x0_max", "x0_mean",
                             "finite_frac", "defect")


def test_probes_bit_identity_and_one_trace_each():
    """Acceptance: probes on OR compiled-but-off never change a bit of
    the samples, and every engine stays on one compiled tick."""
    plain = _engine()
    ref = {r.request_id: r for r in _run_virtual(plain, _reqs(5, S=6))}
    on = _engine(probes=True)
    got_on = {r.request_id: r for r in _run_virtual(on, _reqs(5, S=6))}
    off = _engine(probes=True)
    off.set_probes(False)
    got_off = {r.request_id: r for r in _run_virtual(off, _reqs(5, S=6))}
    for i, r in ref.items():
        np.testing.assert_array_equal(r.x0, got_on[i].x0)
        np.testing.assert_array_equal(r.x0, got_off[i].x0)
    assert plain._traces == 1
    assert on._traces == 1
    assert off._traces == 1


def test_probe_toggle_costs_exactly_one_extra_trace():
    eng = _engine(probes=True)
    _run_virtual(eng, _reqs(2))
    assert eng._traces == 1
    assert eng.stats()["probes"] == eng.probe_spec.describe()
    eng.set_probes(False)
    _run_virtual(eng, _reqs(2))
    assert eng._traces == 2                 # the plain program compiled
    assert eng.stats()["probes"] == "off"
    eng.set_probes(True)
    _run_virtual(eng, _reqs(2))
    assert eng._traces == 2                 # both cached: no third trace
    assert eng.stats()["compiled_ticks"] == 2
    assert eng.stats()["probe_frames"] > 0


def test_probed_tick_has_no_prng_ops():
    """The probe reductions are deterministic arithmetic: no threefry /
    random bits anywhere in the probed program."""
    eng = _engine(probes=True)
    prims = _collect_prims(jax.make_jaxpr(
        lambda x, p, s: eng._tick_probed(x, p, s))(
            eng._x2, eng._probe_prev, eng._states()).jaxpr, [])
    bad = [p for p in prims if "threefry" in p or "random" in p
           or "prng" in p]
    assert not bad, bad


def test_probe_frame_schema_and_disabled_columns_nan():
    spec = ProbeSpec(x0_stats=False, defect=False)
    eng = _engine(probes=spec)
    eng.submit(SampleRequest(request_id=9, S=4, eta=0.0, seed=3), now=0.0)
    eng.tick(now=0.001)
    fr = eng.last_frame
    assert set(fr) == FLIGHT_FRAME_KEYS - {"record"}
    vals = np.asarray(fr["values"])
    assert vals.shape == (SLOTS, len(PROBE_COLUMNS))
    ent = fr["slots"][0]
    assert ent["request_id"] == 9 and ent["k"] == 0
    assert fr["slots"][1] is None           # second slot unoccupied
    row = vals[0]
    assert row[COL["eps_rms"]] > 0.0
    assert row[COL["finite_frac"]] == 1.0
    for c in ("x0_min", "x0_max", "x0_mean", "defect"):
        assert math.isnan(row[COL[c]])      # disabled = "not computed"
    # the defect column is computed on-device every tick (the k=0 frame
    # compares against the zeroed carry — the HOST accumulators discard
    # it via the slot.k >= 1 gate, asserted in the quality test below)
    full = _engine(probes=True)
    full.submit(SampleRequest(request_id=1, S=4, eta=0.0, seed=1), now=0.0)
    full.tick(now=0.001)
    full.tick(now=0.002)
    d = np.asarray(full.last_frame["values"])[0][COL["defect"]]
    assert math.isfinite(d) and d >= 0.0


def test_sample_results_carry_quality_summaries():
    eng = _engine(probes=True)
    for r in _run_virtual(eng, _reqs(3, S=5)):
        q = r.quality
        assert q is not None and q["frames"] == 5
        assert q["finite_frac_min"] == 1.0
        assert q["eps_rms_last"] > 0.0
        assert q["defect_max"] is not None and q["defect_max"] >= 0.0
        assert q["defect_mean"] is not None
    for r in _run_virtual(_engine(), _reqs(2)):
        assert r.quality is None


def test_set_probes_without_spec_is_a_loud_error():
    eng = _engine()
    with pytest.raises(RuntimeError, match="probes"):
        eng.set_probes(True)
    eng.set_probes(False)                   # no-op: allowed
    assert eng.stats()["probes"] is None


def test_mega_plus_probes_is_a_loud_error():
    from repro import diffusion_lm as dlm
    from repro.models.common import ArchConfig
    arch = ArchConfig(name="probe-mega-test", family="dense", n_layers=2,
                      d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                      vocab=50)
    cfg = dlm.DiffusionLMConfig(arch=arch, time_dim=32, latent_dim=32)
    params = dlm.init_params(jax.random.PRNGKey(0), cfg)
    slots, seq = 2, 64
    shape = (seq, cfg.latent_dim)
    eps = dlm.make_tile_eps_fn(params, cfg, slots, seq)
    with pytest.raises(ValueError, match="mega"):
        ContinuousBatchingEngine(SCH, eps, shape, slots=slots, probes=True)
    eng = ContinuousBatchingEngine(SCH, eps, shape, slots=slots,
                                   use_mega=False, probes=True)
    assert not eng.use_mega and eng.probe_spec is not None


# ------------------------------------------------------ flight recorder
def _frame(tick, values, slots_map, pool=0):
    return {"tick": tick, "now": 0.001 * tick, "pool": pool,
            "slots": slots_map, "values": values}


def _row(eps_rms=1.0, finite=1.0, defect=0.01):
    r = [0.0] * len(PROBE_COLUMNS)
    r[COL["eps_rms"]] = eps_rms
    r[COL["finite_frac"]] = finite
    r[COL["defect"]] = defect
    return r


def test_flight_ring_capacity_and_memory_only_mode():
    fl = FlightRecorder(3, pool_id=1)
    for i in range(5):
        fl.record(_frame(i, [_row()], [None], pool=1))
    assert [f["tick"] for f in fl.frames()] == [2, 3, 4]
    assert fl.dump("anything") is None      # no out_dir: ring only
    snap = fl.snapshot()
    assert snap["pool"] == 1 and snap["capacity"] == 3
    assert snap["columns"] == list(PROBE_COLUMNS)
    with pytest.raises(ValueError):
        FlightRecorder(0)


def test_flight_dump_roundtrip_nan_cleaning_and_attribution(tmp_path):
    fl = FlightRecorder(8, pool_id=2, out_dir=str(tmp_path))
    ent = [{"slot": 0, "request_id": 7, "k": 3}]
    nan_row = _row(eps_rms=float("nan"), finite=0.25)
    fl.record(_frame(10, [_row()], ent, pool=2))
    fl.record(_frame(11, [nan_row], ent, pool=2))
    path = fl.dump("quarantine", error="boom", pump=42)
    assert path is not None and "pool2" in path and "quarantine" in path
    header, frames = read_flight(path)
    assert set(header) == FLIGHT_HEADER_KEYS
    assert header["reason"] == "quarantine"
    assert header["frames"] == 2 and len(frames) == 2
    assert header["context"] == {"error": "boom", "pump": 42}
    # NaN floats serialize as null; the attribution pins (pool, slot,
    # step) from the finite_frac drop
    assert frames[1]["values"][0][COL["eps_rms"]] is None
    attr = header["attribution"]
    assert (attr["pool"], attr["slot"], attr["step"]) == (2, 0, 3)
    assert attr["request_id"] == 7 and attr["tick"] == 11
    assert fl.dumps == 1 and fl.dump_paths == [path]
    # a frame file with no header is a loud error
    bare = tmp_path / "noheader.jsonl"
    bare.write_text('{"record": "frame", "tick": 0}\n')
    with pytest.raises(ValueError, match="header"):
        read_flight(str(bare))


def test_attribute_nonfinite_skips_empty_slots_and_finite_frames():
    frames = [
        _frame(0, [_row(), _row()], [None, None]),          # unoccupied
        _frame(1, [_row(finite=0.5), _row()],
               [None, {"slot": 1, "request_id": 4, "k": 2}]),
    ]
    # slot 0's drop is unattributable (no resident) — slot 1 is finite,
    # so nothing is attributed in these frames
    assert attribute_nonfinite(frames) is None
    frames.append(_frame(2, [_row(), _row(finite=0.75)],
                         [None, {"slot": 1, "request_id": 4, "k": 3}]))
    attr = attribute_nonfinite(frames)
    assert (attr["slot"], attr["step"], attr["request_id"]) == (1, 3, 4)


def test_detect_weight_corruption_jump_vs_smooth_drift():
    ent = lambda k: [{"slot": 0, "request_id": 5, "k": k}]
    smooth = [_frame(i, [_row(eps_rms=1.0 + 0.1 * i)], ent(i))
              for i in range(6)]
    assert detect_weight_corruption(smooth) is None
    jump = smooth + [_frame(6, [_row(eps_rms=9.0)], ent(6))]
    det = detect_weight_corruption(jump)
    assert det is not None and det["tick"] == 6 and det["slot"] == 0
    assert det["ratio"] == pytest.approx(9.0 / 1.5)
    # factor is a dial: a 6x jump is invisible at factor=10
    assert detect_weight_corruption(jump, factor=10.0) is None
    # fresh request ids never compare across requests
    other = [_frame(0, [_row(eps_rms=0.1)],
                    [{"slot": 0, "request_id": 1, "k": 0}]),
             _frame(1, [_row(eps_rms=5.0)],
                    [{"slot": 0, "request_id": 2, "k": 0}])]
    assert detect_weight_corruption(other) is None


def test_engine_dumps_frames_into_its_flight_ring(tmp_path):
    fl = FlightRecorder(16, pool_id=0, out_dir=str(tmp_path))
    eng = _engine(probes=True, flight=fl)
    _run_virtual(eng, _reqs(2, S=4))
    assert len(fl.frames()) == eng.stats()["probe_frames"] > 0
    path = fl.dump("test")
    header, frames = read_flight(path)
    assert header["pool"] == 0
    assert all(set(f) == FLIGHT_FRAME_KEYS for f in frames)
