"""Tests for the continuous-batching scheduler (ISSUE 2).

Covers the acceptance criteria:
  * per-row-coefficient sampler_step vs the scalar path: BIT-EXACT at
    eta=0 (uniform rows == lockstep kernel), distribution-level tolerance
    at eta>0 (independent noise streams);
  * per-row kernel vs its pure-jnp oracle (allclose sweeps; software PRNG
    bit-exact);
  * scheduler end-to-end: mixed-S request loads produce per-request
    outputs bit-identical (eta=0) to single-request core.sample at the
    same S;
  * the tick function is compiled ONCE per engine — admission, retirement
    and arbitrary slot-content churn never retrace;
  * the eta=0 (deterministic) tick contains no PRNG ops at the jaxpr
    level;
  * deadlines, preview streaming, DiffusionSampler._bucket_for /
    _chunk_plan edge cases, and the tile-aware diffusion-LM eps model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SamplerConfig, StepStates, make_schedule, sample,
                        sample_step, slot_tile_step, step_table)
from repro.kernels.sampler_step import ops as tile_ops
from repro.kernels.sampler_step.ref import (sampler_rows_noise,
                                            sampler_step_rows_ref)
from repro.serving import DiffusionSampler
from repro.serving.scheduler import ContinuousBatchingEngine, SampleRequest

SCH = make_schedule("linear", T=1000)


def analytic_eps(sch, mu=2.0, s=0.5):
    def eps_fn(x, t):
        a = sch.alpha_bar[t].reshape((-1,) + (1,) * (x.ndim - 1))
        return (x - jnp.sqrt(a) * mu) * jnp.sqrt(1 - a) / (1 - a + a * s * s)
    return eps_fn


def slot_aware_eps(sch, s=1.0):
    """Elementwise analytic model consuming the slot-tile view directly."""
    def eps_fn(x2, t):
        rps = x2.shape[0] // t.shape[0]
        a = jnp.repeat(sch.alpha_bar[t], rps)[:, None]
        return x2 * jnp.sqrt(1 - a) / (1 - a + a * s * s)
    eps_fn.slot_tile_aware = True
    return eps_fn


def _slot_batch(B, shape, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    x = jax.random.normal(ks[0], (B,) + shape)
    e = jax.random.normal(ks[1], (B,) + shape)
    x2, n = tile_ops.to_slot_tile_layout(x)
    e2, _ = tile_ops.to_slot_tile_layout(e)
    return x, e, x2, e2, n


# ------------------------------------------------- per-row kernel vs oracle
@pytest.mark.parametrize("clip", [None, 1.0])
@pytest.mark.parametrize("stochastic", [False, True])
@pytest.mark.parametrize("shape", [(5,), (7, 23), (16, 16, 3)])
def test_sampler_step_rows_vs_oracle(shape, stochastic, clip):
    B = 3
    _, _, x2, e2, _ = _slot_batch(B, shape)
    rps = x2.shape[0] // B
    coefs = jnp.asarray(np.random.RandomState(0).uniform(0.1, 1.0, (B, 5)),
                        jnp.float32)
    rows = tile_ops.expand_slot_coefs(coefs, rps)
    seeds = tile_ops.derive_row_seeds(
        jnp.arange(B, dtype=jnp.int32) * 7 + 1, rps) if stochastic else None
    out = tile_ops.sampler_step_rows(x2, e2, rows, seeds, clip=clip,
                                     stochastic=stochastic, want_x0=True)
    ref = sampler_step_rows_ref(x2, e2, rows, seeds, clip=clip,
                                stochastic=stochastic, want_x0=True)
    for o, r in zip(out, ref):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   atol=2e-5, rtol=2e-5)


def test_row_noise_field_bit_exact_and_row_distinct():
    """Software per-row PRNG: kernel == oracle bitwise; rows and seeds give
    distinct streams; the field is tile-placement invariant by design."""
    R, C = 24, 256
    seeds = jnp.arange(R, dtype=jnp.int32) * 13 + 5
    rows = jnp.tile(jnp.asarray([[0., 0., 1., 1., 0., 0., 0., 0.]],
                                jnp.float32), (R, 1))
    out = tile_ops.sampler_step_rows(jnp.zeros((R, C)), jnp.zeros((R, C)),
                                     rows, seeds, stochastic=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(sampler_rows_noise(seeds, C)))
    z = np.asarray(out)
    assert np.abs(z[0] - z[1]).max() > 0.1          # distinct rows
    z2 = np.asarray(sampler_rows_noise(seeds + 1, C))
    assert np.abs(z - z2).max() > 0.1               # distinct seeds
    assert abs(z.mean()) < 0.05 and abs(z.std() - 1.0) < 0.05


def test_per_row_eta0_bit_exact_vs_scalar_kernel():
    """Satellite: uniform per-row coefficients reproduce the scalar
    (lockstep) deterministic kernel BITWISE — same fused arithmetic."""
    B = 4
    _, _, x2, e2, _ = _slot_batch(B, (33, 9))
    rps = x2.shape[0] // B
    cvec = jnp.asarray([0.97, 0.12, 0.0, 0.95, 0.31], jnp.float32)
    rows = tile_ops.expand_slot_coefs(jnp.tile(cvec[None], (B, 1)), rps)
    a = tile_ops.sampler_step_tiles(x2, e2, cvec)
    b = tile_ops.sampler_step_rows(x2, e2, rows)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # clip path too
    a = tile_ops.sampler_step_tiles(x2, e2, cvec, clip=1.0)
    b = tile_ops.sampler_step_rows(x2, e2, rows, clip=1.0)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_per_row_eta_pos_matches_scalar_in_distribution():
    """Satellite: at eta>0 the per-row path uses different (per-row) noise
    streams than the scalar path — agreement is statistical, not bitwise."""
    B, shape = 1, (16384,)
    _, _, x2, e2, _ = _slot_batch(B, shape)
    rps = x2.shape[0] // B
    cvec = jnp.asarray([0.95, 0.08, 0.12, 0.95, 0.31], jnp.float32)
    rows = tile_ops.expand_slot_coefs(jnp.tile(cvec[None], (B, 1)), rps)
    seeds = tile_ops.derive_row_seeds(jnp.asarray([3], jnp.int32), rps)
    a = np.asarray(tile_ops.sampler_step_tiles(x2, e2, cvec, seed=11,
                                               stochastic=True))
    b = np.asarray(tile_ops.sampler_step_rows(x2, e2, rows, seeds,
                                              stochastic=True))
    assert np.abs(a - b).max() > 1e-3   # genuinely different streams
    np.testing.assert_allclose(a.mean(), b.mean(), atol=0.01)
    np.testing.assert_allclose(a.std(), b.std(), atol=0.01)


def test_slot_tile_layout_round_trip():
    for shape in [(5,), (7, 23), (8, 256), (4, 4, 4)]:
        x = jax.random.normal(jax.random.PRNGKey(1), (3,) + shape)
        x2, n = tile_ops.to_slot_tile_layout(x)
        assert x2.shape[0] % tile_ops.slot_rows(shape) == 0
        np.testing.assert_array_equal(
            np.asarray(tile_ops.from_slot_tile_layout(x2, n, x.shape)),
            np.asarray(x))


# ------------------------------------------------- single-step core API
def test_sample_step_replays_tile_resident_scan_bitwise():
    """Driving sample_step over a request's step_table reproduces the
    whole-trajectory tile-resident scan bit-for-bit (eta=0)."""
    cfg = SamplerConfig(S=20)
    eps = analytic_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (1, 7, 23))
    ref = sample(SCH, eps, xT, cfg, tile_resident=True)
    tab = step_table(SCH, cfg)
    x = xT
    for k in range(cfg.S):
        states = StepStates(
            t=jnp.asarray([tab["t"][k]], jnp.int32),
            c_x0=jnp.asarray([tab["c_x0"][k]]),
            c_dir=jnp.asarray([tab["c_dir"][k]]),
            c_noise=jnp.asarray([tab["c_noise"][k]]),
            sqrt_a_t=jnp.asarray([tab["sqrt_a_t"][k]]),
            sqrt_1m_a_t=jnp.asarray([tab["sqrt_1m_a_t"][k]]))
        x = sample_step(SCH, eps, x, states)
    np.testing.assert_array_equal(np.asarray(x), np.asarray(ref))


# --------------------------------------------------- engine end-to-end
def test_engine_mixed_S_bitwise_vs_core_sample():
    """Acceptance: per-request outputs of a mixed-S continuous load are
    bit-identical (eta=0) to single-request core.sample at the same S."""
    shape = (7, 23)
    eps = analytic_eps(SCH)
    eng = ContinuousBatchingEngine(SCH, eps, shape, slots=4)
    reqs = [SampleRequest(request_id=i, S=s, seed=100 + i)
            for i, s in enumerate([10, 20, 5, 50, 15, 30, 7, 12])]
    results = eng.serve(reqs)
    assert len(results) == len(reqs)
    for r in results:
        req = reqs[r.request_id]
        xT = jax.random.normal(jax.random.PRNGKey(req.seed), (1,) + shape)
        ref = sample(SCH, eps, xT, SamplerConfig(S=req.S),
                     tile_resident=True)
        # two compiled programs (the engine's rows tick vs the tile scan):
        # XLA fuses the Eq. 12 update differently in each, so they agree
        # to a few float32 ulp, not bit for bit
        np.testing.assert_allclose(r.x0, np.asarray(ref)[0], rtol=1e-5,
                                   atol=1e-6)


def test_engine_slot_tile_aware_model_matches_adapter_model():
    """slot_tile_aware eps (no per-tick repack) == adapter-path eps."""
    shape = (512,)
    reqs = lambda: [SampleRequest(request_id=i, S=s, seed=i)
                    for i, s in enumerate([5, 9, 13, 7])]
    out = {}
    for name, eps in [("nat", analytic_eps(SCH, mu=0.0, s=1.0)),
                      ("tile", slot_aware_eps(SCH))]:
        eng = ContinuousBatchingEngine(SCH, eps, shape, slots=2)
        out[name] = {r.request_id: r.x0 for r in eng.serve(reqs())}
    for i in out["nat"]:
        np.testing.assert_array_equal(out["nat"][i], out["tile"][i])


def test_engine_tick_compiled_once_under_churn():
    """Acceptance: one trace per engine — slot churn never recompiles."""
    eng = ContinuousBatchingEngine(SCH, analytic_eps(SCH), (100,), slots=3)
    rng = np.random.RandomState(0)
    for wave in range(3):   # three admission waves, ragged S mix
        for i in range(5):
            eng.submit(SampleRequest(request_id=wave * 10 + i,
                                     S=int(rng.randint(2, 25)),
                                     tau_kind=("quadratic" if i % 2 else
                                               "linear"),
                                     seed=i))
        eng.run()
    assert eng._traces == 1
    assert eng.stats()["compiled_ticks"] == 1


def test_engine_stochastic_statistics_match_classic_sampler():
    eps = analytic_eps(SCH, mu=2.0, s=0.5)
    eng = ContinuousBatchingEngine(SCH, eps, (512,), slots=8,
                                   stochastic=True)
    res = eng.serve([SampleRequest(request_id=i, S=25, eta=1.0, seed=i)
                     for i in range(16)])
    xs = np.stack([r.x0 for r in res])
    ref = sample(SCH, eps, jax.random.normal(jax.random.PRNGKey(7),
                                             (16, 512)),
                 SamplerConfig(S=25, eta=1.0), rng=jax.random.PRNGKey(8))
    np.testing.assert_allclose(xs.mean(), float(np.asarray(ref).mean()),
                               atol=0.05)
    np.testing.assert_allclose(xs.std(), float(np.asarray(ref).std()),
                               atol=0.05)
    assert eng._traces == 1   # mixed stochastic load, still one program


def test_engine_rejects_stochastic_on_deterministic():
    eng = ContinuousBatchingEngine(SCH, analytic_eps(SCH), (8,), slots=1)
    with pytest.raises(ValueError):
        eng.submit(SampleRequest(request_id=0, S=5, eta=1.0))


def test_engine_deadline_drop_and_miss_flag():
    eng = ContinuousBatchingEngine(SCH, analytic_eps(SCH), (64,), slots=1)
    eng.submit(SampleRequest(request_id=0, S=5, deadline=-1.0), now=0.0)
    eng.submit(SampleRequest(request_id=1, S=5), now=0.0)
    res = {r.request_id: r for r in eng.run()}
    assert res[0].dropped and res[0].deadline_missed and res[0].x0 is None
    assert not res[1].dropped and res[1].x0 is not None
    assert eng.stats()["dropped"] == 1


def test_engine_backpressure_rejection_returns_dropped_results():
    """serve() must return exactly one result per submitted request even
    when the queue depth bound rejects some — rejections come back as
    dropped results, not silent holes."""
    eng = ContinuousBatchingEngine(SCH, analytic_eps(SCH), (32,), slots=1,
                                   max_queue=2)
    reqs = [SampleRequest(request_id=i, S=3, seed=i) for i in range(6)]
    res = {r.request_id: r for r in eng.serve(reqs, now=0.0)}
    assert set(res) == {r.request_id for r in reqs}
    # all 6 submitted before any tick: 2 fit the depth bound, 4 reject
    rejected = [r for r in res.values() if r.dropped]
    assert len(rejected) == 4 and all(r.x0 is None for r in rejected)
    assert all(not r.deadline_missed for r in rejected)
    done = [r for r in res.values() if not r.dropped]
    assert len(done) == 2 and all(r.x0 is not None for r in done)


def test_engine_preview_streaming():
    got = []
    eng = ContinuousBatchingEngine(SCH, analytic_eps(SCH), (100,), slots=2,
                                   preview=True)
    eng.serve([SampleRequest(
        request_id=0, S=10, seed=1, preview_every=3,
        on_preview=lambda rid, k, x0: got.append((rid, k, x0)))])
    assert [(g[0], g[1]) for g in got] == [(0, 3), (0, 6), (0, 9)]
    for _, _, x0 in got:
        assert x0.shape == (100,) and np.isfinite(x0).all()


# ------------------------------------------------------ jaxpr inspection
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


def _prims_of(fn, *args):
    jx = jax.make_jaxpr(fn)(*args)
    return _collect_prims(jx.jaxpr, [])


def _demo_states(B, stochastic):
    z = jnp.zeros((B,), jnp.float32)
    return StepStates(t=jnp.ones((B,), jnp.int32), c_x0=z + 1.0, c_dir=z,
                      c_noise=z, sqrt_a_t=z + 1.0, sqrt_1m_a_t=z,
                      seed=jnp.ones((B,), jnp.int32) if stochastic
                      else None)


def test_deterministic_tick_has_no_prng_ops():
    """Acceptance: the eta=0 per-row tick contains no PRNG ops at all."""
    eps = slot_aware_eps(SCH)
    B = 4
    x2 = jnp.zeros((B * tile_ops.slot_rows((100,)), tile_ops.TILE_C))
    prims = _prims_of(
        lambda x, st: slot_tile_step(eps, x, st, (100,), stochastic=False),
        x2, _demo_states(B, False))
    bad = [p for p in prims if "threefry" in p or "random" in p
           or "prng" in p]
    assert not bad, bad


def test_stochastic_tick_keeps_host_randomness_out():
    """Stochastic ticks draw noise IN-KERNEL from precomputed seeds: no
    jax.random/threefry in the tick program either."""
    eps = slot_aware_eps(SCH)
    B = 4
    x2 = jnp.zeros((B * tile_ops.slot_rows((100,)), tile_ops.TILE_C))
    prims = _prims_of(
        lambda x, st: slot_tile_step(eps, x, st, (100,), stochastic=True),
        x2, _demo_states(B, True))
    bad = [p for p in prims if "threefry" in p or "random_bits" in p]
    assert not bad, bad


# ------------------------------------------- DiffusionSampler satellites
def _svc(buckets=(4, 8, 16, 32)):
    return DiffusionSampler(SCH, analytic_eps(SCH), (4,), batch_size=32,
                            bucket_sizes=buckets)


def test_bucket_for_edges():
    svc = _svc()
    assert svc._bucket_for(0) == 4          # degenerate: smallest rung
    assert svc._bucket_for(16) == 16        # exactly at a rung
    assert svc._bucket_for(17) == 32        # just above a rung
    assert svc._bucket_for(100) == 32       # above the top rung: clamp


def test_chunk_plan_ragged_tail_split():
    svc = _svc()
    assert svc._chunk_plan(17) == [16, 4]     # not one padded 32
    assert svc._chunk_plan(16) == [16]
    assert svc._chunk_plan(33) == [32, 4]
    assert svc._chunk_plan(3) == [4]
    assert svc._chunk_plan(0) == []
    assert sum(svc._chunk_plan(100)) >= 100


def test_serve_zero_and_ragged():
    svc = _svc()
    out, stats = svc.serve(0, SamplerConfig(S=2))
    assert out.shape == (0, 4) and stats["batches"] == 0
    out, stats = svc.serve(17, SamplerConfig(S=2))
    assert out.shape == (17, 4)
    assert stats["batches"] == 2            # 16 + 4, not a single 32
    assert np.isfinite(np.asarray(out)).all()


# ------------------------------------------------ diffusion-LM tile_aware
def _tiny_dlm():
    from repro import diffusion_lm as dlm
    from repro.models.common import ArchConfig
    arch = ArchConfig(name="dlm-test", family="dense", n_layers=2,
                      d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                      vocab=50)
    cfg = dlm.DiffusionLMConfig(arch=arch, time_dim=32, latent_dim=32)
    params = dlm.init_params(jax.random.PRNGKey(0), cfg)
    return dlm, cfg, params


def test_diffusion_lm_tile_aware_matches_adapter():
    """Satellite: the tile-aware diffusion-LM eps (seq*latent aligned to
    the 8x256 granule) matches the natural-shape path on the scan."""
    dlm, cfg, params = _tiny_dlm()
    B, seq = 2, 64                           # 64*32 = 2048-aligned
    xT = jax.random.normal(jax.random.PRNGKey(1), (B, seq, cfg.latent_dim))
    scfg = SamplerConfig(S=4)
    ref = sample(SCH, dlm.make_eps_fn(params, cfg), xT, scfg)
    tile_fn = dlm.make_tile_eps_fn(params, cfg, B, seq)
    assert tile_fn.tile_aware and tile_fn.slot_tile_aware
    out = sample(SCH, tile_fn, xT, scfg, tile_resident=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def _scan_body_prims(fn, *args):
    body = []

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                body.extend(_collect_prims(eqn.params["jaxpr"].jaxpr, []))
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    find(v.jaxpr)

    find(jax.make_jaxpr(fn)(*args).jaxpr)
    return body


def test_diffusion_lm_tile_aware_scan_body_repack_free():
    """The aligned tile-aware model removes the per-step eps repack: no
    pad/gather of the state in the scan body (the trunk's own internal
    slices — attention head splits etc. — are model compute, not layout
    traffic). Contrast: an UNALIGNED latent on the adapter path must pad
    every step."""
    dlm, cfg, params = _tiny_dlm()
    B, seq = 2, 64
    tile_fn = dlm.make_tile_eps_fn(params, cfg, B, seq)
    xT = jax.random.normal(jax.random.PRNGKey(1), (B, seq, cfg.latent_dim))
    body = _scan_body_prims(
        lambda x: sample(SCH, tile_fn, x, SamplerConfig(S=3),
                         tile_resident=True), xT)
    banned = {"pad", "gather"}
    assert not banned & set(body), sorted(banned & set(body))

    nat_fn = dlm.make_eps_fn(params, cfg)      # adapter path, 63*32 latent
    xT_odd = jax.random.normal(jax.random.PRNGKey(1),
                               (B, 63, cfg.latent_dim))
    body_odd = _scan_body_prims(
        lambda x: sample(SCH, nat_fn, x, SamplerConfig(S=3),
                         tile_resident=True), xT_odd)
    assert "pad" in body_odd


def test_diffusion_lm_unaligned_raises():
    dlm, cfg, params = _tiny_dlm()
    with pytest.raises(ValueError):
        dlm.make_tile_eps_fn(params, cfg, 2, 63)   # 63*32 not aligned


def test_engine_runs_diffusion_lm_tile_aware():
    """The scheduler ticks a slot_tile_aware diffusion-LM with mixed S and
    matches the single-request tile-resident scan."""
    dlm, cfg, params = _tiny_dlm()
    slots, seq = 2, 64
    shape = (seq, cfg.latent_dim)
    eng = ContinuousBatchingEngine(
        SCH, dlm.make_tile_eps_fn(params, cfg, slots, seq), shape,
        slots=slots)
    reqs = [SampleRequest(request_id=i, S=s, seed=40 + i)
            for i, s in enumerate([3, 5, 4])]
    results = eng.serve(reqs)
    assert len(results) == 3 and eng._traces == 1
    one_fn = dlm.make_tile_eps_fn(params, cfg, 1, seq)
    for r in results:
        req = reqs[r.request_id]
        xT = jax.random.normal(jax.random.PRNGKey(req.seed), (1,) + shape)
        ref = sample(SCH, one_fn, xT, SamplerConfig(S=req.S),
                     tile_resident=True)
        # batch-2 vs batch-1 eps matmuls differ in reduction order, and the
        # untrained trunk amplifies magnitudes — compare relatively
        np.testing.assert_allclose(r.x0, np.asarray(ref)[0],
                                   atol=1e-3, rtol=5e-4)
