"""Tests for the unified SamplerPlan front door (ISSUE 3).

Covers the acceptance criteria:
  * ONE plan drives all three backends ('jnp', 'tile_resident', 'rows')
    with deterministic (eta=0) outputs BIT-IDENTICAL across them —
    uniform / quadratic / explicit-learned tau, clip policy included;
    multistep (order>1) plans are bit-identical between 'jnp' and 'rows'
    and fp32-tight on 'tile_resident' (XLA FMA-contraction freedom);
  * deterministic plans trace NO PRNG ops on any backend (jaxpr-asserted);
  * the continuous-batching scheduler accepts heterogeneous per-slot
    plans — mixed tau spacing, sigma schedule, and solver order — with
    ZERO retraces per engine, and order-1 results replay
    plan.run(backend='rows') bit-for-bit;
  * ODE encode/decode round-trip (paper §4.3): plan.encode then plan.run
    at eta=0 reconstructs x0 within tolerance, including quadratic-tau
    and multistep plans;
  * every deprecated wrapper (ddim_sample, ddpm_sample, multistep_sample,
    fused_ddim_step) warns and is bit-identical (eta=0) or
    identically-seeded-equal to its plan-based replacement;
  * spec validation, plan hashing, and the plan-keyed DiffusionSampler
    program cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (SamplerConfig, make_schedule, sample,
                        trajectory_coefficients)
from repro.sampling import (MAX_ORDER, SamplerPlan, SigmaSpec, TauSpec,
                            X0Policy)
from repro.serving import DiffusionSampler
from repro.serving.scheduler import ContinuousBatchingEngine, SampleRequest

SCH = make_schedule("linear", T=1000)
BACKENDS = ("jnp", "tile_resident", "rows")


def analytic_eps(sch, mu=2.0, s=0.5):
    def eps_fn(x, t):
        a = sch.alpha_bar[t].reshape((-1,) + (1,) * (x.ndim - 1))
        return (x - jnp.sqrt(a) * mu) * jnp.sqrt(1 - a) / (1 - a + a * s * s)
    return eps_fn


EPS = analytic_eps(SCH)


# ------------------------------------------------------------ specs / build
def test_tau_spec_validation():
    with pytest.raises(ValueError):
        TauSpec.explicit([5, 5, 10])         # not strictly increasing
    with pytest.raises(ValueError):
        TauSpec.explicit([0, 10])            # below the model grid
    with pytest.raises(ValueError):
        TauSpec.uniform(0)
    with pytest.raises(ValueError):
        TauSpec(kind="nope", S=5)
    with pytest.raises(ValueError):          # explicit tau beyond T
        SamplerPlan.build(SCH, tau=TauSpec.explicit([10, 2000]))
    # the legacy 'linear' spelling normalizes to 'uniform'
    assert TauSpec(kind="linear", S=5) == TauSpec.uniform(5)


def test_sigma_spec_validation():
    with pytest.raises(ValueError):
        SigmaSpec.from_eta(0.5, sigma_hat=True)   # sigma_hat needs eta=1
    with pytest.raises(ValueError):
        SigmaSpec(kind="eta", eta=-0.1)
    with pytest.raises(ValueError):               # schedule length != S
        SamplerPlan.build(SCH, tau=10, sigma=SigmaSpec.schedule([0.0] * 7))
    with pytest.raises(ValueError):               # Eq. 16 feasibility bound
        SamplerPlan.build(SCH, tau=5,
                          sigma=SigmaSpec.explicit([9.9] * 5))
    with pytest.raises(ValueError):
        X0Policy(clip=-1.0)


def test_order_validation():
    with pytest.raises(ValueError):
        SamplerPlan.build(SCH, tau=10, order=MAX_ORDER + 1)
    with pytest.raises(ValueError):               # multistep must be det.
        SamplerPlan.build(SCH, tau=10, sigma=1.0, order=2)


def test_plan_hash_and_equality():
    a = SamplerPlan.build(SCH, tau=20, sigma=0.5, x0=1.0)
    b = SamplerPlan.build(SCH, tau=20, sigma=0.5, x0=1.0)
    c = SamplerPlan.build(SCH, tau=20, sigma=0.5)
    assert a == b and hash(a) == hash(b)
    assert a != c
    other = make_schedule("cosine", T=1000)
    assert SamplerPlan.build(other, tau=20) != SamplerPlan.build(SCH, tau=20)


def test_plan_compiles_one_coefficient_program():
    """trajectory_coefficients is now a VIEW of the plan table — same
    values, legacy trajectory order."""
    cfg = SamplerConfig(S=10, eta=0.7, tau_kind="quadratic")
    legacy = trajectory_coefficients(SCH, cfg)
    tab = cfg.to_plan(SCH).steps()
    for k in ("t", "c_x0", "c_dir", "c_noise", "sqrt_a_t", "sqrt_1m_a_t"):
        np.testing.assert_array_equal(np.asarray(legacy[k])[::-1], tab[k])
    assert tab["solver_w"].shape == (10, 1)
    np.testing.assert_array_equal(tab["solver_w"], 1.0)


def test_plan_last_step_and_determinism_flags():
    tab = SamplerPlan.build(SCH, tau=10).steps()
    # final row (k=S-1) jumps to t=0: c_x0 = sqrt(alpha_bar[0]) = 1
    np.testing.assert_allclose(tab["c_x0"][-1], 1.0, rtol=1e-6)
    assert SamplerPlan.build(SCH, tau=10).deterministic
    assert SamplerPlan.build(SCH, tau=10, sigma=0.3).stochastic
    # an eta schedule of all zeros IS deterministic
    assert SamplerPlan.build(
        SCH, tau=10, sigma=SigmaSpec.schedule([0.0] * 10)).deterministic


def test_explicit_sigma_reproduces_eta_plan_bitwise():
    """SigmaSpec.explicit with Eq. 16 values == the scalar-eta plan."""
    eta_plan = SamplerPlan.build(SCH, tau=8, sigma=0.6)
    # recover the sigmas the eta spec produced (sampling order -> traj.)
    sig = eta_plan.steps()["c_noise"][::-1]
    exp_plan = SamplerPlan.build(SCH, tau=8,
                                 sigma=SigmaSpec.explicit(sig.tolist()))
    rng = jax.random.PRNGKey(3)
    xT = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    np.testing.assert_array_equal(
        np.asarray(eta_plan.run(EPS, xT, rng)),
        np.asarray(exp_plan.run(EPS, xT, rng)))


# ------------------------------------------------- backend tri-identity
@pytest.mark.parametrize("build_kw", [
    dict(tau=12),
    dict(tau=TauSpec.quadratic(15)),
    dict(tau=TauSpec.explicit([3, 40, 200, 550, 1000])),
    dict(tau=12, x0=1.0),
], ids=["uniform", "quadratic", "explicit-learned", "clip"])
def test_deterministic_plan_bit_identical_across_backends(build_kw):
    """Acceptance: one eta=0 plan -> bit-identical x0 on every backend."""
    plan = SamplerPlan.build(SCH, **build_kw)
    xT = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 23))
    outs = [np.asarray(plan.run(EPS, xT, backend=b)) for b in BACKENDS]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])
    assert np.isfinite(outs[0]).all()


@pytest.mark.parametrize("order", [2, 3])
def test_multistep_plan_backend_equivalence(order):
    """order>1: 'jnp' and 'rows' are bit-identical; 'tile_resident' is
    fp32-tight (XLA may contract the history FMA chain differently)."""
    plan = SamplerPlan.build(SCH, tau=10, order=order)
    xT = jax.random.normal(jax.random.PRNGKey(1), (4, 7, 23))
    a = np.asarray(plan.run(EPS, xT, backend="jnp"))
    b = np.asarray(plan.run(EPS, xT, backend="tile_resident"))
    c = np.asarray(plan.run(EPS, xT, backend="rows"))
    np.testing.assert_array_equal(a, c)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_return_trajectory_all_backends():
    plan = SamplerPlan.build(SCH, tau=6)
    xT = jax.random.normal(jax.random.PRNGKey(0), (3, 5))
    for b in BACKENDS:
        x0, traj = plan.run(EPS, xT, backend=b, return_trajectory=True)
        assert traj.shape == (7, 3, 5)
        np.testing.assert_array_equal(np.asarray(traj[0]), np.asarray(xT))
        np.testing.assert_array_equal(np.asarray(traj[-1]), np.asarray(x0))


def test_stochastic_plan_statistics_across_backends():
    """eta>0 backends use different noise streams — agreement is
    distributional: every backend must match the reference scan's
    moments at finite S."""
    plan = SamplerPlan.build(SCH, tau=50, sigma=1.0)
    xT = jax.random.normal(jax.random.PRNGKey(1), (8192, 2))
    ref = plan.run(EPS, xT, jax.random.PRNGKey(9), backend="jnp")
    for i, b in enumerate(("tile_resident", "rows")):
        out = plan.run(EPS, xT, jax.random.PRNGKey(10 + i), backend=b)
        np.testing.assert_allclose(float(out.mean()), float(ref.mean()),
                                   atol=0.05)
        np.testing.assert_allclose(float(out.std()), float(ref.std()),
                                   atol=0.05)


def test_eta_schedule_plan_runs_and_uses_noise_only_where_scheduled():
    """Per-step eta schedule: sigma>0 only on early (large-t) steps; the
    plan is stochastic, runs on all backends, and its late steps have
    c_noise == 0 exactly."""
    etas = [0.0] * 5 + [1.0] * 5          # trajectory order: noise at big t
    plan = SamplerPlan.build(SCH, tau=10, sigma=SigmaSpec.schedule(etas))
    tab = plan.steps()                     # sampling order: big t first
    assert (tab["c_noise"][:5] > 0).all() and (tab["c_noise"][5:] == 0).all()
    xT = jax.random.normal(jax.random.PRNGKey(0), (64, 2))
    for b in BACKENDS:
        out = plan.run(EPS, xT, jax.random.PRNGKey(2), backend=b)
        assert bool(jnp.isfinite(out).all())


def test_stochastic_plan_requires_rng():
    plan = SamplerPlan.build(SCH, tau=5, sigma=1.0)
    with pytest.raises(ValueError):
        plan.run(EPS, jnp.zeros((2, 2)))
    with pytest.raises(ValueError):
        plan.run(EPS, jnp.zeros((2, 2)), jax.random.PRNGKey(0),
                 backend="nope")


# ------------------------------------------------------- jaxpr inspection
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


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("order", [1, 2])
def test_deterministic_plan_traces_no_prng(backend, order):
    """Acceptance: a deterministic plan's program contains no PRNG ops on
    ANY backend (noise is skipped, not zero-scaled), at any order."""
    plan = SamplerPlan.build(SCH, tau=4, order=order)
    xT = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 23))
    prims = _collect_prims(
        jax.make_jaxpr(lambda x: plan.run(EPS, x, backend=backend))(
            xT).jaxpr, [])
    bad = [p for p in prims if "threefry" in p or "random" in p
           or "prng" in p]
    assert not bad, bad


# --------------------------------------------------------- encode / decode
@pytest.mark.parametrize("build_kw", [
    dict(tau=100),
    dict(tau=TauSpec.quadratic(100)),
    dict(tau=60, order=2),
], ids=["uniform", "quadratic", "multistep"])
def test_encode_decode_roundtrip(build_kw):
    """Paper §4.3 / Table 2: plan.encode then the deterministic plan.run
    reconstructs x0 — including on a quadratic-tau trajectory."""
    plan = SamplerPlan.build(SCH, **build_kw)
    data = 2.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(2), (128, 2))
    z = plan.encode(EPS, data)
    rec = plan.run(EPS, z)
    assert float(jnp.mean((rec - data) ** 2)) < 1e-3


def test_roundtrip_error_decreases_with_S():
    errs = []
    data = 2.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(2), (128, 2))
    for S in (10, 50, 200):
        plan = SamplerPlan.build(SCH, tau=S)
        rec = plan.run(EPS, plan.encode(EPS, data))
        errs.append(float(jnp.mean((rec - data) ** 2)))
    assert errs[0] > errs[1] > errs[2]


def test_encode_ignores_sigma_spec():
    """Encoding is the deterministic ODE direction: the sigma spec of the
    plan plays no role."""
    data = jax.random.normal(jax.random.PRNGKey(0), (8, 2))
    z0 = SamplerPlan.build(SCH, tau=20).encode(EPS, data)
    z1 = SamplerPlan.build(SCH, tau=20, sigma=1.0).encode(EPS, data)
    np.testing.assert_array_equal(np.asarray(z0), np.asarray(z1))


# ------------------------------------------------------ deprecated wrappers
def test_ddim_sample_wrapper_warns_and_matches_plan():
    from repro.core import ddim_sample
    xT = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    with pytest.warns(DeprecationWarning):
        old = ddim_sample(SCH, EPS, xT, S=20)
    new = SamplerPlan.build(SCH, tau=20).run(EPS, xT)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


def test_ddpm_sample_wrapper_warns_and_matches_plan():
    from repro.core import ddpm_sample
    xT = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    rng = jax.random.PRNGKey(5)
    with pytest.warns(DeprecationWarning):
        old = ddpm_sample(SCH, EPS, xT, rng, S=15, sigma_hat=True)
    new = SamplerPlan.build(SCH, tau=15,
                            sigma=SigmaSpec.ddpm(sigma_hat=True)).run(
        EPS, xT, rng)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


def test_multistep_sample_wrapper_warns_and_matches_plan():
    from repro.core import multistep_sample
    xT = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    with pytest.warns(DeprecationWarning):
        old = multistep_sample(SCH, EPS, xT, S=12, order=3)
    new = SamplerPlan.build(SCH, tau=12, order=3).run(EPS, xT)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


def test_multistep_plan_beats_euler_at_small_S():
    """The quality claim survives the migration: AB-2 at S=10 beats Euler
    DDIM at S=10 against the S=1000 reference."""
    eps_fn = analytic_eps(SCH, mu=0.0, s=1.0)
    xT = jax.random.normal(jax.random.PRNGKey(0), (8192, 2))
    ref = SamplerPlan.build(SCH, tau=1000).run(eps_fn, xT)
    e1 = SamplerPlan.build(SCH, tau=10).run(eps_fn, xT)
    e2 = SamplerPlan.build(SCH, tau=10, order=2).run(eps_fn, xT)
    assert (float(jnp.mean((e2 - ref) ** 2))
            < float(jnp.mean((e1 - ref) ** 2)))


def test_fused_ddim_step_shim_warns_and_routes_to_sampler_step():
    """Satellite: the legacy kernel entry warns, and its deterministic
    output equals the sampler_step kernel's (the ddim_step ref oracle
    stays as the regression pin in test_kernels.py)."""
    from repro.kernels import fused_ddim_step
    from repro.kernels.sampler_step.ops import fused_sampler_step
    x = jax.random.normal(jax.random.PRNGKey(0), (7, 333))
    e = jax.random.normal(jax.random.PRNGKey(1), (7, 333))
    args = (0.98, 0.15, 0.0, 0.97, 0.24)
    with pytest.warns(DeprecationWarning):
        old = fused_ddim_step(x, e, None, *args)
    new = fused_sampler_step(x, e, *args)
    np.testing.assert_array_equal(np.asarray(old), np.asarray(new))


def _warn_count(warnlist):
    return sum(1 for w in warnlist
               if issubclass(w.category, DeprecationWarning))


@pytest.mark.parametrize("wrapper", ["ddim_sample", "ddpm_sample",
                                     "multistep_sample", "fused_ddim_step"])
def test_deprecation_shims_warn_exactly_once(wrapper):
    """ISSUE 4 satellite — the warning CONTRACT, not just equivalence:
    each deprecated entry emits exactly ONE DeprecationWarning per call
    (no duplicate warns from nested shims)."""
    import warnings as _warnings
    xT = jax.random.normal(jax.random.PRNGKey(0), (4, 6))
    with _warnings.catch_warnings(record=True) as rec:
        _warnings.simplefilter("always")
        if wrapper == "ddim_sample":
            from repro.core import ddim_sample
            ddim_sample(SCH, EPS, xT, S=4)
        elif wrapper == "ddpm_sample":
            from repro.core import ddpm_sample
            ddpm_sample(SCH, EPS, xT, jax.random.PRNGKey(1), S=4)
        elif wrapper == "multistep_sample":
            from repro.core import multistep_sample
            multistep_sample(SCH, EPS, xT, S=4, order=2)
        else:
            from repro.kernels import fused_ddim_step
            e = jax.random.normal(jax.random.PRNGKey(1), (4, 6))
            fused_ddim_step(xT, e, None, 0.98, 0.15, 0.0, 0.97, 0.24)
    assert _warn_count(rec) == 1, [str(w.message) for w in rec]


@pytest.mark.parametrize("wrapper", ["ddim_sample", "ddpm_sample",
                                     "multistep_sample"])
def test_deprecation_shims_route_through_a_plan(wrapper, monkeypatch):
    """The sampler wrappers must execute via SamplerPlan.run — the one
    compiled coefficient program — not a private legacy scan."""
    import warnings as _warnings
    calls = []
    real_run = SamplerPlan.run

    def spy(self, *a, **kw):
        calls.append(self)
        return real_run(self, *a, **kw)

    monkeypatch.setattr(SamplerPlan, "run", spy)
    xT = jax.random.normal(jax.random.PRNGKey(0), (4, 6))
    with _warnings.catch_warnings():
        _warnings.simplefilter("ignore", DeprecationWarning)
        if wrapper == "ddim_sample":
            from repro.core import ddim_sample
            ddim_sample(SCH, EPS, xT, S=4)
            want = SamplerPlan.build(SCH, tau=4)
        elif wrapper == "ddpm_sample":
            from repro.core import ddpm_sample
            ddpm_sample(SCH, EPS, xT, jax.random.PRNGKey(1), S=4)
            want = SamplerPlan.build(SCH, tau=4, sigma=1.0)
        else:
            from repro.core import multistep_sample
            multistep_sample(SCH, EPS, xT, S=4, order=2)
            want = SamplerPlan.build(SCH, tau=4, order=2)
    assert len(calls) == 1 and calls[0] == want


def test_sample_adapter_matches_plan_bitwise():
    """core.sample is a thin adapter: identical outputs to the plan."""
    cfg = SamplerConfig(S=10, eta=0.5, tau_kind="quadratic", clip_x0=2.0)
    xT = jax.random.normal(jax.random.PRNGKey(0), (8, 6))
    rng = jax.random.PRNGKey(4)
    a = sample(SCH, EPS, xT, cfg, rng=rng)
    b = cfg.to_plan(SCH).run(EPS, xT, rng)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ----------------------------------------- scheduler: heterogeneous plans
def _plan_mix():
    return [
        SamplerPlan.build(SCH, tau=12),
        SamplerPlan.build(SCH, tau=TauSpec.quadratic(20)),
        SamplerPlan.build(SCH, tau=TauSpec.explicit(
            [3, 50, 200, 400, 800, 1000])),
        SamplerPlan.build(SCH, tau=9, order=2),
        SamplerPlan.build(SCH, tau=15, order=3),
    ]


def test_engine_heterogeneous_plans_zero_retraces_and_replay():
    """Acceptance: mixed tau spacing x solver order across resident slots,
    ONE compiled tick; every slot replays plan.run(backend='rows') to
    fp32 tolerance."""
    shape = (7, 23)
    eng = ContinuousBatchingEngine(SCH, EPS, shape, slots=3, max_order=3)
    plans = _plan_mix()
    reqs = [SampleRequest(request_id=i, plan=p, seed=100 + i)
            for i, p in enumerate(plans)]
    res = {r.request_id: r for r in eng.serve(reqs)}
    assert eng._traces == 1
    assert eng.stats()["max_order"] == 3
    for i, p in enumerate(plans):
        xT = jax.random.normal(jax.random.PRNGKey(100 + i), (1,) + shape)
        ref = np.asarray(p.run(EPS, xT, backend="rows"))[0]
        assert res[i].S == p.S
        if p.order == 1:
            # two compiled programs (the engine tick vs the plan's rows
            # scan) round the Eq. 12 update differently: a few f32 ulp
            np.testing.assert_allclose(res[i].x0, ref, rtol=1e-5, atol=1e-6)
        else:
            np.testing.assert_allclose(res[i].x0, ref, atol=2e-5, rtol=2e-5)


def test_engine_mixes_sigma_schedules_and_orders_one_trace():
    """A stochastic engine serves eta-schedule plans, multistep
    deterministic plans and legacy scalar-knob requests in one program."""
    eng = ContinuousBatchingEngine(SCH, EPS, (64,), slots=2,
                                   stochastic=True, max_order=2)
    p_sched = SamplerPlan.build(
        SCH, tau=10, sigma=SigmaSpec.schedule([1.0] * 5 + [0.0] * 5))
    p_ord = SamplerPlan.build(SCH, tau=8, order=2)
    res = eng.serve([SampleRequest(request_id=0, plan=p_sched, seed=1),
                     SampleRequest(request_id=1, plan=p_ord, seed=2),
                     SampleRequest(request_id=2, S=6, eta=1.0, seed=3)])
    assert eng._traces == 1 and len(res) == 3
    assert all(np.isfinite(r.x0).all() for r in res)


def test_engine_multistep_order1_rides_identically():
    """An order-1 request served by a multistep-capable engine must be
    bit-identical to the same request on a max_order=1 engine (its weight
    row is [1, 0, ...])."""
    shape = (100,)
    req = lambda: SampleRequest(request_id=0, S=9, seed=7)
    e1 = ContinuousBatchingEngine(SCH, EPS, shape, slots=2)
    e2 = ContinuousBatchingEngine(SCH, EPS, shape, slots=2, max_order=2)
    r1 = e1.serve([req()])[0]
    r2 = e2.serve([req()])[0]
    np.testing.assert_array_equal(r1.x0, r2.x0)


def test_engine_plan_validation():
    eng = ContinuousBatchingEngine(SCH, EPS, (8,), slots=1, max_order=2)
    with pytest.raises(ValueError):       # order beyond engine capacity
        eng.submit(SampleRequest(request_id=0,
                                 plan=SamplerPlan.build(SCH, tau=5,
                                                        order=3)))
    with pytest.raises(ValueError):       # foreign schedule
        other = make_schedule("cosine", T=1000)
        eng.submit(SampleRequest(request_id=0,
                                 plan=SamplerPlan.build(other, tau=5)))
    with pytest.raises(ValueError):       # clip policy is a pool property
        eng.submit(SampleRequest(request_id=0,
                                 plan=SamplerPlan.build(SCH, tau=5,
                                                        x0=1.0)))
    with pytest.raises(ValueError):       # stochastic plan, det. engine
        eng.submit(SampleRequest(
            request_id=0, plan=SamplerPlan.build(SCH, tau=5, sigma=1.0)))


def test_multistep_tick_has_no_prng_and_engine_stochastic_flag():
    """The deterministic multistep tick is PRNG-free too."""
    eng = ContinuousBatchingEngine(SCH, EPS, (64,), slots=2, max_order=2)
    res = eng.serve([SampleRequest(
        request_id=0, plan=SamplerPlan.build(SCH, tau=6, order=2),
        seed=3)])
    assert len(res) == 1 and np.isfinite(res[0].x0).all()
    prims = _collect_prims(
        jax.make_jaxpr(lambda x, h, s: eng._tick_fn.__wrapped__(x, h, s))(
            eng._x2, eng._hist2, eng._states()).jaxpr, [])
    bad = [p for p in prims if "threefry" in p or "random" in p
           or "prng" in p]
    assert not bad, bad


# --------------------------------------------- DiffusionSampler plan cache
def test_diffusion_sampler_accepts_plans_and_keys_cache_on_them():
    svc = DiffusionSampler(SCH, EPS, (4,), batch_size=8)
    plan = SamplerPlan.build(SCH, tau=3)
    out, stats = svc.serve(8, plan)
    assert out.shape == (8, 4) and stats["net_evals_per_sample"] == 3
    assert stats["compiled_programs"] == 1
    # an EQUAL plan (fresh object) reuses the compiled program
    svc.serve(8, SamplerPlan.build(SCH, tau=3))
    assert len(svc._compiled) == 1
    # a different sigma spec compiles a second program
    svc.serve(8, SamplerPlan.build(SCH, tau=3,
                                   sigma=SigmaSpec.schedule([0.0] * 3)))
    assert len(svc._compiled) == 2
    # the legacy SamplerConfig surface still works and lands on the same
    # cache via its equivalent plan
    out2, _ = svc.serve(8, SamplerConfig(S=3))
    assert len(svc._compiled) == 2
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_diffusion_sampler_plan_equals_direct_run():
    svc = DiffusionSampler(SCH, EPS, (6,), batch_size=4,
                           tile_resident=True)
    plan = SamplerPlan.build(SCH, tau=4)
    out, _ = svc.sample_batch(plan, jax.random.PRNGKey(0))
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    xT = jax.random.normal(k1, (4, 6), jnp.float32)
    ref = plan.run(EPS, xT, k2, backend="tile_resident")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
