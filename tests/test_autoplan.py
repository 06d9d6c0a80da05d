"""Tests for the budget-aware trajectory autotuner (ISSUE 5).

Covers the acceptance criteria and satellites:
  * `eval.transition_elbo_table` against a plain-jnp oracle that builds
    the per-transition Gaussians explicitly (same noise injected);
  * exact DP optimality vs brute-force enumeration of every sub-sequence
    on a small grid, frontier monotonicity, valid emitted TauSpecs;
  * TauSpec.explicit validation hardening (non-integer, unsorted,
    duplicate, out-of-range — all at construction, with indexed errors);
  * PlanExecutor: rollouts bit-identical to plan.run(backend='jnp') and
    ONE compilation for N candidates sharing (S, order, ...) — the
    plan-cache-reuse satellite;
  * refinement never loses to the raw DP plan under the scorer;
  * PlanBank round-trip / digest validation / best-and-select policy /
    frozen-plan identity;
  * bank plans run on all four backends, eta=0 order-1 BIT-IDENTICAL
    across jnp / tile_resident / rows (mega falls back, still runs);
  * scheduler integration: deadline-aware admission picks the expected
    NFE rows under a virtual clock with a seeded tick EWMA, mixed
    bank-selected + explicit plans complete with ZERO retraces, the
    bank-selected output replays plan.run(backend='rows') bitwise, and
    stats()/results expose the selection policy's inputs.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.autoplan import (BankEntry, ObjectiveConfig, PlanBank,
                            PlanExecutor, RefineConfig, SearchConfig,
                            build_objective, dp_search, make_grid,
                            refine_plan, search_bank, step_doubling_defect)
from repro.core import make_schedule
from repro.eval import transition_elbo_table
from repro.sampling import SamplerPlan, SigmaSpec, TauSpec
from repro.serving import DiffusionSampler
from repro.serving.scheduler import ContinuousBatchingEngine, SampleRequest

SCH = make_schedule("linear", T=1000)


def analytic_eps(sch, mu=2.0, s=0.5):
    """Layout-invariant eps (elementwise): exact bit-identity across
    backends survives it."""
    def eps_fn(x, t):
        a = sch.alpha_bar[t].reshape((-1,) + (1,) * (x.ndim - 1))
        return (x - jnp.sqrt(a) * mu) * jnp.sqrt(1 - a) / (1 - a + a * s * s)
    return eps_fn


EPS = analytic_eps(SCH)


def small_table(grid_size=10, batch=32, quality_weight=1.0, seed=0):
    x0 = 2.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(seed), (batch, 2))
    cfg = ObjectiveConfig(grid_size=grid_size, batch=batch,
                          quality_weight=quality_weight, seed=seed)
    return build_objective(SCH, EPS, x0, cfg)


# ------------------------------------------------ TauSpec hardening (sat.)
def test_tau_explicit_rejects_non_integer_values():
    with pytest.raises(ValueError, match=r"taus\[1\].*not an integer"):
        TauSpec.explicit([5, 10.7, 20])
    with pytest.raises(ValueError, match="not an integer"):
        TauSpec.explicit([True, 10])            # bool is not a timestep
    with pytest.raises(ValueError, match="not an integer"):
        TauSpec.explicit([float("nan"), 10])
    # integral floats (e.g. out of np.floor arithmetic) are fine
    assert TauSpec.explicit([5.0, np.float64(10.0)]).taus == (5, 10)
    assert TauSpec.explicit(np.array([5, 9], np.int64)).taus == (5, 9)
    # a learned tau emitted as a jax array is the advertised use case
    assert TauSpec.explicit(jnp.asarray([5, 40, 300])).taus == (5, 40, 300)
    with pytest.raises(ValueError, match="not an integer"):
        TauSpec.explicit(jnp.asarray([True, False]))


def test_tau_explicit_indexed_order_errors():
    with pytest.raises(ValueError, match=r"taus\[1\] = 7 >= taus\[2\] = 7 "
                                         r"\(duplicate"):
        TauSpec.explicit([3, 7, 7])
    with pytest.raises(ValueError, match=r"taus\[0\] = 9 >= taus\[1\] = 4"):
        TauSpec.explicit([9, 4])
    with pytest.raises(ValueError, match=r"taus\[0\] = 0"):
        TauSpec.explicit([0, 4])
    with pytest.raises(ValueError, match=r"taus\[0\] = -3"):
        TauSpec.explicit([-3, 4])


def test_tau_explicit_T_bound_at_construction():
    with pytest.raises(ValueError, match="exceeds T=1000"):
        TauSpec.explicit([5, 1001], T=1000)
    # the bound is validation-only: identity ignores it
    assert TauSpec.explicit([5, 40], T=1000) == TauSpec.explicit([5, 40])
    assert hash(TauSpec.explicit([5, 40], T=50)) == hash(
        TauSpec.explicit([5, 40]))


# --------------------------------------------- transition ELBO table (sat.)
def test_transition_elbo_table_matches_plain_jnp_oracle():
    """Vectorized table == per-pair explicit-Gaussian KL (same noise)."""
    grid = np.array([10, 200, 700])
    B = 16
    x0 = 2.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(0), (B, 2))
    noise = jax.random.normal(jax.random.PRNGKey(1),
                              (len(grid),) + x0.shape, jnp.float32)
    eta, rs = 0.8, 0.2
    tab = transition_elbo_table(SCH, EPS, x0, grid=grid, eta=eta,
                                recon_sigma=rs, noise=noise)
    ab = np.asarray(SCH.alpha_bar, np.float64)
    nodes = tab.nodes
    for j in range(1, len(nodes)):          # source t
        a_t = ab[nodes[j]]
        x_t = (np.sqrt(a_t) * np.asarray(x0, np.float64)
               + np.sqrt(1 - a_t) * np.asarray(noise[j - 1], np.float64))
        t_vec = jnp.full((B,), int(nodes[j]), jnp.int32)
        eps_hat = np.asarray(EPS(jnp.asarray(x_t, jnp.float32), t_vec),
                             np.float64)
        x0_hat = (x_t - np.sqrt(1 - a_t) * eps_hat) / np.sqrt(a_t)
        x0_64 = np.asarray(x0, np.float64)
        for i in range(j):                  # destination s
            a_s = ab[nodes[i]]
            if i == 0:
                # explicit decoder: E[-log N(x0; x0_hat, rs^2)] per-dim
                want = np.mean(0.5 * np.log(2 * np.pi * rs ** 2)
                               + (x0_64 - x0_hat) ** 2 / (2 * rs ** 2))
            else:
                sig2 = (eta ** 2 * (1 - a_s) / (1 - a_t)
                        * (1 - a_t / a_s))
                coef = np.sqrt(np.clip(1 - a_s - sig2, 0, None))
                mu_q = (np.sqrt(a_s) * np.asarray(x0, np.float64)
                        + coef * (x_t - np.sqrt(a_t) * np.asarray(
                            x0, np.float64)) / np.sqrt(1 - a_t))
                mu_p = (np.sqrt(a_s) * x0_hat
                        + coef * (x_t - np.sqrt(a_t) * x0_hat)
                        / np.sqrt(1 - a_t))
                want = np.mean((mu_q - mu_p) ** 2) / (2 * sig2)
            np.testing.assert_allclose(tab.trans[i, j], want, rtol=2e-4)
    # prior column: closed-form Gaussian KL per-dim
    m2 = float(np.mean(np.asarray(x0, np.float64) ** 2))
    for j in range(1, len(nodes)):
        a = ab[nodes[j]]
        want = 0.5 * (a * m2 + (1 - a) - 1 - np.log(1 - a))
        np.testing.assert_allclose(tab.prior[j], want, rtol=1e-10)


def test_transition_elbo_path_helpers_and_validation():
    tab = transition_elbo_table(SCH, EPS,
                                jax.random.normal(jax.random.PRNGKey(0),
                                                  (8, 2)),
                                rng=jax.random.PRNGKey(1),
                                grid=[50, 200, 500, 1000])
    nelbo = tab.path_nelbo([50, 500, 1000])
    assert np.isfinite(nelbo)
    np.testing.assert_allclose(tab.path_bpd([50, 500, 1000]),
                               nelbo / np.log(2), rtol=1e-12)
    with pytest.raises(ValueError, match="not on the table's grid"):
        tab.path_nelbo([50, 300])
    with pytest.raises(ValueError, match="eta > 0"):
        transition_elbo_table(SCH, EPS, jnp.zeros((4, 2)),
                              rng=jax.random.PRNGKey(0), eta=0.0)
    with pytest.raises(ValueError, match="need rng"):
        transition_elbo_table(SCH, EPS, jnp.zeros((4, 2)))
    with pytest.raises(ValueError, match="grid"):
        transition_elbo_table(SCH, EPS, jnp.zeros((4, 2)),
                              rng=jax.random.PRNGKey(0), grid=[0, 10])


# ------------------------------------------------------------ objective/DP
def test_make_grid_properties():
    for kind in ("uniform", "quadratic"):
        g = make_grid(1000, 32, kind)
        assert len(g) == 32 and g[-1] == 1000 and g[0] >= 1
        assert (np.diff(g) > 0).all()
    assert len(make_grid(10, 64, "uniform")) == 10   # clamps to T


def test_step_doubling_defect_shape_and_adjacent_zero():
    grid = make_grid(1000, 8, "uniform")
    x0 = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    noise = jax.random.normal(jax.random.PRNGKey(1),
                              (len(grid),) + x0.shape, jnp.float32)
    d = step_doubling_defect(SCH, EPS, x0, grid, noise)
    assert d.shape == (9, 9)
    assert (d >= 0).all()
    # adjacent node pairs have no interior midpoint -> identically zero
    for j in range(1, 9):
        assert d[j - 1, j] == 0.0
    # some long jump must register positive curvature
    assert d[0, 8] > 0.0


def test_dp_matches_brute_force_enumeration():
    """Exact optimality: DP == min over ALL C(G, S) sub-sequences."""
    import itertools
    tab = small_table(grid_size=7)
    cost, prior, nodes = tab.cost, tab.prior, tab.nodes
    G = len(nodes) - 1
    dp = dp_search(tab, (1, 2, 3, 4))
    for S in (1, 2, 3, 4):
        best = np.inf
        for combo in itertools.combinations(range(1, G + 1), S):
            c = prior[combo[-1]] + cost[0, combo[0]]
            for a, b in zip(combo, combo[1:]):
                c += cost[a, b]
            best = min(best, c)
        np.testing.assert_allclose(dp[S].objective, best, rtol=1e-12)
        # and the returned path really costs what the DP claims
        np.testing.assert_allclose(tab.path_cost(dp[S].taus),
                                   dp[S].objective, rtol=1e-12)


def test_dp_frontier_monotone_and_specs_valid():
    tab = small_table(grid_size=12)
    dp = dp_search(tab, (2, 4, 8, 30))
    objs = [dp[S].objective for S in (2, 4, 8)]
    assert objs[0] >= objs[1] >= objs[2]     # more budget never hurts
    for S, r in dp.items():
        spec = r.tau_spec(T=SCH.T)           # constructs + validates
        assert spec.S == r.S == len(r.taus)
    assert dp[30].S == 12                    # budgets clamp to the grid


def test_dp_validation():
    tab = small_table(grid_size=5)
    with pytest.raises(ValueError, match="budgets"):
        dp_search(tab, ())
    with pytest.raises(ValueError, match="budgets"):
        dp_search(tab, (0, 3))


# ---------------------------------------------------- executor (satellite)
def test_executor_bitwise_and_single_trace_across_candidates():
    """N candidates sharing (S, order, stochastic, clip, shape) compile
    the backend executor at most ONCE (plan-cache-reuse satellite)."""
    ex = PlanExecutor(EPS)
    xT = jax.random.normal(jax.random.PRNGKey(1), (16, 2))
    tab = small_table(grid_size=10)
    dp = dp_search(tab, (4,))
    candidates = [SamplerPlan.build(SCH, tau=TauSpec.explicit(t)) for t in
                  [dp[4].taus, (5, 50, 500, 1000), (1, 2, 3, 4),
                   (100, 200, 300, 400), (7, 70, 700, 999)]]
    outs = [ex.run(p, xT) for p in candidates]
    assert ex.traces == 1 and ex.calls == len(candidates)
    for p, out in zip(candidates, outs):
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(p.run(EPS, xT, backend="jnp")))
    # a different step budget is a different program: exactly one more
    ex.run(SamplerPlan.build(SCH, tau=TauSpec.explicit((10, 1000))), xT)
    assert ex.traces == 2
    with pytest.raises(ValueError, match="needs rng"):
        ex.run(SamplerPlan.build(SCH, tau=4, sigma=1.0), xT)
    # stochastic candidates match the jnp backend under the same rng
    rng = jax.random.PRNGKey(5)
    sp = SamplerPlan.build(SCH, tau=4, sigma=1.0)
    np.testing.assert_array_equal(
        np.asarray(ex.run(sp, xT, rng)),
        np.asarray(sp.run(EPS, xT, rng, backend="jnp")))


# ------------------------------------------------------------- refinement
def test_refine_never_worse_and_respects_order_constraint():
    ex = PlanExecutor(EPS)
    xT = jax.random.normal(jax.random.PRNGKey(1), (64, 2))
    ref = 2.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(2), (64, 2))
    rng = jax.random.PRNGKey(3)

    def score(plan):
        out = ex.run(plan, xT, rng if plan.stochastic else None)
        return float(jnp.mean((jnp.sort(out, 0) - jnp.sort(ref, 0)) ** 2))

    taus = (20, 60, 150, 400, 1000)
    base = score(SamplerPlan.build(SCH, tau=TauSpec.explicit(taus)))
    plan, s, trials = refine_plan(SCH, taus, score,
                                  RefineConfig(per_step_eta=True))
    assert s <= base and trials > 1
    if plan.stochastic:
        assert plan.order == 1       # multistep plans must be deterministic
    assert plan.tau.taus == taus     # refinement never moves the DP tau


def test_search_bank_end_to_end_smoke():
    tab = small_table(grid_size=10)
    bank = search_bank(SCH, tab, SearchConfig(budgets=(3, 5), refine=None))
    assert bank.nfes == (3, 5)
    assert bank.search_config["objective"]["grid_size"] == 10
    for e in bank.entries:
        assert e.objective is not None and e.meta["dp_taus"]


# ---------------------------------------------------------------- PlanBank
def _toy_bank():
    bank = PlanBank(SCH, search_config={"note": "test"}, model_digest="t")
    bank.add_plan(SamplerPlan.build(SCH, tau=TauSpec.explicit(
        [50, 300, 1000])), score=0.3)
    bank.add_plan(SamplerPlan.build(
        SCH, tau=TauSpec.explicit([20, 60, 150, 400, 700, 1000]),
        order=2), score=0.2)
    bank.add_plan(SamplerPlan.build(
        SCH, tau=TauSpec.explicit([5, 15, 30, 60, 100, 180, 300, 450, 650,
                                   1000]),
        sigma=SigmaSpec.schedule([0.0] * 9 + [0.5])), score=0.1)
    return bank


def test_bank_roundtrip_and_digest_validation(tmp_path):
    bank = _toy_bank()
    p = str(tmp_path / "bank.json")
    bank.save(p)
    loaded = PlanBank.load(p, SCH)
    assert loaded.nfes == bank.nfes == (3, 6, 10)
    assert loaded.model_digest == "t"
    assert loaded.search_config == {"note": "test"}
    for nfe in bank.nfes:
        assert loaded.plan(nfe) == bank.plan(nfe)        # full plan hash
    # frozen-plan cache: repeated selection returns the SAME object
    assert loaded.plan(6) is loaded.plan(6)
    with pytest.raises(ValueError, match="different noise schedule"):
        PlanBank.load(p, make_schedule("cosine", T=1000))
    with open(p) as f:
        d = json.load(f)
    d["format"] = "nope"
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump(d, f)
    with pytest.raises(ValueError, match="not a PlanBank artifact"):
        PlanBank.load(bad, SCH)


def test_bank_entry_validation():
    bank = PlanBank(SCH)
    with pytest.raises(ValueError, match="exceeds T"):
        bank.add_entry(BankEntry(nfe=2, taus=(5, 2000)))
    with pytest.raises(ValueError, match="nfe=3 != len"):
        bank.add_entry(BankEntry(nfe=3, taus=(5, 10)))
    with pytest.raises(ValueError, match="explicit"):
        bank.add_plan(SamplerPlan.build(SCH, tau=10))
    with pytest.raises(ValueError, match="different noise schedule"):
        bank.add_plan(SamplerPlan.build(make_schedule("cosine", T=1000),
                                        tau=TauSpec.explicit([5, 1000])))
    # duplicate budget replaces the row
    bank.add_entry(BankEntry(nfe=2, taus=(5, 500)))
    bank.add_entry(BankEntry(nfe=2, taus=(9, 900)))
    assert len(bank) == 1 and bank.entries[0].taus == (9, 900)


def test_bank_best_and_select_policy():
    bank = _toy_bank()
    assert bank.best().S == 10
    assert bank.best(max_nfe=7).S == 6
    assert bank.best(max_nfe=1).S == 3          # degrade to smallest
    # deterministic filter drops the stochastic 10-row
    assert bank.best(deterministic=True).S == 6
    # order filter drops the AB-2 row
    assert bank.best(max_nfe=7, deterministic=True, max_order=1).S == 3
    assert bank.best(deterministic=True, max_order=1, clip=1.0) is None
    # select: fits = headroom * margin / per_step
    assert bank.select(float("inf"), 0.1).S == 10
    assert bank.select(1.0, 0.1, margin=0.9).S == 6     # fit = 9
    assert bank.select(2.0, 0.1, margin=0.9).S == 10
    assert bank.select(0.1, 0.1).S == 3                 # nothing fits
    assert bank.select(1.0, None).S == 3                # no measurement yet
    assert bank.select(float("inf"), None).S == 10


# ----------------------------------------------- four-backend executability
def test_bank_plans_run_on_all_four_backends_bit_identical():
    """Acceptance: bank rows are valid frozen plans on every backend;
    eta=0 order-1 rows agree across jnp/tile_resident/rows (mega is not
    eligible for this eps model and must fall back to tile_resident,
    producing the identical result)."""
    bank = _toy_bank()
    plan = bank.plan(3)                        # eta=0, order-1 row
    xT = jax.random.normal(jax.random.PRNGKey(1), (16, 2))
    outs = {b: np.asarray(plan.run(EPS, xT, backend=b))
            for b in ("jnp", "tile_resident", "rows", "mega")}
    # the fallback runs the very same tile_resident program
    np.testing.assert_array_equal(outs["tile_resident"], outs["mega"])
    # separate compiled programs round the Eq. 12 update differently
    # (XLA's fusion choices): a few f32 ulp apart, not bit for bit
    for b in ("tile_resident", "rows"):
        np.testing.assert_allclose(outs[b], outs["jnp"], rtol=1e-5,
                                   atol=1e-6)
    # the AB-2 and stochastic rows execute too (jnp reference)
    assert np.isfinite(np.asarray(bank.plan(6).run(EPS, xT))).all()
    assert np.isfinite(np.asarray(
        bank.plan(10).run(EPS, xT, jax.random.PRNGKey(2)))).all()


# ------------------------------------------------- scheduler integration
def test_engine_auto_plan_validation():
    eng = ContinuousBatchingEngine(SCH, EPS, (8,), slots=2)
    with pytest.raises(ValueError, match="plan_bank"):
        eng.submit(SampleRequest(request_id=0, auto_plan=True), now=0.0)
    bank = _toy_bank()
    eng = ContinuousBatchingEngine(SCH, EPS, (8,), slots=2, plan_bank=bank)
    with pytest.raises(ValueError, match="mutually exclusive"):
        eng.submit(SampleRequest(request_id=0, auto_plan=True,
                                 plan=bank.plan(3)), now=0.0)
    # deterministic order-1 engine: the 3-row is the only compatible one
    assert eng._bank_candidates() == 1
    with pytest.raises(ValueError, match="different noise schedule"):
        ContinuousBatchingEngine(make_schedule("cosine", T=1000), EPS,
                                 (8,), slots=2, plan_bank=bank)


def test_engine_deadline_aware_selection_virtual_clock_replay():
    """The deadline-aware admission policy under a virtual clock: a
    seeded (frozen) tick EWMA makes the NFE picks exact, mixed
    bank-selected + explicit plans finish in ONE compiled tick, and the
    results expose the policy's inputs."""
    bank = _toy_bank()
    eng = ContinuousBatchingEngine(SCH, EPS, (8,), slots=4, plan_bank=bank,
                                   max_order=2, tick_ewma_alpha=0.0)
    eng.tick_ewma_s = 0.1                    # frozen by alpha=0
    explicit = SamplerPlan.build(SCH, tau=TauSpec.explicit([10, 500, 1000]))
    reqs = [
        # headroom 0.95s, fit = floor(0.95*0.9/0.1) = 8 -> the 6-row
        SampleRequest(request_id=0, auto_plan=True, deadline=10.95, seed=1),
        # headroom 0.25s, fit = 2 -> nothing fits -> smallest (3)
        SampleRequest(request_id=1, auto_plan=True, deadline=10.25, seed=2),
        # no deadline -> quality end of the DETERMINISTIC frontier (6)
        SampleRequest(request_id=2, auto_plan=True, seed=3),
        # an explicit plan rides along in the same tick
        SampleRequest(request_id=3, plan=explicit, seed=4),
    ]
    for r in reqs:
        eng.submit(r, now=10.0)
    clock, res = 10.0, []
    while len(eng.queue) or eng.active:
        res.extend(eng.tick(now=clock))
        clock += 0.01
    res.sort(key=lambda r: r.request_id)
    assert [r.nfe for r in res] == [6, 3, 6, 3]
    assert [r.auto_plan for r in res] == [True, True, True, False]
    np.testing.assert_allclose(res[0].deadline_headroom_s, 0.95)
    np.testing.assert_allclose(res[1].deadline_headroom_s, 0.25)
    assert res[2].deadline_headroom_s is None
    assert not any(r.deadline_missed for r in res)
    st = eng.stats()
    assert st["compiled_ticks"] == 1         # ZERO retraces across the mix
    assert st["bank_selected"] == 3
    assert st["plan_bank"] == 3
    assert st["tick_ewma_s"] == 0.1          # alpha=0 froze the seed
    # the bank-selected eta=0 order-1 output replays the plan: request 1
    # (seed 2) got the 3-row; re-draw its x_T the engine's way. The engine
    # tick and the plan's rows scan are two compiled programs that round
    # the Eq. 12 update differently, so they agree to a few f32 ulp
    done = {r.request_id: r for r in res}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 8), jnp.float32)
    want = bank.plan(3).run(EPS, x, backend="rows")
    np.testing.assert_allclose(done[1].x0, np.asarray(want)[0], rtol=1e-5,
                               atol=1e-6)


def test_engine_tick_ewma_updates_when_alpha_positive():
    eng = ContinuousBatchingEngine(SCH, EPS, (8,), slots=2,
                                   tick_ewma_alpha=0.5)
    assert eng.stats()["tick_ewma_s"] is None
    eng.submit(SampleRequest(request_id=0, S=3, seed=1), now=0.0)
    eng.run()
    ew = eng.stats()["tick_ewma_s"]
    assert ew is not None and ew > 0.0


def test_engine_stochastic_bank_rows_need_stochastic_engine():
    bank = _toy_bank()
    det = ContinuousBatchingEngine(SCH, EPS, (8,), slots=2, plan_bank=bank,
                                   tick_ewma_alpha=0.0)
    det.tick_ewma_s = 1e-9                  # everything "fits"
    det.submit(SampleRequest(request_id=0, auto_plan=True, seed=1), now=0.0)
    det.run()
    # quality end of the DETERMINISTIC order-1 frontier is the 3-row
    assert det.completed == 1
    sto = ContinuousBatchingEngine(SCH, EPS, (8,), slots=2, plan_bank=bank,
                                   stochastic=True, tick_ewma_alpha=0.0)
    sto.tick_ewma_s = 1e-9
    sto.submit(SampleRequest(request_id=0, auto_plan=True, seed=1), now=0.0)
    res = sto.run()
    assert res[0].nfe == 10                  # the stochastic 10-row now fits


# ------------------------------------------------- DiffusionSampler glue
def test_diffusion_sampler_auto_cfg_and_bank_plan():
    bank = _toy_bank()
    svc = DiffusionSampler(SCH, EPS, (8,), batch_size=4, plan_bank=bank)
    assert svc.bank_plan().S == 10
    assert svc.bank_plan(max_nfe=7).S == 6
    out, _ = svc.sample_batch("auto", jax.random.PRNGKey(0))
    assert out.shape == (4, 8)
    eng = svc.continuous(slots=2)            # bank forwards to the engine
    assert eng.plan_bank is bank
    svc2 = DiffusionSampler(SCH, EPS, (8,), batch_size=4)
    with pytest.raises(ValueError, match="no plan bank"):
        svc2.serve(2, "auto")
    with pytest.raises(ValueError, match="different noise schedule"):
        DiffusionSampler(make_schedule("cosine", T=1000), EPS, (8,),
                         batch_size=4, plan_bank=bank)
