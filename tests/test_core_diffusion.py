"""Unit + property tests for the DDIM core (schedules, samplers, ODE)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (NoiseSchedule, make_schedule, make_tau, q_sample,
                        predict_x0, eps_from_x0, posterior_sigma, sigma_hat,
                        gamma_weights, simple_loss, training_loss,
                        SamplerConfig, trajectory_coefficients, sample,
                        ddim_sample, ddpm_sample, encode, decode,
                        probability_flow_sample, multistep_sample, slerp,
                        slerp_grid, discrete)

SCH = make_schedule("linear", T=1000)


def analytic_eps(sch, mu=2.0, s=0.5):
    """Optimal eps-model for data N(mu, s^2 I) — closed form."""
    def eps_fn(x, t):
        a = sch.alpha_bar[t].reshape((-1,) + (1,) * (x.ndim - 1))
        return (x - jnp.sqrt(a) * mu) * jnp.sqrt(1 - a) / (1 - a + a * s * s)
    return eps_fn


# ---------------------------------------------------------------- schedules
@pytest.mark.parametrize("kind", ["linear", "cosine", "scaled_linear"])
def test_schedule_monotone_and_bounds(kind):
    sch = make_schedule(kind, T=500)
    ab = np.asarray(sch.alpha_bar)
    assert ab[0] == 1.0
    assert np.all(np.diff(ab) < 0)
    assert ab[-1] > 0
    assert np.all(np.asarray(sch.betas) > 0)
    assert np.all(np.asarray(sch.betas) < 1)


@given(S=st.integers(1, 1000),
       kind=st.sampled_from(["linear", "quadratic"]))
@settings(max_examples=50, deadline=None)
def test_tau_property(S, kind):
    tau = make_tau(1000, S, kind)
    assert len(tau) == S
    assert tau[0] >= 1 and tau[-1] <= 1000
    assert np.all(np.diff(tau) > 0)  # strictly increasing


def test_tau_full_trajectory_is_identity():
    assert np.array_equal(make_tau(100, 100, "linear"), np.arange(1, 101))


# ------------------------------------------------------------ forward / x0
def test_q_sample_marginal_stats():
    key = jax.random.PRNGKey(0)
    x0 = jnp.ones((20000, 2)) * 3.0
    t = jnp.full((20000,), 500, jnp.int32)
    x_t = q_sample(SCH, x0, t, jax.random.normal(key, x0.shape))
    a = float(SCH.alpha_bar[500])
    np.testing.assert_allclose(float(x_t.mean()), 3.0 * a ** 0.5, atol=0.02)
    np.testing.assert_allclose(float(x_t.std()), (1 - a) ** 0.5, atol=0.02)


def test_predict_x0_inverts_q_sample():
    key = jax.random.PRNGKey(1)
    x0 = jax.random.normal(key, (8, 4, 4, 3))
    t = jnp.asarray([1, 10, 100, 500, 700, 900, 999, 1000], jnp.int32)
    noise = jax.random.normal(jax.random.PRNGKey(2), x0.shape)
    x_t = q_sample(SCH, x0, t, noise)
    np.testing.assert_allclose(predict_x0(SCH, x_t, t, noise), x0,
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(eps_from_x0(SCH, x_t, t, x0), noise,
                               atol=1e-3, rtol=1e-3)


# ------------------------------------------------------------------ sigmas
def test_eta1_matches_ddpm_posterior_std():
    """eta=1 sigma^2 must equal the DDPM posterior variance
    (1-a_{t-1})/(1-a_t) * beta_t (paper below Eq. 12 / App. C.2)."""
    t = jnp.arange(2, 1001)
    s = t - 1
    sig = posterior_sigma(SCH, t, s, eta=1.0)
    a_t, a_s = SCH.alpha_bar[t], SCH.alpha_bar[s]
    beta_t = 1 - a_t / a_s
    np.testing.assert_allclose(sig ** 2, (1 - a_s) / (1 - a_t) * beta_t,
                               rtol=1e-5)


def test_sigma_hat_geq_sigma1():
    t = jnp.arange(2, 1001)
    s = t - 1
    assert np.all(np.asarray(sigma_hat(SCH, t, s)) >=
                  np.asarray(posterior_sigma(SCH, t, s, 1.0)) - 1e-7)


def test_gamma_weights_theorem1():
    sig = posterior_sigma(SCH, jnp.arange(1, 1001),
                          jnp.maximum(jnp.arange(0, 1000), 0), eta=1.0)
    sig = jnp.maximum(sig, 1e-3)
    g = gamma_weights(SCH, sig, d=32 * 32 * 3)
    assert g.shape == (1000,)
    assert np.all(np.asarray(g) > 0)


# ---------------------------------------------------------------- sampling
def test_ddim_deterministic():
    eps_fn = analytic_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    a = ddim_sample(SCH, eps_fn, xT, S=20)
    b = ddim_sample(SCH, eps_fn, xT, S=20)
    np.testing.assert_array_equal(a, b)


def test_ddim_recovers_analytic_distribution():
    eps_fn = analytic_eps(SCH, mu=2.0, s=0.5)
    xT = jax.random.normal(jax.random.PRNGKey(0), (8192, 2))
    x0 = ddim_sample(SCH, eps_fn, xT, S=100)
    np.testing.assert_allclose(float(x0.mean()), 2.0, atol=0.05)
    np.testing.assert_allclose(float(x0.std()), 0.5, atol=0.05)


def test_quality_improves_with_steps():
    """Paper Table 1 trend: larger S -> closer to the data distribution."""
    eps_fn = analytic_eps(SCH, mu=0.0, s=1.0)  # data = N(0, I)
    xT = jax.random.normal(jax.random.PRNGKey(0), (8192, 2))
    errs = []
    for S in (5, 20, 100):
        x0 = ddim_sample(SCH, eps_fn, xT, S=S)
        errs.append(abs(float(x0.std()) - 1.0))
    assert errs[2] < errs[0]


def test_ddpm_needs_rng():
    eps_fn = analytic_eps(SCH)
    xT = jnp.zeros((2, 2))
    with pytest.raises(ValueError):
        sample(SCH, eps_fn, xT, SamplerConfig(S=5, eta=1.0))


def test_sigma_hat_requires_eta1():
    with pytest.raises(ValueError):
        SamplerConfig(S=5, eta=0.0, sigma_hat=True)


def test_trajectory_coefficients_shapes_and_last_step():
    cfg = SamplerConfig(S=10, eta=0.0)
    c = trajectory_coefficients(SCH, cfg)
    for k, v in c.items():
        assert v.shape == (10,), k
    # first entry corresponds to smallest t, jumping to t=0: c_x0 = sqrt(a_0)=1
    np.testing.assert_allclose(float(c["c_x0"][0]), 1.0, rtol=1e-6)
    # deterministic: no noise anywhere
    assert np.all(np.asarray(c["c_noise"]) == 0.0)


def test_return_trajectory():
    eps_fn = analytic_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (4, 2))
    x0, traj = sample(SCH, eps_fn, xT, SamplerConfig(S=7),
                      return_trajectory=True)
    assert traj.shape == (8, 4, 2)
    np.testing.assert_array_equal(traj[-1], x0)
    np.testing.assert_array_equal(traj[0], xT)


@given(eta=st.floats(0.0, 1.0), S=st.sampled_from([5, 10, 25]))
@settings(max_examples=10, deadline=None)
def test_sampler_family_all_finite(eta, S):
    """Property: every (eta, S) member of the family produces finite samples."""
    eps_fn = analytic_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (32, 2))
    x0 = sample(SCH, eps_fn, xT, SamplerConfig(S=S, eta=eta),
                rng=jax.random.PRNGKey(1))
    assert bool(jnp.all(jnp.isfinite(x0)))


# --------------------------------------------------------------------- ODE
def test_reconstruction_error_decreases_with_S():
    """Paper Table 2: encode->decode error shrinks as S grows."""
    eps_fn = analytic_eps(SCH)
    data = 2.0 + 0.5 * jax.random.normal(jax.random.PRNGKey(2), (128, 2))
    errs = []
    for S in (10, 50, 200):
        lat = encode(SCH, eps_fn, data, S=S)
        rec = decode(SCH, eps_fn, lat, S=S)
        errs.append(float(jnp.mean((rec - data) ** 2)))
    assert errs[0] > errs[1] > errs[2]
    assert errs[2] < 1e-3


def test_probability_flow_converges_to_ddim():
    """Prop. 1: PF-Euler and DDIM agree in the many-step limit."""
    eps_fn = analytic_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (64, 2))
    a = ddim_sample(SCH, eps_fn, xT, S=1000)
    b = probability_flow_sample(SCH, eps_fn, xT, S=1000)
    np.testing.assert_allclose(a, b, atol=0.05)


def test_multistep_beats_euler_at_small_S():
    eps_fn = analytic_eps(SCH, mu=0.0, s=1.0)
    xT = jax.random.normal(jax.random.PRNGKey(0), (8192, 2))
    ref = ddim_sample(SCH, eps_fn, xT, S=1000)
    e1 = float(jnp.mean((ddim_sample(SCH, eps_fn, xT, S=10) - ref) ** 2))
    e2 = float(jnp.mean((multistep_sample(SCH, eps_fn, xT, S=10,
                                          order=2) - ref) ** 2))
    assert e2 < e1


# ------------------------------------------------------------------- slerp
def test_slerp_endpoints():
    x0 = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 8))
    x1 = jax.random.normal(jax.random.PRNGKey(1), (3, 8, 8))
    out = slerp(x0, x1, jnp.asarray([0.0, 1.0]))
    np.testing.assert_allclose(out[0], x0, atol=1e-4)
    np.testing.assert_allclose(out[1], x1, atol=1e-4)


def test_slerp_grid_shape():
    corners = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    g = slerp_grid(corners, 5)
    assert g.shape == (5, 5, 16)


# ---------------------------------------------------------------- discrete
def test_discrete_marginals_sum_to_one():
    sch = make_schedule("linear", T=100)
    x0 = jax.nn.one_hot(jnp.asarray([0, 3, 7]), 8)
    p = discrete.q_probs(sch, x0, jnp.asarray([1, 50, 100]))
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-5)
    assert np.all(np.asarray(p) >= 0)


def test_discrete_posterior_valid_distribution():
    sch = make_schedule("linear", T=100)
    key = jax.random.PRNGKey(0)
    x0 = jax.nn.one_hot(jax.random.randint(key, (16,), 0, 8), 8)
    t = jnp.full((16,), 60, jnp.int32)
    x_t = discrete.q_sample(sch, x0, t, key)
    s = t - 10
    sig = 0.7 * discrete.sigma_implicit(sch, t, s)
    p = discrete.posterior_probs(sch, x_t, x0, t, s, sig)
    np.testing.assert_allclose(p.sum(-1), 1.0, rtol=1e-5)
    assert np.all(np.asarray(p) >= -1e-7)


def test_discrete_reverse_perfect_model_recovers_x0():
    """With f_theta == true x0, the implicit reverse chain returns x0-like
    samples concentrated on the data point."""
    sch = make_schedule("linear", T=100)
    key = jax.random.PRNGKey(0)
    true_idx = 3
    x0 = jax.nn.one_hot(jnp.full((256,), true_idx), 8)

    def x0_fn(x_t, t):
        return x0

    x_T = discrete.q_sample(sch, x0, jnp.full((256,), 100, jnp.int32), key)
    out = discrete.reverse_sample(sch, x0_fn, x_T, jax.random.PRNGKey(1),
                                  S=25, eta=1.0)
    acc = float(jnp.mean(out.argmax(-1) == true_idx))
    assert acc > 0.95


def test_discrete_kl_zero_for_perfect_model():
    sch = make_schedule("linear", T=100)
    x0 = jax.nn.one_hot(jnp.asarray([1, 2, 3, 4]), 8)
    loss = discrete.kl_loss(sch, lambda x, t: x0, x0,
                            jnp.asarray([10, 40, 70, 100]),
                            jax.random.PRNGKey(0))
    assert float(loss) < 1e-6


# ---------------------------------------------------------------- training
def test_training_loss_zero_for_perfect_eps():
    x0 = jnp.zeros((8, 4))  # data identically 0 => eps* = x_t/sqrt(1-a)
    def eps_fn(x, t):
        a = SCH.alpha_bar[t].reshape(-1, 1)
        return x / jnp.sqrt(1 - a)
    loss = training_loss(SCH, eps_fn, x0, jax.random.PRNGKey(0))
    assert float(loss) < 1e-8


def test_weighted_loss_matches_manual():
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (16, 3))
    t = jnp.full((16,), 500, jnp.int32)
    noise = jax.random.normal(jax.random.PRNGKey(1), x0.shape)
    w = jnp.full((1000,), 2.0)
    def eps_fn(x, tt):
        return jnp.zeros_like(x)
    l1 = simple_loss(SCH, eps_fn, x0, t, noise)
    l2 = simple_loss(SCH, eps_fn, x0, t, noise, weights=w)
    np.testing.assert_allclose(float(l2), 2 * float(l1), rtol=1e-6)


# ---------------------------------------------------- beyond: v-pred, CFG
def test_v_parameterization_roundtrip():
    from repro.core import (eps_from_v, v_from_eps_x0, x0_from_v, q_sample)
    key = jax.random.PRNGKey(0)
    x0 = jax.random.normal(key, (8, 4))
    t = jnp.asarray([1, 10, 100, 400, 600, 800, 950, 1000], jnp.int32)
    noise = jax.random.normal(jax.random.PRNGKey(1), x0.shape)
    x_t = q_sample(SCH, x0, t, noise)
    v = v_from_eps_x0(SCH, t, noise, x0)
    np.testing.assert_allclose(eps_from_v(SCH, x_t, t, v), noise,
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(x0_from_v(SCH, x_t, t, v), x0,
                               atol=1e-4, rtol=1e-4)


def test_v_model_plugs_into_ddim_sampler():
    """Optimal v-model for the analytic Gaussian == optimal eps-model:
    samples must agree exactly through the eps adapter."""
    from repro.core import eps_fn_from_v_fn, v_from_eps_x0, predict_x0
    eps_fn = analytic_eps(SCH, mu=2.0, s=0.5)

    def v_fn(x_t, t):
        eps = eps_fn(x_t, t)
        x0 = predict_x0(SCH, x_t, t, eps)
        return v_from_eps_x0(SCH, t, eps, x0)

    xT = jax.random.normal(jax.random.PRNGKey(0), (64, 2))
    a = ddim_sample(SCH, eps_fn, xT, S=20)
    b = ddim_sample(SCH, eps_fn_from_v_fn(SCH, v_fn), xT, S=20)
    np.testing.assert_allclose(a, b, atol=1e-3, rtol=1e-3)


def test_cfg_guidance_interpolates():
    from repro.core import cfg_eps_fn
    e1 = analytic_eps(SCH, mu=2.0, s=0.5)   # "conditional"
    e0 = analytic_eps(SCH, mu=0.0, s=0.5)   # "unconditional"
    xT = jax.random.normal(jax.random.PRNGKey(0), (2048, 2))
    # w=0 -> unconditional; w=1 -> conditional; w>1 extrapolates past mu=2
    means = []
    for w in (0.0, 1.0, 2.0):
        out = ddim_sample(SCH, cfg_eps_fn(e1, e0, w), xT, S=100)
        means.append(float(out.mean()))
    np.testing.assert_allclose(means[0], 0.0, atol=0.1)
    np.testing.assert_allclose(means[1], 2.0, atol=0.1)
    assert means[2] > means[1] + 0.5   # guidance overshoots the cond mean
