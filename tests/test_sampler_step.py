"""Tests for the tile-resident sampler hot path (kernels/sampler_step).

Covers the ISSUE-1 acceptance criteria:
  * allclose sweeps of the fused full-step kernel (interpret mode) against
    the pure-jnp oracle across dtypes, clip on/off, eta in {0, 0.5, 1} and
    odd shapes exercising the padding lanes;
  * eta=0 sampling is bitwise independent of the rng argument;
  * the tile-resident scan performs ZERO layout conversions of the state
    inside the scan body (jaxpr inspection) — one conversion per sample();
  * the deterministic sampler's scan contains no PRNG ops at all.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import SamplerConfig, make_schedule, sample
from repro.core.sampler import trajectory_coefficients
from repro.kernels import fused_sampler_step
from repro.kernels.sampler_step.ref import (sampler_noise_tiles,
                                            sampler_step_ref)

SCH = make_schedule("linear", T=1000)

TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=2e-2, rtol=2e-2)}


def analytic_eps(sch, mu=2.0, s=0.5):
    def eps_fn(x, t):
        a = sch.alpha_bar[t].reshape((-1,) + (1,) * (x.ndim - 1))
        return (x - jnp.sqrt(a) * mu) * jnp.sqrt(1 - a) / (1 - a + a * s * s)
    return eps_fn


def tile_aware_eps(sch, s=1.0):
    """Elementwise analytic model operating natively on the (R, C) view."""
    def eps_fn(x2, t):
        a = sch.alpha_bar[t]
        return x2 * jnp.sqrt(1 - a) / (1 - a + a * s * s)
    eps_fn.tile_aware = True
    return eps_fn


# --------------------------------------------------------- kernel vs oracle
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("clip", [None, 1.0])
@pytest.mark.parametrize("eta_coefs", [
    # (c_x0, c_dir, c_noise) triples shaped like eta = 0 / 0.5 / 1
    (0.98, 0.15, 0.0), (0.97, 0.12, 0.05), (0.95, 0.08, 0.12)])
@pytest.mark.parametrize("shape", [(2, 100), (7, 333), (4, 16, 16, 3),
                                   (256, 256), (3, 8, 8, 8, 3)])
def test_sampler_step_sweep(shape, eta_coefs, clip, dtype):
    c_x0, c_dir, c_noise = eta_coefs
    stochastic = c_noise > 0.0
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    x = jax.random.normal(ks[0], shape, dtype)
    e = jax.random.normal(ks[1], shape, dtype)
    args = (c_x0, c_dir, c_noise, 0.97, 0.24)
    out = fused_sampler_step(x, e, *args, seed=13, clip=clip,
                             stochastic=stochastic)
    ref = sampler_step_ref(x, e, *args, seed=13, clip=clip,
                           stochastic=stochastic)
    assert out.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@given(c_x0=st.floats(0.1, 1.0), c_dir=st.floats(0.0, 1.0),
       a_t=st.floats(0.01, 0.999))
@settings(max_examples=20, deadline=None)
def test_sampler_step_property_coefficients(c_x0, c_dir, a_t):
    """Property: kernel == oracle for arbitrary valid coefficients (clip
    path, which exercises the full x0-predict/clip/rederive pipeline)."""
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x, e = (jax.random.normal(k, (4, 64)) for k in ks)
    args = (c_x0, c_dir, 0.0, a_t ** 0.5, (1 - a_t) ** 0.5)
    np.testing.assert_allclose(
        fused_sampler_step(x, e, *args, clip=1.0),
        sampler_step_ref(x, e, *args, clip=1.0), atol=1e-4, rtol=1e-4)


def test_in_kernel_noise_is_standard_normal():
    z = sampler_noise_tiles(123, 512, 512)
    assert abs(float(z.mean())) < 0.02
    np.testing.assert_allclose(float(z.std()), 1.0, atol=0.02)
    # Box-Muller sanity: excess kurtosis of a normal is 0 (E[z^4] = 3)
    np.testing.assert_allclose(float((z ** 4).mean()), 3.0, atol=0.1)


def test_noise_streams_differ_by_seed_and_tile():
    a = sampler_noise_tiles(1, 256, 256)
    b = sampler_noise_tiles(2, 256, 256)
    assert float(jnp.abs(a - b).max()) > 0.1
    big = sampler_noise_tiles(1, 512, 256)   # two row-tiles, same seed
    assert float(jnp.abs(big[:256] - big[256:]).max()) > 0.1


# ---------------------------------------------------- full-trajectory paths
def test_tile_resident_matches_classic_ddim():
    """eta=0: tile-resident trajectory == pure-jnp trajectory."""
    eps_fn = analytic_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    a = sample(SCH, eps_fn, xT, SamplerConfig(S=20))
    b = sample(SCH, eps_fn, xT, SamplerConfig(S=20), tile_resident=True)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_tile_resident_matches_classic_with_clip():
    eps_fn = analytic_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    a = sample(SCH, eps_fn, xT, SamplerConfig(S=20, clip_x0=3.0))
    b = sample(SCH, eps_fn, xT, SamplerConfig(S=20, clip_x0=3.0),
               tile_resident=True)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("eta,sigma_hat", [(0.5, False), (1.0, False),
                                           (1.0, True)])
def test_tile_resident_stochastic_statistics(eta, sigma_hat):
    """In-kernel noise must reproduce the analytic target distribution to
    the same accuracy as the classic jax.random path."""
    eps_fn = analytic_eps(SCH, mu=2.0, s=0.5)
    xT = jax.random.normal(jax.random.PRNGKey(1), (8192, 2))
    cfg = SamplerConfig(S=50, eta=eta, sigma_hat=sigma_hat)
    ref = sample(SCH, eps_fn, xT, cfg, rng=jax.random.PRNGKey(2))
    out = sample(SCH, eps_fn, xT, cfg, rng=jax.random.PRNGKey(3),
                 tile_resident=True)
    np.testing.assert_allclose(float(out.mean()), float(ref.mean()),
                               atol=0.05)
    np.testing.assert_allclose(float(out.std()), float(ref.std()), atol=0.05)


def test_eta0_bitwise_rng_independent():
    """Regression: the deterministic sampler's output must be bitwise
    identical for different rng keys (noise is skipped, not zero-scaled)."""
    eps_fn = analytic_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (16, 2))
    cfg = SamplerConfig(S=10)
    for tile in (False, True):
        a = sample(SCH, eps_fn, xT, cfg, rng=jax.random.PRNGKey(11),
                   tile_resident=tile)
        b = sample(SCH, eps_fn, xT, cfg, rng=jax.random.PRNGKey(999),
                   tile_resident=tile)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tile_resident_trajectory_and_bf16():
    eps_fn = analytic_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (4, 2), jnp.bfloat16)
    x0, traj = sample(SCH, eps_fn, xT, SamplerConfig(S=7),
                      tile_resident=True, return_trajectory=True)
    assert x0.dtype == jnp.bfloat16
    assert traj.shape == (8, 4, 2)
    np.testing.assert_array_equal(np.asarray(traj[-1], np.float32),
                                  np.asarray(x0, np.float32))


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


def _scan_body_prims(fn, *args):
    """Primitive names inside every lax.scan body of fn's jaxpr."""
    out = []

    def find(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "scan":
                out.extend(_collect_prims(eqn.params["jaxpr"].jaxpr, []))
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    find(v.jaxpr)

    find(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def test_tile_resident_scan_body_has_no_layout_conversion():
    """Acceptance: exactly one layout conversion per sample() call — the
    scan body must contain NO pad/reshape/slice of the state (with a
    tile-aware model there is no conversion of anything at all)."""
    eps_fn = tile_aware_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    body = _scan_body_prims(
        lambda x: sample(SCH, eps_fn, x, SamplerConfig(S=5),
                         tile_resident=True), xT)
    banned = {"pad", "reshape", "gather", "slice"}
    assert not banned & set(body), sorted(banned & set(body))


def test_legacy_fused_path_does_pay_per_step_conversion():
    """Contrast check: the pre-refactor kernel path pads/reshapes every
    step (this is exactly the traffic the tentpole removes)."""
    from repro.kernels import fused_ddim_step
    eps_fn = analytic_eps(SCH)
    xT = jax.random.normal(jax.random.PRNGKey(0), (7, 333))
    body = _scan_body_prims(
        lambda x: sample(SCH, eps_fn, x, SamplerConfig(S=5),
                         step_impl=fused_ddim_step), xT)
    assert "pad" in body


def test_deterministic_scan_has_no_random_ops():
    """Acceptance: the eta=0 sampler's scan contains no threefry/PRNG ops
    on either path (noise generation is skipped, not multiplied by 0)."""
    xT = jax.random.normal(jax.random.PRNGKey(0), (256, 256))
    for fn in (
        lambda x: sample(SCH, analytic_eps(SCH), x, SamplerConfig(S=5)),
        lambda x: sample(SCH, tile_aware_eps(SCH), x, SamplerConfig(S=5),
                         tile_resident=True),
    ):
        body = _scan_body_prims(fn, xT)
        rand = [p for p in body if "threefry" in p or "random" in p
                or "prng" in p]
        assert not rand, rand


def test_stochastic_scan_draws_no_host_randomness():
    """The stochastic tile-resident scan keeps jax.random OUT of the loop:
    per-step seeds are precomputed, noise is drawn in-kernel."""
    body = _scan_body_prims(
        lambda x, r: sample(SCH, tile_aware_eps(SCH), x,
                            SamplerConfig(S=5, eta=1.0), rng=r,
                            tile_resident=True),
        jax.random.normal(jax.random.PRNGKey(0), (256, 256)),
        jax.random.PRNGKey(1))
    rand = [p for p in body if "threefry" in p or "random_bits" in p]
    assert not rand, rand


def test_coefficients_fp32_under_bf16_state():
    """dtype policy: trajectory coefficients are fp32 even when sampling
    in bf16 (the kernel computes fp32 internally)."""
    coefs = trajectory_coefficients(SCH, SamplerConfig(S=10, eta=1.0))
    for k, v in coefs.items():
        if k != "t":
            assert v.dtype == jnp.float32, k
