"""Per-kernel allclose sweeps: shapes x dtypes against the ref.py oracles,
executed in interpret mode (the kernel body runs in Python on CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import SamplerConfig, make_schedule, sample, ddim_sample
from repro.kernels import (fused_ddim_step, gqa_flash, mha_flash,
                           rms_norm_kernel)
from repro.kernels.ddim_step.ref import ddim_step_ref
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rmsnorm.ref import rms_norm_ref

TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=2e-2, rtol=2e-2)}


# ------------------------------------------------------------- ddim_step
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 16, 16, 3), (2, 100), (7, 333),
                                   (1, 64, 32), (3, 8, 8, 8, 3), (256, 256)])
def test_ddim_step_sweep(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], shape, dtype)
    e = jax.random.normal(ks[1], shape, dtype)
    n = jax.random.normal(ks[2], shape, dtype)
    c = (0.98, 0.15, 0.02, 0.97, 0.24)
    out = fused_ddim_step(x, e, n, *c)
    ref = ddim_step_ref(x, e, n, *c)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@given(c_x0=st.floats(0.1, 1.0), c_dir=st.floats(0.0, 1.0),
       c_noise=st.floats(0.0, 0.5), a_t=st.floats(0.01, 0.999))
@settings(max_examples=25, deadline=None)
def test_ddim_step_property_coefficients(c_x0, c_dir, c_noise, a_t):
    """Property: kernel == oracle for arbitrary valid coefficient values."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x, e, n = (jax.random.normal(k, (4, 64)) for k in ks)
    args = (c_x0, c_dir, c_noise, a_t ** 0.5, (1 - a_t) ** 0.5)
    np.testing.assert_allclose(fused_ddim_step(x, e, n, *args),
                               ddim_step_ref(x, e, n, *args),
                               atol=1e-4, rtol=1e-4)


def test_ddim_step_is_dropin_for_sampler():
    """sample(..., step_impl=kernel) == sample(..., default) exactly the
    same trajectory (paper Eq. 12 fused in one kernel)."""
    sch = make_schedule("linear", T=200)
    def eps_fn(x, t):
        a = sch.alpha_bar[t].reshape(-1, 1)
        return x / jnp.sqrt(1 - a + a * 0.25)
    xT = jax.random.normal(jax.random.PRNGKey(0), (8, 32))
    a = sample(sch, eps_fn, xT, SamplerConfig(S=10))
    b = sample(sch, eps_fn, xT, SamplerConfig(S=10),
               step_impl=fused_ddim_step)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


# -------------------------------------------------------- flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,S,D", [(2, 4, 256, 64), (1, 2, 128, 128),
                                     (2, 1, 512, 32), (1, 8, 384, 64)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_sweep(B, H, S, D, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, H, S, D), dtype)
    v = jax.random.normal(ks[2], (B, H, S, D), dtype)
    out = mha_flash(q, k, v, causal=causal)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("block", [(64, 64), (128, 64), (64, 128)])
def test_flash_attention_block_shapes(block):
    """Output must be invariant to the BlockSpec tiling choice."""
    bq, bk = block
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 2, 256, 64))
    k = jax.random.normal(ks[1], (2, 2, 256, 64))
    v = jax.random.normal(ks[2], (2, 2, 256, 64))
    out = mha_flash(q, k, v, causal=True, block_q=bq, block_k=bk)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_gqa_flash_matches_model_attention():
    from repro.models.attention import _grouped_attention
    from repro.models.common import causal_mask
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 128, 8, 32))
    k = jax.random.normal(ks[1], (2, 128, 2, 32))
    v = jax.random.normal(ks[2], (2, 128, 2, 32))
    ref = _grouped_attention(q, k, v, jnp.maximum(causal_mask(128), -1e30))
    out = gqa_flash(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_scale_invariance_property():
    """Softmax shift invariance: adding a constant to all logits (via a
    constant key direction) must not change the output."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 1, 128, 64))
    k = jax.random.normal(ks[1], (1, 1, 128, 64))
    v = jax.random.normal(ks[2], (1, 1, 128, 64))
    out1 = mha_flash(q, k, v)
    out2 = mha_flash(q, k + 0 * q, v)
    np.testing.assert_allclose(out1, out2, atol=1e-6)


# ---------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 128), (3, 17, 96), (2, 5, 7, 64),
                                   (1000, 256), (1, 64)])
def test_rmsnorm_sweep(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape, dtype)
    s = jax.random.normal(jax.random.PRNGKey(1), (shape[-1],), dtype)
    out = rms_norm_kernel(x, s)
    ref = rms_norm_ref(x, s)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_rmsnorm_matches_model_rmsnorm():
    from repro.models.common import rms_norm
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 33, 192))
    s = jnp.ones((192,))
    np.testing.assert_allclose(rms_norm_kernel(x, s), rms_norm(x, s),
                               atol=2e-6, rtol=2e-6)


@given(rows=st.integers(1, 300), d=st.sampled_from([32, 64, 128, 256]))
@settings(max_examples=15, deadline=None)
def test_rmsnorm_property_shapes(rows, d):
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d))
    s = jnp.ones((d,))
    np.testing.assert_allclose(rms_norm_kernel(x, s), rms_norm_ref(x, s),
                               atol=2e-5, rtol=2e-5)
