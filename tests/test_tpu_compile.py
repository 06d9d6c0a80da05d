"""The serving path compiled for a described TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a chip that is described (``jax.experimental.topologies``), and it
refuses here what it would refuse on the chip. Interpret mode hides those
refusals, so every program below is compiled with ``interpret=False`` at
the CIFAR10 U-Net's serving shapes (4 slots of 32x32x3, float32). Nothing
runs; these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.sampler_step import ops
from repro.kernels.sampler_step.kernel import (COEF_COLS, sampler_step_2d,
                                               sampler_step_rows_2d)
from repro.models import unet

SLOTS = 4
SAMPLE = (32, 32, 3)
HBM_BYTES = 16 * 10 ** 9          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with JAX's persistent
    compilation cache off (a compile for a described chip is written to
    it but can never be read back without the chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            try:
                topo = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return 'custom_call_target="tpu_custom_call"' in compiled.as_text()


@pytest.mark.parametrize("stochastic,hw_prng,want_x0", [
    (False, False, False),
    (False, False, True),
    (True, True, False),
    (True, True, True),
    (True, False, False),
], ids=["det", "det-preview", "hw-prng", "hw-prng-preview", "sw-prng"])
def test_rows_kernel_compiles_for_v5e(one_chip, stochastic, hw_prng,
                                      want_x0):
    """The scheduler tick's per-row step kernel, every variant a CIFAR10
    pool can pick (the served one is hw-prng-preview)."""
    R = SLOTS * ops.slot_rows(SAMPLE)
    C = ops.TILE_C

    def step(x, eps, coefs, seeds):
        return sampler_step_rows_2d(x, eps, coefs,
                                    seeds if stochastic else None,
                                    stochastic=stochastic, want_x0=want_x0,
                                    hw_prng=hw_prng, interpret=False)

    compiled = jax.jit(step).lower(
        _sds(one_chip, (R, C)), _sds(one_chip, (R, C)),
        _sds(one_chip, (R, COEF_COLS)),
        _sds(one_chip, (R,), jnp.int32)).compile()
    assert _has_kernel(compiled)


@pytest.mark.parametrize("hw_prng", [True, False], ids=["hw-prng", "sw-prng"])
def test_lockstep_stochastic_kernel_compiles_for_v5e(one_chip, hw_prng):
    """The lockstep (one coefficient set per call) stochastic kernel over
    a CIFAR10 batch of SLOTS samples in the padded tile layout."""
    n = SLOTS * int(np.prod(SAMPLE))
    R = jax.eval_shape(lambda: ops.to_tile_layout(
        jnp.zeros((n,), jnp.float32))[0]).shape[0]
    C = ops.TILE_C

    def step(x, eps, coefs, seed):
        return sampler_step_2d(x, eps, coefs, seed, stochastic=True,
                               hw_prng=hw_prng, interpret=False)

    compiled = jax.jit(step).lower(
        _sds(one_chip, (R, C)), _sds(one_chip, (R, C)),
        _sds(one_chip, (5,)), _sds(one_chip, (), jnp.int32)).compile()
    assert _has_kernel(compiled)


def test_cifar10_unet_forward_compiles_for_v5e_and_fits(one_chip):
    """The paper's CIFAR10 U-Net (35.7M parameters) at batch = slots, from
    parameter shapes only; weights, activations and scratch fit one chip."""
    ucfg = configs.CIFAR10_UNET
    shapes = jax.eval_shape(
        lambda: unet.init_params(jax.random.PRNGKey(0), ucfg))
    assert sum(int(np.prod(w.shape))
               for w in jax.tree.leaves(shapes)) == 35_725_696
    params = jax.tree.map(lambda w: _sds(one_chip, w.shape, w.dtype), shapes)
    compiled = jax.jit(lambda p, x, t: unet.forward(p, ucfg, x, t)).lower(
        params, _sds(one_chip, (SLOTS,) + SAMPLE),
        _sds(one_chip, (SLOTS,), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 4 * 35_725_696 <= mem.argument_size_in_bytes
    assert used < HBM_BYTES
