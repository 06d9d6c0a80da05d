"""The yardstick's arithmetic against XLA and against the engine."""
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(ROOT / "src"))

import flops  # noqa: E402
import reference  # noqa: E402

CONFIGS = {"cifar10-unet": 11.67e9, "celeba64-unet": 46.21e9}


def config(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


def unet_config(cfg):
    from repro.models.unet import UNetConfig
    keys = ("in_channels", "base_width", "width_mults", "n_res_blocks",
            "attn_levels", "time_dim", "groups")
    return UNetConfig(**{k: tuple(cfg[k]) if isinstance(cfg[k], list)
                         else cfg[k] for k in keys})


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_unet_flops_match_xla_cost_analysis(name):
    from repro.models import unet
    cfg = config(name)
    ucfg = unet_config(cfg)
    shapes = jax.eval_shape(lambda: unet.init_params(jax.random.PRNGKey(0),
                                                     ucfg))
    H = cfg["image_size"]
    lowered = jax.jit(lambda p, x, t: unet.forward(p, ucfg, x, t)).lower(
        shapes, jax.ShapeDtypeStruct((1, H, H, 3), jnp.float32),
        jax.ShapeDtypeStruct((1,), jnp.int32))
    xla = lowered.cost_analysis()["flops"]
    ours = flops.unet_flops(cfg)
    # convolutions, dense layers and attention products; XLA also counts
    # the elementwise work (0.4% of it at these sizes)
    assert 0.99 * xla <= ours <= xla
    assert ours == pytest.approx(CONFIGS[name], rel=0.01)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_layout_is_the_programs(name):
    from repro.models import unet
    cfg = config(name)
    shapes = jax.eval_shape(lambda: unet.init_params(jax.random.PRNGKey(0),
                                                     unet_config(cfg)))
    trunk = reference.trunk(cfg)
    ours = trunk._layout(cfg)
    theirs = jax.tree.map(lambda a: tuple(a.shape), shapes)
    is_leaf = trunk._is_shape
    assert (jax.tree.structure(ours, is_leaf=is_leaf)
            == jax.tree.structure(theirs, is_leaf=is_leaf))
    assert (jax.tree.leaves(ours, is_leaf=is_leaf)
            == jax.tree.leaves(theirs, is_leaf=is_leaf))
    assert trunk.param_count(cfg) == cfg["params"]


@pytest.mark.parametrize("name,slots", [("cifar10-unet", 32),
                                        ("celeba64-unet", 16)])
@pytest.mark.parametrize("stochastic,preview", [(True, True),
                                                (False, False)])
def test_step_kernel_bytes_follow_the_engine_geometry(name, slots,
                                                      stochastic, preview):
    from repro.core import make_schedule
    from repro.serving.scheduler import ContinuousBatchingEngine
    cfg = config(name)
    H = cfg["image_size"]
    eng = ContinuousBatchingEngine(
        make_schedule("linear", T=1000), lambda x, t: x, (H, H, 3), slots,
        stochastic=stochastic, preview=preview)
    R, C = eng._x2.shape
    assert R == slots * flops.slot_rows(cfg) and C == flops.TILE_C
    # what one call of the program's per-row kernel reads and writes, at
    # the engine's own slot-tile geometry
    from repro.kernels.sampler_step import ops
    coefs = ops.expand_slot_coefs(jnp.zeros((slots, 5)), eng._rps)
    seeds = jnp.zeros((R,), jnp.int32) if stochastic else None
    args = [eng._x2, eng._x2, coefs] + ([seeds] if stochastic else [])
    out = jax.eval_shape(lambda x, e, c, s=None: ops.sampler_step_rows(
        x, e, c, s, stochastic=stochastic, want_x0=preview), *args)
    moved = sum(a.size * a.dtype.itemsize
                for a in args + list(jax.tree.leaves(out)))
    assert flops.step_kernel_bytes(cfg, slots, stochastic=stochastic,
                                   preview=preview) == moved
