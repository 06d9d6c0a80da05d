"""Dry-run machinery on the LOCAL device mesh (smoke configs, 1 CPU):
the same lower->compile pipeline the 512-device production dry-run uses,
plus the HLO analyzer on real compiled modules."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import shapes as shp
from repro.launch.hlo_analysis import aggregate
from repro.launch.roofline import analyze, lm_model_flops
from repro.models import get_api
from repro.sharding import replicated, shard_batch, shard_cache, shard_params
from repro.training import (AdamWConfig, TrainState, init_train_state,
                            make_lm_train_step)


@pytest.fixture(scope="module")
def mesh():
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))


def _lower_smoke_train(arch, mesh, B=2, S=16):
    cfg = configs.get_smoke(arch)
    api = get_api(cfg)
    param_shapes = jax.eval_shape(
        functools.partial(api.init_params, cfg=cfg), jax.random.PRNGKey(0))
    p_shard = shard_params(param_shapes, mesh)
    opt_cfg = AdamWConfig()
    from repro.training.optim import adamw_init
    opt_shapes = jax.eval_shape(adamw_init, param_shapes)
    state_shapes = TrainState(param_shapes, opt_shapes,
                              jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    state_shard = TrainState(p_shard, shard_params(opt_shapes, mesh),
                             replicated(mesh))
    inputs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.family in ("vlm", "audio"):
        inputs["embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_ctx_embeds, cfg.d_model), jnp.float32)
    in_shard = shard_batch(inputs, mesh)
    step = make_lm_train_step(cfg, opt_cfg)
    metrics_shard = {k: replicated(mesh)
                     for k in ("loss", "aux", "grad_norm", "lr")}
    jitted = jax.jit(step, in_shardings=(state_shard, in_shard),
                     out_shardings=(state_shard, metrics_shard))
    with mesh:
        return jitted.lower(state_shapes, inputs), cfg


@pytest.mark.parametrize("arch", ["smollm-135m", "kimi-k2-1t-a32b",
                                  "rwkv6-7b", "zamba2-2.7b",
                                  "seamless-m4t-large-v2",
                                  "llava-next-mistral-7b"])
def test_smoke_train_step_lowers_and_compiles(arch, mesh):
    lowered, cfg = _lower_smoke_train(arch, mesh)
    compiled = lowered.compile()
    assert compiled.cost_analysis() is not None


def test_hlo_analyzer_loop_correction(mesh):
    """The analyzer must multiply scan-body flops by the layer count."""
    lowered, cfg = _lower_smoke_train("smollm-135m", mesh)
    compiled = lowered.compile()
    tot = aggregate(compiled.as_text())
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):  # pre-0.4.30 jaxlib: one dict per device
        ca = ca[0] if ca else {}
    raw = float(ca.get("flops", 0.0))
    # loop-corrected flops must exceed raw (scan body counted once) and the
    # trip counts must include the layer count
    assert tot["flops"] > raw
    assert cfg.n_layers in tot["trip_counts"].values()


def test_roofline_terms_positive_and_bottleneck(mesh):
    lowered, cfg = _lower_smoke_train("smollm-135m", mesh)
    compiled = lowered.compile()
    terms = analyze(compiled, compiled.as_text(), n_chips=1,
                    device_kind="TPU v5 lite",
                    model_flops=lm_model_flops(10_000_000, 2 * 16))
    assert terms.compute_s > 0 and terms.memory_s > 0
    assert terms.bottleneck in ("compute", "memory", "collective")
    assert 0 < terms.useful_ratio


def test_peaks_keyed_by_device_kind_unknown_kind_raises():
    """The roofline reads peaks from one table keyed by device_kind; a
    device that is not in it is an error, never a default."""
    from repro.launch.mesh import PEAKS, peaks
    assert peaks("TPU v5 lite")["flops_bf16"] == 197e12
    assert peaks("TPU v5 lite")["hbm_bw"] == 819e9
    assert set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError, match="cpu"):
        peaks("cpu")


def test_input_specs_all_combos_shapes():
    """input_specs/cache_specs produce well-formed abstract values for every
    (arch x shape) without allocation."""
    for arch in configs.ARCH_IDS:
        for shape_id in shp.SHAPE_IDS:
            combo = shp.resolve(configs.get(arch), shape_id)
            specs = shp.input_specs(combo)
            assert "tokens" in specs
            B = combo.batch
            assert specs["tokens"].shape[0] == B
            if combo.kind == "train" and combo.arch.family == "vlm":
                total = (specs["tokens"].shape[1] +
                         specs["embeds"].shape[1])
                assert total == combo.seq_len
            if combo.kind != "train":
                cache = shp.cache_specs(combo)
                assert len(jax.tree.leaves(cache)) > 0


def test_long500k_policy():
    """windowed variants only for full-attention families."""
    for arch in configs.ARCH_IDS:
        combo = shp.resolve(configs.get(arch), "long_500k")
        fam = configs.get(arch).family
        if fam in ("ssm", "hybrid"):
            assert not combo.windowed, arch
        else:
            assert combo.windowed, arch
            assert combo.arch.sliding_window == shp.WINDOW


def test_production_mesh_shapes():
    from repro.launch.mesh import make_production_mesh
    n = len(jax.devices())
    if n < 512:
        pytest.skip("production mesh needs 512 placeholder devices "
                    "(dryrun sets XLA_FLAGS before jax init)")
    mesh = make_production_mesh()
    assert dict(mesh.shape) == {"data": 16, "model": 16}
