"""Substrate tests: optimizers, EMA, schedules, checkpointing, synthetic
data pipelines, sharding rules, serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.data import GaussianMixture2D, SyntheticImages, SyntheticTokens
from repro.sharding import (batch_spec, data_axes, shard_params,
                            spec_for_param)
from repro.training import (AdafactorConfig, AdamWConfig, adamw_init,
                            adamw_update, clip_by_global_norm, ema_init,
                            ema_update, global_norm, warmup_cosine,
                            checkpoint)
from repro.training.optim import adafactor_init, adafactor_update


# ----------------------------------------------------------------- optim
def _quad_problem():
    params = {"w": jnp.asarray([3.0, -2.0]), "b": jnp.asarray(1.5)}

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2

    return params, loss


def test_adamw_converges_on_quadratic():
    params, loss = _quad_problem()
    cfg = AdamWConfig(lr=0.1, clip_norm=0.0)
    state = adamw_init(params)
    for _ in range(200):
        grads = jax.grad(loss)(params)
        params, state, m = adamw_update(cfg, grads, state, params)
    assert float(loss(params)) < 1e-3
    assert float(m["grad_norm"]) < 1.0


def test_adafactor_converges_on_quadratic():
    params = {"w": jnp.ones((4, 3)) * 2.0}
    cfg = AdafactorConfig(lr=0.3)

    def loss(p):
        return jnp.sum(p["w"] ** 2)

    start = float(loss(params))
    state = adafactor_init(params)
    for _ in range(800):
        grads = jax.grad(loss)(params)
        params, state, _ = adafactor_update(cfg, grads, state, params)
    assert float(loss(params)) < start / 50


def test_adafactor_state_is_factored():
    params = {"w": jnp.zeros((64, 32)), "v": jnp.zeros((10,))}
    state = adafactor_init(params)
    assert state.vr["w"].shape == (64,)
    assert state.vc["w"].shape == (32,)
    assert state.v["v"].shape == (10,)
    # factored state is ~sqrt of adam's
    adam = adamw_init(params)
    n_af = sum(x.size for x in jax.tree.leaves((state.vr, state.vc)))
    n_adam = sum(x.size for x in jax.tree.leaves((adam.mu, adam.nu)))
    assert n_af < n_adam / 10


def test_clip_by_global_norm():
    g = {"a": jnp.ones((10,)) * 100.0}
    clipped, norm = clip_by_global_norm(g, 1.0)
    np.testing.assert_allclose(float(global_norm(clipped)), 1.0, rtol=1e-4)
    assert float(norm) > 100.0


def test_ema_tracks_params():
    p = {"w": jnp.zeros(3)}
    ema = ema_init(p)
    target = {"w": jnp.ones(3)}
    for _ in range(500):
        ema = ema_update(ema, target, decay=0.99)
    np.testing.assert_allclose(np.asarray(ema["w"]), 1.0, atol=1e-2)


def test_warmup_cosine_schedule():
    sched = warmup_cosine(10, 100)
    assert float(sched(jnp.asarray(0))) == 0.0
    np.testing.assert_allclose(float(sched(jnp.asarray(10))), 1.0, rtol=1e-5)
    assert float(sched(jnp.asarray(100))) <= 0.11


# ------------------------------------------------------------ checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16),
                       "c": jnp.asarray(3, jnp.int32)}}
    path = os.path.join(tmp_path, "ck.npz")
    checkpoint.save(path, tree, step=7)
    restored, meta = checkpoint.restore(path, tree)
    assert meta["step"] == 7
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_shape_mismatch_raises(tmp_path):
    path = os.path.join(tmp_path, "ck.npz")
    checkpoint.save(path, {"a": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        checkpoint.restore(path, {"a": jnp.zeros((3, 2))})


def test_checkpoint_gc_keeps_latest(tmp_path):
    d = str(tmp_path)
    for step in (1, 2, 3, 4, 5):
        checkpoint.save_step(d, step, {"a": jnp.zeros(1)}, keep=2)
    latest = checkpoint.latest(d)
    assert latest.endswith("00000005.npz")
    assert len([f for f in os.listdir(d) if f.endswith(".npz")]) == 2


# ------------------------------------------------------------------ data
def test_gmm_pipeline_deterministic():
    d = GaussianMixture2D(seed=3)
    a = next(d.batches(64))
    b = next(GaussianMixture2D(seed=3).batches(64))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_gmm_mode_assignment():
    d = GaussianMixture2D()
    modes = d.modes()
    assign = d.mode_assignment(modes)
    np.testing.assert_array_equal(assign, np.arange(d.n_modes))


def test_images_range_and_shape():
    d = SyntheticImages(size=8)
    x = d.sample(jax.random.PRNGKey(0), 4)
    assert x.shape == (4, 8, 8, 3)
    assert float(jnp.abs(x).max()) <= 1.0


def test_tokens_follow_markov_chain():
    d = SyntheticTokens(vocab=32, seed=1)
    toks = d.sample(jax.random.PRNGKey(0), 8, 64)
    assert toks.shape == (8, 64)
    assert d.bigram_validity(np.asarray(toks)) == 1.0
    # random tokens are mostly invalid
    rnd = np.random.RandomState(0).randint(0, 32, (8, 64))
    assert d.bigram_validity(rnd) < 0.5


# -------------------------------------------------------------- sharding
@pytest.fixture(scope="module")
def mesh():
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"))


def test_param_rules_shard_expected_dims(mesh):
    from jax.sharding import PartitionSpec as P
    assert spec_for_param("layers/attn/wq", (30, 512, 512), mesh) == \
        P(None, None, "model" if 512 % mesh.shape["model"] == 0 else None)
    assert spec_for_param("layers/moe/w_gate", (60, 8, 128, 64), mesh)[1] \
        in ("model", None)
    assert spec_for_param("embed", (1024, 64), mesh)[0] in ("model", None)
    # norms replicate
    assert spec_for_param("layers/attn_norm", (30, 512), mesh) == \
        P(None, None)


def test_indivisible_dims_replicate():
    m = jax.make_mesh((1, 1), ("data", "model"))
    spec = spec_for_param("attn/wk", (64, 7), m)  # 7 % 1 == 0 -> sharded ok
    # with model axis size 1 everything divides; use a fake bigger mesh via
    # the rule function contract instead:
    from repro.sharding.rules import _divisible
    assert _divisible((7,), ("model",), jax.make_mesh(
        (1, 1), ("data", "model"))) == ("model",)


def test_shard_params_covers_whole_tree(mesh):
    from repro import configs
    from repro.models import get_api
    cfg = configs.get_smoke("smollm-135m")
    api = get_api(cfg)
    import functools
    shapes = jax.eval_shape(
        functools.partial(api.init_params, cfg=cfg), jax.random.PRNGKey(0))
    shardings = shard_params(shapes, mesh)
    assert (len(jax.tree.leaves(shardings)) ==
            len(jax.tree.leaves(shapes)))


def test_batch_spec_divisibility(mesh):
    from jax.sharding import PartitionSpec as P
    n = mesh.shape["data"]
    assert batch_spec(mesh, n * 4, 2)[0] in ("data", ("data",))
    if n > 1:  # on a 1-device CPU mesh everything divides
        assert batch_spec(mesh, n * 4 + 1, 2)[0] is None


# --------------------------------------------------------------- serving
def test_ar_generator_greedy_deterministic():
    from repro import configs
    from repro.models import get_api
    from repro.serving import ARGenerator, GenRequest
    cfg = configs.get_smoke("smollm-135m")
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    gen = ARGenerator(cfg, params, batch_size=2, max_len=48)
    reqs = [GenRequest(prompt=np.arange(8, dtype=np.int32),
                       max_new_tokens=6) for _ in range(2)]
    r1 = gen.generate(reqs)
    r2 = gen.generate(reqs)
    np.testing.assert_array_equal(r1[0].tokens, r2[0].tokens)
    np.testing.assert_array_equal(r1[0].tokens, r1[1].tokens)


def test_diffusion_sampler_service():
    from repro.core import SamplerConfig, make_schedule
    from repro.serving import DiffusionSampler
    sch = make_schedule("linear", T=100)

    def eps_fn(x, t):
        a = sch.alpha_bar[t].reshape((-1,) + (1,) * (x.ndim - 1))
        return x / jnp.sqrt(1 - a + a)

    svc = DiffusionSampler(sch, eps_fn, (4,), batch_size=8)
    samples, stats = svc.serve(20, SamplerConfig(S=5), seed=0)
    assert samples.shape == (20, 4)
    assert stats["batches"] == 3
    assert stats["net_evals_per_sample"] == 5


# -------------------------------------------------- gradient accumulation
def test_grad_accum_matches_single_step():
    """accum_steps microbatching must produce identical updates."""
    from repro import configs
    from repro.models import get_api
    from repro.training import (AdamWConfig, init_train_state,
                                make_lm_train_step)
    cfg = configs.get_smoke("smollm-135m")
    api = get_api(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    opt = AdamWConfig(lr=1e-3)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, cfg.vocab)
    batch = {"tokens": toks}
    s1 = init_train_state(params, jax.random.PRNGKey(2), opt)
    s2 = init_train_state(params, jax.random.PRNGKey(2), opt)
    step1 = make_lm_train_step(cfg, opt, accum_steps=1)
    step4 = make_lm_train_step(cfg, opt, accum_steps=4)
    s1, m1 = step1(s1, batch)
    s2, m4 = step4(s2, batch)
    # loss metric: mean over microbatches == full-batch loss
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-4)
    # accumulated grad norm == full-batch grad norm (grads identical up to
    # accumulation-order rounding; Adam's first step amplifies ~1e-8 grad
    # noise to ~lr-sized param deltas, so params are compared loosely)
    np.testing.assert_allclose(float(m1["grad_norm"]),
                               float(m4["grad_norm"]), rtol=1e-4)
    lr = 1e-3
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2.5 * lr)
