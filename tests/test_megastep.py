"""Megakernel sampler tests (ISSUE 4 tentpole).

Acceptance criteria covered here:
  * eta=0 order-1 ``backend='mega'`` output is BIT-IDENTICAL to
    ``backend='tile_resident'`` (and jnp) on the diffusion-LM smoke
    config — uniform tau, clip policy, every K chunking including ragged
    remainders;
  * the K-step fused trajectory lowers to exactly ceil(S/K) pallas_call
    equations with NO per-step state pad/reshape between them and no PRNG
    ops anywhere (jaxpr-asserted, the PR 1 residency-contract style);
  * automatic eligibility: stochastic/multistep/trajectory runs, models
    without a mega_spec, and VMEM-overflowing trunks all fall back to the
    tile-resident scan;
  * the per-row flavor advances the continuous-batching scheduler's slots
    bit-identically to the unfused tick, in one trace;
  * ref.py oracles pin both kernel flavors (fp32-tight: the oracle runs
    eagerly outside the kernel's compiled region);
  * make_tile_eps_fn attaches the VMEM-budget metadata, and generate()'s
    misaligned-latent fallback takes the adapter path and matches the
    natural-shape sampler (ISSUE 4 small-fix satellite).

Bit-identity caveat (same one docs/sampling.md states for multistep
tile_resident): the mega <-> tile_resident bit contract holds for the
un-jitted plan.run execution the serving paths use; wrapping BOTH sides
in one outer jax.jit lets XLA contract the trunk's FMA chains differently
per path, which degrades agreement to fp32-tight.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import diffusion_lm as dlm
from repro.core import SamplerConfig, make_schedule
from repro.kernels import megastep
from repro.kernels.megastep import ref as mega_ref
from repro.kernels.sampler_step import ops as tile_ops
from repro.models.common import ArchConfig
from repro.sampling import SamplerPlan
from repro.serving.scheduler import ContinuousBatchingEngine, SampleRequest

SCH = make_schedule("linear", T=1000)


def _tiny_dlm(n_heads=2, n_kv_heads=2, latent=32):
    arch = ArchConfig(name="mega-test", family="dense", n_layers=2,
                      d_model=64, n_heads=n_heads, n_kv_heads=n_kv_heads,
                      d_ff=128, vocab=50)
    cfg = dlm.DiffusionLMConfig(arch=arch, time_dim=32, latent_dim=latent)
    params = dlm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _eps_and_x(B=2, seq=64, **kw):
    cfg, params = _tiny_dlm(**kw)
    eps = dlm.make_tile_eps_fn(params, cfg, B, seq)
    xT = jax.random.normal(jax.random.PRNGKey(1), (B, seq, cfg.latent_dim))
    return cfg, params, eps, xT


# --------------------------------------------------------- bit identity
@pytest.mark.parametrize("k_fuse", [1, 2, 4, None],
                         ids=["K1", "K2", "K4-ragged", "Kdefault"])
def test_mega_bit_identical_to_tile_resident(k_fuse):
    """Acceptance: eta=0 order-1 mega == tile_resident == jnp, bitwise,
    for every chunking (S=6 with K=4 exercises the ragged last chunk)."""
    _, _, eps, xT = _eps_and_x()
    plan = SamplerPlan.build(SCH, tau=6)
    tile = np.asarray(plan.run(eps, xT, backend="tile_resident"))
    mega = np.asarray(plan.run(eps, xT, backend="mega", k_fuse=k_fuse))
    ref = np.asarray(plan.run(eps, xT, backend="jnp"))
    np.testing.assert_array_equal(mega, tile)
    np.testing.assert_array_equal(mega, ref)
    assert np.isfinite(mega).all()


def test_mega_bit_identical_with_clip_and_gqa():
    """The clip specialization and a grouped-KV trunk hold the contract."""
    _, _, eps, xT = _eps_and_x(n_heads=4, n_kv_heads=2)
    plan = SamplerPlan.build(SCH, tau=5, x0=1.5)
    tile = np.asarray(plan.run(eps, xT, backend="tile_resident"))
    mega = np.asarray(plan.run(eps, xT, backend="mega", k_fuse=3))
    np.testing.assert_array_equal(mega, tile)


def test_mega_k_chunks_all_equal():
    """Chunk size is a pure launch-count knob: every K gives one answer."""
    _, _, eps, xT = _eps_and_x()
    plan = SamplerPlan.build(SCH, tau=7)
    outs = [np.asarray(plan.run(eps, xT, backend="mega", k_fuse=k))
            for k in (1, 3, 7)]
    np.testing.assert_array_equal(outs[0], outs[1])
    np.testing.assert_array_equal(outs[0], outs[2])


# ------------------------------------------------------- jaxpr contract
def _top_prims(fn, *args):
    return [eqn.primitive.name
            for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns]


@pytest.mark.parametrize("S,K", [(6, 2), (7, 3), (5, 8)])
def test_mega_jaxpr_launch_count_and_residency(S, K):
    """Acceptance: the fused trajectory is exactly ceil(S/K) kernel calls
    with the (R, C) state carried between them — no pad anywhere, at most
    the entry/exit reshape pair of the tile-layout contract, and no PRNG
    (the deterministic megakernel contains no noise code at all)."""
    _, _, eps, xT = _eps_and_x()
    plan = SamplerPlan.build(SCH, tau=S)
    prims = _top_prims(
        lambda x: plan.run(eps, x, backend="mega", k_fuse=K), xT)
    assert prims.count("pallas_call") == -(-S // K)
    assert "pad" not in prims
    # the tile-layout conversions (ravel+reshape in, reshape out) happen
    # ONCE per trajectory: no reshape between consecutive kernel calls
    calls = [i for i, p in enumerate(prims) if p == "pallas_call"]
    reshapes = [i for i, p in enumerate(prims) if p == "reshape"]
    assert all(i < calls[0] or i > calls[-1] for i in reshapes), prims
    bad = [p for p in prims if "threefry" in p or "random" in p
           or "prng" in p]
    assert not bad, bad


def test_mega_kernel_body_has_no_prng():
    """Inside the kernel jaxpr too: trunk + update trace no random ops."""
    _, _, eps, xT = _eps_and_x()
    plan = SamplerPlan.build(SCH, tau=4)
    jaxpr = jax.make_jaxpr(
        lambda x: plan.run(eps, x, backend="mega", k_fuse=4))(xT)

    def walk(jx, acc):
        for eqn in jx.eqns:
            acc.append(eqn.primitive.name)
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr, acc)
        return acc

    prims = walk(jaxpr.jaxpr, [])
    bad = [p for p in prims if "threefry" in p or "random" in p
           or "prng" in p]
    assert not bad, bad


# ------------------------------------------------------------ fallbacks
def test_mega_falls_back_for_stochastic_plans():
    """A stochastic plan silently runs the tile-resident scan: identical
    output for the identical rng."""
    _, _, eps, xT = _eps_and_x()
    plan = SamplerPlan.build(SCH, tau=4, sigma=1.0)
    rng = jax.random.PRNGKey(3)
    a = np.asarray(plan.run(eps, xT, rng, backend="tile_resident"))
    b = np.asarray(plan.run(eps, xT, rng, backend="mega"))
    np.testing.assert_array_equal(a, b)


def test_mega_falls_back_for_multistep_and_trajectory():
    _, _, eps, xT = _eps_and_x()
    plan = SamplerPlan.build(SCH, tau=5, order=2)
    a = np.asarray(plan.run(eps, xT, backend="tile_resident"))
    b = np.asarray(plan.run(eps, xT, backend="mega"))
    np.testing.assert_array_equal(a, b)
    plan1 = SamplerPlan.build(SCH, tau=4)
    x0a, tra = plan1.run(eps, xT, backend="tile_resident",
                         return_trajectory=True)
    x0b, trb = plan1.run(eps, xT, backend="mega", return_trajectory=True)
    np.testing.assert_array_equal(np.asarray(tra), np.asarray(trb))


def test_mega_falls_back_without_spec():
    """A plain tile-aware eps (no mega_spec) runs the tile path."""
    def eps_fn(x2, t):
        a = SCH.alpha_bar[t]
        a = jnp.repeat(a, x2.shape[0] // a.shape[0])[:, None] if a.ndim \
            else a
        return x2 * jnp.sqrt(1 - a) / (1 - a + a * 0.25)
    eps_fn.tile_aware = True
    xT = jax.random.normal(jax.random.PRNGKey(0), (4, 7, 23))
    plan = SamplerPlan.build(SCH, tau=5)
    a = np.asarray(plan.run(eps_fn, xT, backend="tile_resident"))
    b = np.asarray(plan.run(eps_fn, xT, backend="mega"))
    np.testing.assert_array_equal(a, b)


def test_eligibility_rule():
    """The (spec, state) half of the eligibility rule, including VMEM."""
    _, _, eps, xT = _eps_and_x()
    ok, why = megastep.eligible(eps.mega_spec, xT)
    assert ok, why
    ok, why = megastep.eligible(None, xT)
    assert not ok and "mega_spec" in why
    ok, why = megastep.eligible(eps.mega_spec, xT[:, :32])   # wrong shape
    assert not ok and "geometry" in why
    ok, why = megastep.eligible(eps.mega_spec, xT, budget=1024)
    assert not ok and "VMEM" in why
    ok, why = megastep.eligible(eps.mega_spec, xT, interpret=False)
    assert not ok and why == megastep.TPU_REFUSAL
    assert eps.mega_spec.vmem_bytes() > eps.mega_spec.weight_bytes() > 0


def test_k_fuse_rejected_on_other_backends():
    _, _, eps, xT = _eps_and_x()
    plan = SamplerPlan.build(SCH, tau=3)
    with pytest.raises(ValueError):
        plan.run(eps, xT, backend="tile_resident", k_fuse=4)


# ----------------------------------------------------------- ref oracle
def test_megastep_ref_oracle_tiles():
    _, _, eps, xT = _eps_and_x()
    plan = SamplerPlan.build(SCH, tau=4)
    tab = plan.steps()
    coefs = np.stack([tab["c_x0"], tab["c_dir"], tab["c_noise"],
                      tab["sqrt_a_t"], tab["sqrt_1m_a_t"]],
                     axis=1).astype(np.float32)
    x2, n = tile_ops.to_tile_layout(xT)
    k_out = megastep.megastep_tiles(x2, eps.mega_spec,
                                    jnp.asarray(coefs), jnp.asarray(tab["t"]))
    r_out = mega_ref.megastep_ref(x2, eps.mega_spec, coefs, tab["t"])
    np.testing.assert_allclose(np.asarray(k_out), np.asarray(r_out),
                               atol=2e-5, rtol=2e-5)


def test_megastep_ref_oracle_rows():
    _, _, eps, xT = _eps_and_x()
    plan = SamplerPlan.build(SCH, tau=4)
    tab = plan.steps()
    x2, _ = tile_ops.to_slot_tile_layout(xT)
    B = xT.shape[0]
    rps = x2.shape[0] // B
    row = np.array([tab["c_x0"][0], tab["c_dir"][0], tab["c_noise"][0],
                    tab["sqrt_a_t"][0], tab["sqrt_1m_a_t"][0]], np.float32)
    row_coefs = tile_ops.expand_slot_coefs(jnp.tile(row[None], (B, 1)), rps)
    ts = jnp.full((B,), int(tab["t"][0]), jnp.int32)
    k_out = megastep.megastep_rows(x2, eps.mega_spec, row_coefs, ts)
    r_out = mega_ref.megastep_rows_ref(x2, eps.mega_spec, row_coefs, ts)
    np.testing.assert_allclose(np.asarray(k_out), np.asarray(r_out),
                               atol=2e-5, rtol=2e-5)


def test_flash_attn_impl_matches_exact():
    """The inlined flash_attention online-softmax trunk is fp32-tight
    against the exact-softmax trunk (and runs end to end)."""
    cfg, params = _tiny_dlm(n_heads=4, n_kv_heads=2)
    xT = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.latent_dim))
    eps = dlm.make_tile_eps_fn(params, cfg, 2, 64)
    plan = SamplerPlan.build(SCH, tau=5)
    a = np.asarray(plan.run(eps, xT, backend="mega", k_fuse=2))
    eps_flash = dlm.make_tile_eps_fn(params, cfg, 2, 64)
    eps_flash.mega_spec = dataclasses.replace(eps.mega_spec,
                                              attn_impl="flash")
    b = np.asarray(plan.run(eps_flash, xT, backend="mega", k_fuse=2))
    scale = np.abs(a).max()
    np.testing.assert_allclose(a / scale, b / scale, atol=1e-4)
    assert not np.array_equal(a, b)   # streaming normalization differs


def test_streaming_attention_body_ragged_tail():
    """The inlined flash body streams a partial last KV block instead of
    asserting: S=192 with block_k=128 (a mega-eligible seq length for
    latent_dim=32) must match plain softmax attention."""
    from repro.kernels.flash_attention.kernel import \
        streaming_attention_body
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    S, D = 192, 32
    q = jax.random.normal(kq, (S, D))
    k = jax.random.normal(kk, (S, D))
    v = jax.random.normal(kv, (S, D))
    scale = 1.0 / (D ** 0.5)
    out = streaming_attention_body(q, k, v, scale=scale, block_k=128)
    ref = jax.nn.softmax((q * scale) @ k.T, axis=-1) @ v
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_mega_spec_attn_impl_validation():
    _, _, eps, _ = _eps_and_x()
    with pytest.raises(ValueError):
        dataclasses.replace(eps.mega_spec, attn_impl="nope")


# ----------------------------------------------------- scheduler flavor
def test_mega_rows_equals_unfused_tick():
    """One fused tick == eps_fn + sampler_step_rows, bitwise."""
    _, _, eps, xT = _eps_and_x()
    plan = SamplerPlan.build(SCH, tau=4)
    tab = plan.steps()
    x2, _ = tile_ops.to_slot_tile_layout(xT)
    B = xT.shape[0]
    rps = x2.shape[0] // B
    row = np.array([tab["c_x0"][0], tab["c_dir"][0], tab["c_noise"][0],
                    tab["sqrt_a_t"][0], tab["sqrt_1m_a_t"][0]], np.float32)
    row_coefs = tile_ops.expand_slot_coefs(jnp.tile(row[None], (B, 1)), rps)
    ts = jnp.full((B,), int(tab["t"][0]), jnp.int32)
    fused = megastep.megastep_rows(x2, eps.mega_spec, row_coefs, ts)
    unfused = tile_ops.sampler_step_rows(x2, eps(x2, ts), row_coefs, None)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


def test_engine_mega_tick_bit_identical_and_one_trace():
    """The scheduler auto-detects the mega tick and serves mixed-S slots
    bit-identically to the unfused engine, in ONE compiled tick."""
    cfg, params = _tiny_dlm()
    slots, seq = 2, 64
    shape = (seq, cfg.latent_dim)
    eps = dlm.make_tile_eps_fn(params, cfg, slots, seq)
    reqs = lambda: [SampleRequest(request_id=i, S=s, seed=40 + i)
                    for i, s in enumerate([3, 5, 4])]
    e_mega = ContinuousBatchingEngine(SCH, eps, shape, slots=slots)
    assert e_mega.use_mega and e_mega.stats()["mega_tick"]
    r_mega = {r.request_id: r for r in e_mega.serve(reqs())}
    assert e_mega._traces == 1
    e_ref = ContinuousBatchingEngine(SCH, eps, shape, slots=slots,
                                     use_mega=False)
    assert not e_ref.use_mega
    r_ref = {r.request_id: r for r in e_ref.serve(reqs())}
    for i in r_ref:
        np.testing.assert_array_equal(r_mega[i].x0, r_ref[i].x0)


def test_engine_use_mega_validation():
    """use_mega=True on an ineligible configuration is a loud error;
    auto mode quietly declines."""
    cfg, params = _tiny_dlm()
    slots, seq = 2, 64
    shape = (seq, cfg.latent_dim)
    eps = dlm.make_tile_eps_fn(params, cfg, slots, seq)
    with pytest.raises(ValueError):     # stochastic tick can't fuse
        ContinuousBatchingEngine(SCH, eps, shape, slots=slots,
                                 stochastic=True, use_mega=True)
    with pytest.raises(ValueError):     # geometry bound to 2 slots, not 3
        ContinuousBatchingEngine(SCH, eps, shape, slots=3, use_mega=True)
    eng = ContinuousBatchingEngine(SCH, eps, shape, slots=slots,
                                   stochastic=True)
    assert not eng.use_mega             # auto mode: quiet fallback
    def bare(x2, t):
        return x2
    bare.slot_tile_aware = True
    eng2 = ContinuousBatchingEngine(SCH, bare, shape, slots=slots)
    assert not eng2.use_mega


def test_compiled_engine_never_fuses():
    """Compiled for the chip (interpret=False) the TPU compiler refuses the
    megakernel: auto mode declines it and use_mega=True is a loud error."""
    cfg, params = _tiny_dlm()
    slots, seq = 2, 64
    shape = (seq, cfg.latent_dim)
    eps = dlm.make_tile_eps_fn(params, cfg, slots, seq)
    eng = ContinuousBatchingEngine(SCH, eps, shape, slots=slots,
                                   interpret=False)
    assert not eng.use_mega and eng.tick_variant == "rows"
    with pytest.raises(ValueError, match="TPU compiler refuses"):
        ContinuousBatchingEngine(SCH, eps, shape, slots=slots,
                                 interpret=False, use_mega=True)


# ----------------------------------------------- metadata + small fixes
def test_make_tile_eps_fn_mega_metadata():
    cfg, params = _tiny_dlm()
    eps = dlm.make_tile_eps_fn(params, cfg, 2, 64)
    assert eps.mega_spec is not None
    assert eps.mega_vmem_bytes == eps.mega_spec.vmem_bytes()
    assert eps.mega_spec.fits()
    # embedding/rounding tables never enter the sampler loop
    assert set(eps.mega_spec.params) == {"w_in", "time_w1", "time_w2",
                                         "layers", "out_norm", "w_out"}


def test_non_dense_family_gets_no_mega_spec():
    arch = ArchConfig(name="ssm-test", family="ssm", n_layers=1,
                      d_model=64, n_heads=2, n_kv_heads=2, d_ff=128,
                      vocab=50, ssm_state=16)
    cfg = dlm.DiffusionLMConfig(arch=arch, time_dim=32, latent_dim=32)
    params = dlm.init_params(jax.random.PRNGKey(0), cfg)
    eps = dlm.make_tile_eps_fn(params, cfg, 2, 64)
    assert getattr(eps, "mega_spec", None) is None
    assert eps.tile_aware   # still tile-aware, just not fuse-capable


def test_generate_misaligned_falls_back_to_adapter():
    """ISSUE 4 small-fix satellite: a misaligned seq_len*latent_dim config
    must take generate()'s adapter fallback (make_tile_eps_fn raises) and
    produce the same tokens as the natural-shape path."""
    cfg, params = _tiny_dlm()
    seq = 63                                    # 63*32 % 2048 != 0
    with pytest.raises(ValueError):
        dlm.make_tile_eps_fn(params, cfg, 2, seq)
    rng = jax.random.PRNGKey(5)
    scfg = SamplerConfig(S=3)
    toks_tile = dlm.generate(params, cfg, SCH, rng, batch=2, seq_len=seq,
                             sampler=scfg, tile_resident=True)
    toks_nat = dlm.generate(params, cfg, SCH, rng, batch=2, seq_len=seq,
                            sampler=scfg, tile_resident=False)
    assert toks_tile.shape == (2, seq)
    np.testing.assert_array_equal(np.asarray(toks_tile),
                                  np.asarray(toks_nat))


def test_generate_aligned_uses_mega_and_matches_plain():
    """Aligned configs route through the mega backend transparently."""
    cfg, params = _tiny_dlm()
    rng = jax.random.PRNGKey(6)
    scfg = SamplerConfig(S=3)
    toks_tile = dlm.generate(params, cfg, SCH, rng, batch=2, seq_len=64,
                             sampler=scfg, tile_resident=True)
    toks_nat = dlm.generate(params, cfg, SCH, rng, batch=2, seq_len=64,
                            sampler=scfg, tile_resident=False)
    np.testing.assert_array_equal(np.asarray(toks_tile),
                                  np.asarray(toks_nat))
