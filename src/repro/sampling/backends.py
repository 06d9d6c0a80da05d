"""The three executors behind ``SamplerPlan.run`` (+ the encode direction).

All backends consume the SAME compiled coefficient table and share the
same per-step arithmetic, so a deterministic plan produces bit-identical
outputs on every backend:

  run_jnp            reference lax.scan over the natural shape.  Its step
                     update is a bit-for-bit mirror of the Pallas kernel
                     body (fp32 internal math, the same algebraic two-FMA
                     form at eta=0) — the oracle AND the contract.
  run_tile_resident  the production hot path: one conversion into the
                     padded (R, C) tile layout, the whole S-step scan
                     carried there (kernels/sampler_step scalar mode).
  run_rows           the per-row slot-tick kernel driven in lockstep over
                     the slot-tile layout — the exact step program the
                     continuous-batching scheduler multiplexes, so a
                     scheduled request replays a plan.run(backend='rows')
                     trajectory bit-for-bit at eta=0.  The per-step row
                     coefficient/seed tables are PRE-STACKED outside the
                     scan (ISSUE 4 satellite): the body consumes (R, 8)
                     slices off the scanned xs instead of rebuilding the
                     expand/tile/derive chain every step, which was pure
                     dispatch overhead (0.277 ms/step vs 0.042 jnp at S=10
                     in the PR 3 BENCH_sampler.json).
  run_mega           the megakernel path (kernels/megastep): eps trunk AND
                     Eq. 12 update fused in one Pallas launch, K plan
                     steps per launch, weights/activations/state VMEM-
                     resident.  Automatic eligibility: eps_fn must carry a
                     mega_spec that fits the VMEM budget and the plan must
                     be deterministic order-1 without trajectory capture —
                     anything else falls back to run_tile_resident (same
                     results, per-step eps round trip).

Solver order k > 1 (Adams–Bashforth over the eps history, paper
Discussion §7) threads an (order-1, ...) float32 history through the scan
on every backend; the plan bakes Euler warm-up into per-step weights so
no backend branches at runtime.

Randomness policy: all PRNG use stays OUTSIDE the scan.  The jnp backend
pre-splits per-step keys; the kernel backends pre-draw per-step int32
seeds and generate noise in-kernel.  Deterministic plans trace no PRNG
ops at all (asserted in tests/test_sampler_plan.py).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solver import mix_history, warmup_weights


def kernel_update(x32, eps32, c_x0, c_dir, sqrt_a_t, sqrt_1m_a_t, clip):
    """Bit-for-bit mirror of ``kernels/sampler_step/kernel._update``.

    Keep the two in lockstep: the eta=0 cross-backend bit-identity
    guarantee rests on this function performing the exact same float32
    operation sequence as the kernel body.
    """
    if clip is not None:
        x0 = (x32 - sqrt_1m_a_t * eps32) / sqrt_a_t
        x0 = jnp.clip(x0, -clip, clip)
        eps_eff = (x32 - sqrt_a_t * x0) / sqrt_1m_a_t
        return c_x0 * x0 + c_dir * eps_eff
    # no clip: algebraic fusion down to two FMAs per element
    a = c_x0 / sqrt_a_t
    b = c_dir - a * sqrt_1m_a_t
    return a * x32 + b * eps32


def _hist0(order: int, shape):
    if order == 1:
        return None
    return jnp.zeros((order - 1,) + tuple(shape), jnp.float32)


def _xs(plan):
    """The scan's per-step inputs: the table, already in sampling order."""
    return {k: jnp.asarray(v) for k, v in plan.steps().items()}


# ------------------------------------------------------------------- jnp
def run_jnp(plan, eps_fn, x_T, rng, return_trajectory):
    stochastic = plan.stochastic
    clip = plan.x0.clip
    order = plan.order
    batch = x_T.shape[0]
    keys = jax.random.split(rng, plan.S) if stochastic else None

    def body(carry, per):
        x, hist = carry
        c, key = per
        t = jnp.full((batch,), c["t"], jnp.int32)
        eps = eps_fn(x, t)
        e32 = eps.astype(jnp.float32)
        e32, hist = mix_history(e32, hist, c["solver_w"], order)
        out = kernel_update(x.astype(jnp.float32), e32, c["c_x0"],
                            c["c_dir"], c["sqrt_a_t"], c["sqrt_1m_a_t"],
                            clip)
        if stochastic:
            out = out + c["c_noise"] * jax.random.normal(key, x.shape,
                                                         jnp.float32)
        out = out.astype(x_T.dtype)
        return (out, hist), (out if return_trajectory else None)

    (x0, _), traj = jax.lax.scan(
        body, (x_T, _hist0(order, x_T.shape)), (_xs(plan), keys))
    if return_trajectory:
        return x0, jnp.concatenate([x_T[None], traj], axis=0)
    return x0


# --------------------------------------------------------- tile_resident
def run_tile_resident(plan, eps_fn, x_T, rng, return_trajectory,
                      interpret: Optional[bool]):
    from repro.kernels.sampler_step import ops as tile_ops

    if interpret is None:
        interpret = tile_ops.default_interpret()
    stochastic = plan.stochastic
    hw_prng = tile_ops.default_hw_prng(interpret)
    order, clip = plan.order, plan.x0.clip
    batch, shape = x_T.shape[0], x_T.shape
    tile_aware = getattr(eps_fn, "tile_aware", False)
    # all randomness outside the scan: per-step int32 seeds, noise drawn
    # in-kernel; the deterministic program never touches the PRNG at all
    seeds = (jax.random.randint(rng, (plan.S,), 0, np.iinfo(np.int32).max,
                                dtype=jnp.int32)
             if stochastic else None)

    x2, n = tile_ops.to_tile_layout(x_T)             # conversion #1 (entry)

    def body(carry, per):
        x2, hist = carry
        c, seed = per
        cvec = jnp.stack([c["c_x0"], c["c_dir"], c["c_noise"],
                          c["sqrt_a_t"], c["sqrt_1m_a_t"]])
        if tile_aware:
            eps2 = eps_fn(x2, c["t"])                # native (R, C) model
        else:
            x_view = tile_ops.from_tile_layout(x2, n, shape)
            t = jnp.full((batch,), c["t"], dtype=jnp.int32)
            eps2, _ = tile_ops.to_tile_layout(eps_fn(x_view, t))
        if order > 1:
            eps2, hist = mix_history(eps2.astype(jnp.float32), hist,
                                      c["solver_w"], order)
        x2_prev = tile_ops.sampler_step_tiles(
            x2, eps2, cvec, seed, clip=clip, stochastic=stochastic,
            hw_prng=hw_prng, interpret=interpret)
        return (x2_prev, hist), (x2_prev if return_trajectory else None)

    (x2_0, _), traj2 = jax.lax.scan(
        body, (x2, _hist0(order, x2.shape)), (_xs(plan), seeds))
    x0 = tile_ops.from_tile_layout(x2_0, n, shape)   # conversion #2 (exit)
    if return_trajectory:
        traj = jax.vmap(lambda a: tile_ops.from_tile_layout(a, n, shape))(
            traj2)
        return x0, jnp.concatenate([x_T[None], traj], axis=0)
    return x0


# ------------------------------------------------------------------ rows
def run_rows(plan, eps_fn, x_T, rng, return_trajectory,
             interpret: Optional[bool]):
    from repro.kernels.sampler_step import ops as tile_ops

    if interpret is None:
        interpret = tile_ops.default_interpret()
    stochastic = plan.stochastic
    hw_prng = tile_ops.default_hw_prng(interpret)
    order, clip = plan.order, plan.x0.clip
    B, shape = x_T.shape[0], x_T.shape[1:]
    slot_aware = getattr(eps_fn, "slot_tile_aware", False)

    x2, n = tile_ops.to_slot_tile_layout(x_T)
    rps = x2.shape[0] // B

    # pre-stack the per-step row tables OUTSIDE the scan: the body then
    # gathers one (R, COEF_COLS) slice / one (R,) seed row off the scanned
    # xs instead of re-launching the tile/expand/derive op chain on every
    # step (that rebuild was pure dispatch overhead — the 'rows' lockstep
    # path cost 0.277 ms/step vs 0.042 for jnp at S=10 before this).
    xs = _xs(plan)
    cmat = jnp.stack([xs["c_x0"], xs["c_dir"], xs["c_noise"],
                      xs["sqrt_a_t"], xs["sqrt_1m_a_t"]], axis=1)  # (S, 5)
    cmat = jnp.pad(cmat, ((0, 0), (0, tile_ops.COEF_COLS - cmat.shape[1])))
    row_coefs_all = jnp.repeat(
        jnp.repeat(cmat[:, None, :], B, axis=1), rps, axis=1)   # (S, R, 8)
    if stochastic:
        # per-step PER-SLOT tick seeds (the scheduler's seed granularity),
        # drawn and row-derived outside the scan
        seeds = jax.random.randint(rng, (plan.S, B), 0,
                                   np.iinfo(np.int32).max, dtype=jnp.int32)
        row_seeds_all = jax.vmap(
            lambda s: tile_ops.derive_row_seeds(s, rps))(seeds)   # (S, R)
    else:
        row_seeds_all = None

    def body(carry, per):
        x2, hist = carry
        c, row_coefs, row_seeds = per
        t = jnp.full((B,), c["t"], dtype=jnp.int32)
        if slot_aware:
            eps2 = eps_fn(x2, t)
        else:
            x_nat = tile_ops.from_slot_tile_layout(x2, n, (B,) + tuple(shape))
            eps2, _ = tile_ops.to_slot_tile_layout(eps_fn(x_nat, t))
        if order > 1:
            eps2, hist = mix_history(eps2.astype(jnp.float32), hist,
                                      c["solver_w"], order)
        out = tile_ops.sampler_step_rows(
            x2, eps2, row_coefs, row_seeds, clip=clip,
            stochastic=stochastic, hw_prng=hw_prng, interpret=interpret)
        return (out, hist), (out if return_trajectory else None)

    (x2_0, _), traj2 = jax.lax.scan(
        body, (x2, _hist0(order, x2.shape)),
        (xs, row_coefs_all, row_seeds_all))
    batch_shape = (B,) + tuple(shape)
    x0 = tile_ops.from_slot_tile_layout(x2_0, n, batch_shape)
    if return_trajectory:
        traj = jax.vmap(
            lambda a: tile_ops.from_slot_tile_layout(a, n, batch_shape))(
            traj2)
        return x0, jnp.concatenate([x_T[None], traj], axis=0)
    return x0


# ------------------------------------------------------------------ mega
def run_mega(plan, eps_fn, x_T, rng, return_trajectory,
             interpret: Optional[bool], k_fuse: Optional[int] = None):
    """The megakernel path: trunk + update fused, K plan steps per launch.

    Eligibility is AUTOMATIC: a deterministic order-1 plan over an eps
    model carrying a VMEM-fitting ``mega_spec`` runs fused in interpret
    mode; everything else (a compiled TPU run included) falls back to the
    tile-resident scan (identical results — the same arithmetic, unfused).

    The chunk loop is UNROLLED so an S-step trajectory lowers to exactly
    ceil(S / K) pallas_call equations with the (R, C) state carried
    between them — no per-step state pad/reshape anywhere (jaxpr-asserted
    in tests/test_megastep.py). The last chunk takes the S % K remainder
    as its own smaller K (no identity-row padding, keeping every step
    bit-exact).
    """
    from repro.kernels import megastep as mega_ops
    from repro.kernels.sampler_step import ops as tile_ops

    if interpret is None:
        interpret = tile_ops.default_interpret()
    spec = getattr(eps_fn, "mega_spec", None)
    ok, _why = mega_ops.eligible(spec, x_T, interpret=interpret)
    if (not ok or plan.stochastic or plan.order > 1
            or return_trajectory):
        return run_tile_resident(plan, eps_fn, x_T, rng, return_trajectory,
                                 interpret)
    clip = plan.x0.clip
    tab = plan.steps()                       # sampling order, numpy
    S = plan.S
    K = mega_ops.DEFAULT_K_FUSE if k_fuse is None else int(k_fuse)
    K = max(1, min(K, S))
    coefs = np.stack(
        [tab["c_x0"], tab["c_dir"], tab["c_noise"], tab["sqrt_a_t"],
         tab["sqrt_1m_a_t"]], axis=1).astype(np.float32)     # (S, 5)
    ts = np.asarray(tab["t"], np.int32)                      # (S,)

    x2, n = tile_ops.to_tile_layout(x_T)     # conversion #1 (entry)
    for c0 in range(0, S, K):                # ceil(S/K) fused launches
        sl = slice(c0, min(c0 + K, S))
        x2 = mega_ops.megastep_tiles(
            x2, spec, jnp.asarray(coefs[sl]), jnp.asarray(ts[sl]),
            clip=clip, interpret=interpret)
    return tile_ops.from_tile_layout(x2, n, x_T.shape)  # conversion #2


# ---------------------------------------------------------------- encode
def encode_jnp(plan, eps_fn, x_0):
    """Forward ODE integration x_0 -> x_T on the plan's own trajectory.

    Euler (order=1) or Adams–Bashforth (the plan's order) steps in the
    x_bar/sigma coordinates of Eq. 14, written in the same canonical
    a*x + b*eps form the reverse direction uses:

      x_next = sqrt(a_to)/sqrt(a_from) * x + sqrt(a_to) * dsigma * eps_eff
    """
    ab = np.asarray(plan.schedule.alpha_bar, np.float64)
    t_traj = np.asarray(plan.steps()["t"][::-1], np.int64)  # increasing
    t_from = np.concatenate([[0], t_traj[:-1]])
    a_f, a_to = ab[t_from], ab[t_traj]
    sig = lambda a: np.sqrt((1.0 - a) / a)
    a_coef = np.sqrt(a_to / a_f)
    b_coef = np.sqrt(a_to) * (sig(a_to) - sig(a_f))
    order = plan.order
    solver_w = warmup_weights(len(t_traj), order)
    xs = {
        # the model grid starts at t=1: evaluate the first step there
        "t_eval": jnp.asarray(np.maximum(t_from, 1), jnp.int32),
        "a": jnp.asarray(a_coef, jnp.float32),
        "b": jnp.asarray(b_coef, jnp.float32),
        "solver_w": jnp.asarray(solver_w, jnp.float32),
    }
    batch = x_0.shape[0]

    def body(carry, c):
        x, hist = carry
        t = jnp.full((batch,), c["t_eval"], jnp.int32)
        e32 = eps_fn(x, t).astype(jnp.float32)
        e32, hist = mix_history(e32, hist, c["solver_w"], order)
        out = (c["a"] * x.astype(jnp.float32) + c["b"] * e32).astype(
            x_0.dtype)
        return (out, hist), None

    (x_T, _), _ = jax.lax.scan(body, (x_0, _hist0(order, x_0.shape)), xs)
    return x_T
