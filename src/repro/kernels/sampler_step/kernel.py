"""Pallas TPU kernel: the whole DDIM sampler step body, tile-resident.

Fuses everything the scan body does to the state (paper Eq. 12) into ONE
VPU pass over (TILE_R, TILE_C) VMEM tiles — one HBM read per input tensor
and one write, replacing the three separate passes of the legacy path
(jax.random.normal, clip-x0/eps-rederivation, fused update):

  x0_hat  = (x - sqrt(1-a_t) * eps) / sqrt(a_t)          predicted x0
  x0_hat  = clip(x0_hat, +-clip)                          [optional]
  eps_eff = (x - sqrt(a_t) * x0_hat) / sqrt(1-a_t)        [iff clipped]
  x_prev  = c_x0 * x0_hat + c_dir * eps_eff + c_noise * z

The stochastic variant draws z ~ N(0, I) *inside* the kernel: per-tile
seeded PRNG -> two uint32 draws -> Box-Muller. On real TPUs the hardware
PRNG is used (pltpu.prng_seed + pltpu.prng_random_bits, seeded from an
SMEM scalar plus the grid-tile id); in interpret mode (CPU CI) a
counter-based software generator with identical call structure runs
instead — ref.py replays it bit-exactly for the oracle tests.

The deterministic variant (eta == 0 and not sigma_hat) is a separate
specialization that takes no seed and contains no PRNG code at all, so
the lowered scan body is provably noise-free (asserted on the jaxpr in
tests/test_sampler_step.py).

Two coefficient paths share the fused body:

  * scalar (``sampler_step_2d``) — one (5,) coefficient vector per call;
    every tile row is at the same trajectory position (the lockstep scan).
  * per-row (``sampler_step_rows_2d``) — each tile ROW carries its own
    [c_x0, c_dir, c_noise, sqrt_a_t, sqrt_1m_a_t] and its own PRNG seed,
    so one kernel launch advances B independent requests each at its own
    position in its own trajectory (the continuous-batching scheduler's
    step-multiplexed layout). On the software-PRNG path (interpreter/CI
    and the ref oracle) per-row noise streams are a pure function of
    (row seed, lane) — independent of tile id — so a request's noise does
    not depend on which scheduler slot it landed in; the compiled-TPU
    hardware PRNG seeds per TILE (from the tile's first row seed), so
    there stochastic draws are not placement-invariant. The per-row path can
    additionally emit the predicted x0 as a second output (progressive
    preview streaming). The eta=0 specialization again contains no PRNG
    code at all.

All arithmetic runs in float32 regardless of the tile dtype (bf16 state /
fp32 coefficient policy); the store casts back to the state dtype.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VPU-aligned tile: 8 sublanes x 128 lanes, scaled up for fewer grid steps.
TILE_R = 256
TILE_C = 256
SUBLANE = 8   # minimum row granule — small states tile at (8, TILE_C)
COEF_COLS = 8  # per-row coefficient columns: 5 live + pad to the sublane granule

_GOLDEN = np.uint32(0x9E3779B9)


def _salt(s: int) -> np.uint32:
    """Per-draw salt constant shared by the kernel and the ref oracle."""
    return np.uint32((int(s) * 0x85157AF5) & 0xFFFFFFFF)


def tile_rows(R: int) -> int:
    """Row-tile height for a padded (R, TILE_C) layout.

    Full (TILE_R, TILE_C) tiles when R allows; otherwise fall back to the
    8-sublane granule so a small sampler state (a few hundred elements)
    costs one (8, 256) tile, not a 65536-element minimum.
    """
    return TILE_R if R % TILE_R == 0 else SUBLANE


def _fmix32(h):
    """murmur3 finalizer: full-avalanche 32-bit mix (uint32 in/out)."""
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    return h


def sw_random_bits(seed, tid, salt: int, shape):
    """Counter-based uint32 bits — the software PRNG path.

    Pure jnp arithmetic, so it runs identically inside the Pallas
    interpreter and in the ref.py oracle. ``seed`` and ``tid`` may be
    traced scalars; ``salt`` distinguishes independent draws per tile.
    """
    seed = jnp.asarray(seed).astype(jnp.uint32)
    tid = jnp.asarray(tid).astype(jnp.uint32)
    key = _fmix32(seed ^ (tid * np.uint32(0x632BE59B)) ^ _salt(salt))
    r = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    ctr = r * np.uint32(shape[1]) + c
    return _fmix32((ctr ^ key) * _GOLDEN + key)


def _u24_to_f32(bits):
    """Top 24 bits of a uint32 draw as an exact float32 in [0, 2**24)."""
    return jnp.right_shift(bits, np.uint32(8)).astype(jnp.int32).astype(
        jnp.float32)


def bits_to_normal(b1, b2):
    """Box-Muller: two uint32 draws -> one standard-normal float32."""
    # 24-bit mantissa-sized uniforms in (0, 1), exclusive at both ends.
    # The shifted values are below 2**24, so going through int32 is exact;
    # Mosaic has no direct uint32 -> float32 cast.
    u1 = (_u24_to_f32(b1) + 0.5) * np.float32(1.0 / 16777216.0)
    u2 = _u24_to_f32(b2) * np.float32(1.0 / 16777216.0)
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(
        np.float32(2.0 * np.pi) * u2)


def sw_random_bits_rows(row_seeds, col0, salt: int, shape):
    """Counter-based uint32 bits with one independent stream per ROW.

    ``row_seeds`` is a (rows,) vector (traced ok); ``col0`` is the global
    lane offset of this tile (so streams continue across column tiles);
    ``salt`` distinguishes independent draws. Unlike ``sw_random_bits``
    the stream depends only on (row seed, global lane) — NOT the tile id —
    so a row's noise is invariant to where its slot sits in the grid.
    """
    key = _fmix32(jnp.asarray(row_seeds).astype(jnp.uint32)
                  ^ _salt(salt))[:, None]
    c = (jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
         + jnp.asarray(col0).astype(jnp.uint32))
    return _fmix32((c ^ key) * _GOLDEN + key)


def _row_tile_noise(row_seeds, col0, shape, hw_prng: bool):
    """Per-row-seeded normal draws for one (rows, lanes) tile."""
    if hw_prng:
        # the hardware PRNG seeds once per tile (scalar state), so the
        # compiled-TPU stochastic path keys off the tile's first row seed;
        # per-row stream identity is a software-path (CI/oracle) property.
        s = jnp.asarray(row_seeds).astype(jnp.uint32)
        mixed = _fmix32(s[0] ^ (jnp.asarray(col0).astype(jnp.uint32)
                                * np.uint32(0x632BE59B)))
        pltpu.prng_seed((mixed >> np.uint32(1)).astype(jnp.int32))
        b1 = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
        b2 = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    else:
        b1 = sw_random_bits_rows(row_seeds, col0, 1, shape)
        b2 = sw_random_bits_rows(row_seeds, col0, 2, shape)
    return bits_to_normal(b1, b2)


def _tile_noise(seed, tid, shape, hw_prng: bool):
    if hw_prng:
        # mix (seed, tid) with full avalanche before seeding — a plain
        # seed + tid would collide across (step, tile) pairs whose sums
        # coincide, replaying identical noise blocks
        mixed = _fmix32(jnp.asarray(seed).astype(jnp.uint32)
                        ^ (jnp.asarray(tid).astype(jnp.uint32)
                           * np.uint32(0x632BE59B)))
        pltpu.prng_seed((mixed >> np.uint32(1)).astype(jnp.int32))
        b1 = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
        b2 = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    else:
        b1 = sw_random_bits(seed, tid, 1, shape)
        b2 = sw_random_bits(seed, tid, 2, shape)
    return bits_to_normal(b1, b2)


def _update(x, eps, coef_ref, clip):
    """The fused deterministic part: x0-predict [+clip+eps-rederive] + Eq 12."""
    c_x0, c_dir = coef_ref[0], coef_ref[1]
    sqrt_a_t, sqrt_1m_a_t = coef_ref[3], coef_ref[4]
    if clip is not None:
        x0 = (x - sqrt_1m_a_t * eps) / sqrt_a_t
        x0 = jnp.clip(x0, -clip, clip)
        eps_eff = (x - sqrt_a_t * x0) / sqrt_1m_a_t
        return c_x0 * x0 + c_dir * eps_eff
    # no clip: algebraic fusion down to two FMAs per element
    a = c_x0 / sqrt_a_t
    b = c_dir - a * sqrt_1m_a_t
    return a * x + b * eps


def _det_kernel(coef_ref, x_ref, eps_ref, out_ref, *, clip):
    """Deterministic specialization: no seed input, no PRNG code."""
    x = x_ref[...].astype(jnp.float32)
    eps = eps_ref[...].astype(jnp.float32)
    out_ref[...] = _update(x, eps, coef_ref, clip).astype(out_ref.dtype)


def _stoch_kernel(coef_ref, seed_ref, x_ref, eps_ref, out_ref, *, clip,
                  hw_prng):
    x = x_ref[...].astype(jnp.float32)
    eps = eps_ref[...].astype(jnp.float32)
    out = _update(x, eps, coef_ref, clip)
    tid = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    noise = _tile_noise(seed_ref[0], tid, x.shape, hw_prng)
    out_ref[...] = (out + coef_ref[2] * noise).astype(out_ref.dtype)


def sampler_step_2d(x: jnp.ndarray, eps: jnp.ndarray, coefs: jnp.ndarray,
                    seed=None, *, clip=None, stochastic: bool = False,
                    hw_prng: bool = False, interpret: bool = True
                    ) -> jnp.ndarray:
    """Tiled full-step update over a 2D (R, C) view.

    Args:
      x, eps: (R, C) with R % tile_rows(R) == 0 and C % TILE_C == 0 (the
        padded tile layout produced by ops.to_tile_layout — core/sampler
        owns it).
      coefs: (5,) float32 [c_x0, c_dir, c_noise, sqrt_a_t, sqrt_1m_a_t].
      seed: int32 scalar; required iff stochastic. Each grid tile derives
        its stream from seed + tile-id, so draws never repeat across tiles.
      clip: static |x0| bound, or None (compile-time specialization).
      stochastic: False selects the no-PRNG deterministic kernel.
      hw_prng: use the TPU hardware PRNG (compiled mode only; the
        interpreter has no CPU lowering for pltpu.prng_seed).
    """
    R, C = x.shape
    tr = tile_rows(R)
    grid = (R // tr, C // TILE_C)
    spec = pl.BlockSpec((tr, TILE_C), lambda i, j: (i, j))
    clip = None if clip is None else float(clip)
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    args = [coefs.astype(jnp.float32)]
    if stochastic:
        if seed is None:
            raise ValueError("stochastic sampler_step needs a seed")
        kernel = functools.partial(_stoch_kernel, clip=clip, hw_prng=hw_prng)
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(jnp.asarray(seed, jnp.int32).reshape(1))
    else:
        kernel = functools.partial(_det_kernel, clip=clip)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs + [spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((R, C), x.dtype),
        interpret=interpret,
    )(*args, x, eps)


# ----------------------------------------------------- per-row coefficients
def _row_update(x, eps, coef, clip, want_x0):
    """Fused deterministic body with per-row coefficients.

    ``coef`` is the (rows, COEF_COLS) block; column k broadcasts over the
    row's lanes. The no-clip/no-x0 branch uses the identical two-FMA
    algebraic form as the scalar kernel so the eta=0 per-row path is
    bit-exact against the lockstep scan.
    """
    c_x0, c_dir = coef[:, 0:1], coef[:, 1:2]
    sqrt_a_t, sqrt_1m_a_t = coef[:, 3:4], coef[:, 4:5]
    if clip is None and not want_x0:
        a = c_x0 / sqrt_a_t
        b = c_dir - a * sqrt_1m_a_t
        return None, a * x + b * eps
    x0 = (x - sqrt_1m_a_t * eps) / sqrt_a_t
    if clip is not None:
        x0 = jnp.clip(x0, -clip, clip)
        eps = (x - sqrt_a_t * x0) / sqrt_1m_a_t
    return x0, c_x0 * x0 + c_dir * eps


def _row_det_kernel(coef_ref, x_ref, eps_ref, *out_refs, clip, want_x0):
    """Per-row deterministic specialization: no seeds, no PRNG code."""
    x = x_ref[...].astype(jnp.float32)
    eps = eps_ref[...].astype(jnp.float32)
    x0, out = _row_update(x, eps, coef_ref[...], clip, want_x0)
    out_refs[0][...] = out.astype(out_refs[0].dtype)
    if want_x0:
        out_refs[1][...] = x0.astype(out_refs[1].dtype)


def _row_stoch_kernel(coef_ref, seed_ref, x_ref, eps_ref, *out_refs, clip,
                      want_x0, hw_prng):
    x = x_ref[...].astype(jnp.float32)
    eps = eps_ref[...].astype(jnp.float32)
    coef = coef_ref[...]
    x0, out = _row_update(x, eps, coef, clip, want_x0)
    col0 = pl.program_id(1) * x.shape[1]
    noise = _row_tile_noise(seed_ref[...][:, 0], col0, x.shape, hw_prng)
    out_refs[0][...] = (out + coef[:, 2:3] * noise).astype(out_refs[0].dtype)
    if want_x0:
        out_refs[1][...] = x0.astype(out_refs[1].dtype)


def sampler_step_rows_2d(x: jnp.ndarray, eps: jnp.ndarray,
                         row_coefs: jnp.ndarray, row_seeds=None, *,
                         clip=None, stochastic: bool = False,
                         want_x0: bool = False, hw_prng: bool = False,
                         interpret: bool = True):
    """Tiled full-step update where every ROW has its own coefficients.

    The step-multiplexed entry for the continuous-batching scheduler: rows
    belonging to different requests sit at different positions of different
    trajectories, so the Eq. 12 coefficients (and the noise stream seed)
    are gathered per row instead of broadcast per call. Tiles may span
    requests freely — there is no per-request alignment requirement beyond
    the row granule.

    Args:
      x, eps: (R, C) padded tile layout (ops.to_slot_tile_layout owns it).
      row_coefs: (R, COEF_COLS) float32; columns [c_x0, c_dir, c_noise,
        sqrt_a_t, sqrt_1m_a_t, pad...] (ops.expand_slot_coefs builds it).
      row_seeds: (R,) int32 per-row stream seeds; required iff stochastic.
      clip: static |x0| bound or None (compile-time specialization).
      stochastic: False selects the no-PRNG deterministic kernel.
      want_x0: also return the (clipped) predicted x0 — the progressive
        preview output. Note the x0-producing variant computes the update
        via the explicit x0 form (same as the clip path), which is not
        bit-identical to the two-FMA eta=0 fast path.
      hw_prng: TPU hardware PRNG (compiled mode only).

    Returns x_prev, or (x_prev, x0_hat) when want_x0.
    """
    R, C = x.shape
    tr = tile_rows(R)
    grid = (R // tr, C // TILE_C)
    spec = pl.BlockSpec((tr, TILE_C), lambda i, j: (i, j))
    cspec = pl.BlockSpec((tr, COEF_COLS), lambda i, j: (i, 0))
    clip = None if clip is None else float(clip)
    in_specs = [cspec]
    args = [row_coefs.astype(jnp.float32)]
    if stochastic:
        if row_seeds is None:
            raise ValueError("stochastic sampler_step_rows needs row_seeds")
        kernel = functools.partial(_row_stoch_kernel, clip=clip,
                                   want_x0=want_x0, hw_prng=hw_prng)
        in_specs.append(pl.BlockSpec((tr, 1), lambda i, j: (i, 0)))
        args.append(jnp.asarray(row_seeds, jnp.int32).reshape(R, 1))
    else:
        kernel = functools.partial(_row_det_kernel, clip=clip,
                                   want_x0=want_x0)
    st = jax.ShapeDtypeStruct((R, C), x.dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs + [spec, spec],
        out_specs=[spec, spec] if want_x0 else spec,
        out_shape=[st, st] if want_x0 else st,
        interpret=interpret,
    )(*args, x, eps)
    return tuple(out) if want_x0 else out
