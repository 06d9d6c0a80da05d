"""Pallas megakernel: eps-model trunk + Eq. 12 update fused in ONE launch.

After the tile-resident scan (PR 1), a sampler step costs ~one kernel
launch and zero layout traffic — but every step still pays a full
HBM round trip through the eps model: write x, launch the trunk graph,
read eps back, launch the step kernel. For SMALL models (diffusion-LM at
135M-smoke class and below) that launch/readback overhead dominates the
step. This kernel removes it: the whole step — time conditioning,
embedding, the dense trunk (RMSNorm + GQA attention + SwiGLU layers), the
output head, and the Eq. 12 sampler update — runs inside a single
``pl.pallas_call`` with the (R, 256) tile state, the activations, and the
weights all resident in VMEM.

Two flavors, mirroring the two sampler_step coefficient modes:

  * ``megastep_call``   — lockstep: K consecutive plan steps fused into one
    launch (``for k in range(K)`` over the prefetched coefficient rows),
    weights read once, state never leaving VMEM between the K fused steps.
    An S-step eta=0 trajectory becomes ceil(S/K) launches with ZERO state
    HBM writes inside each chunk.
  * ``megastep_rows_call`` — per-row: every tile row carries its own Eq. 12
    coefficients and every SLOT its own timestep, so the continuous-
    batching scheduler's tick advances B requests at B different
    trajectory positions in one fused launch (trunk included).

Numerical contract (the acceptance criterion): with ``attn_impl='exact'``
the in-kernel eps is the diffusion-LM ``eps_forward`` itself traced inside
the kernel — the literal op sequence the 'tile_resident' backend's eps_fn
runs outside it — and the update body is the sampler_step kernel's
``_update``/``_row_update``. eta=0 order-1 mega output is therefore
BIT-IDENTICAL to the tile-resident scan (asserted in
tests/test_megastep.py).

``attn_impl='flash'`` swaps the trunk's attention for the inlined
streaming-softmax body extracted from kernels/flash_attention
(``online_softmax_step`` driven by ``streaming_attention_body``) and its
norms for the kernels/rmsnorm body — the VMEM-lean variant for longer
sequences, where the full (S, S) score block would crowd the budget. It
is mathematically equal but not bit-identical (the streaming
normalization divides after the PV matmul), so it trades the bit contract
for an fp32-tight one.

Validated under interpret=True on CPU (this container). On a real TPU the
trunk's einsum/reshape sequence lowers through Mosaic; the reshape between
the (R, 256) tile view and the (B, S, d) model view is a pure relayout
for granule-aligned latents (the make_tile_eps_fn eligibility rule).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.kernel import streaming_attention_body
from repro.kernels.rmsnorm.kernel import rms_norm_body
from repro.kernels.sampler_step.kernel import _row_update, _update

ATTN_IMPLS = ("exact", "flash")


# ------------------------------------------------------------ eps trunks
def eps_exact(w, cfg, batch: int, seq_len: int, x2, t):
    """The diffusion-LM tile-aware eps, traced INSIDE the kernel.

    This is textually ``diffusion_lm.make_tile_eps_fn``'s body: broadcast
    t, run ``eps_forward`` on the natural view, restore the tile view. By
    calling the model's own forward the mirror can never drift from the
    function the 'tile_resident' backend evaluates outside the kernel —
    the bit-identity contract rests on this.
    """
    from repro.diffusion_lm.model import eps_forward

    shape = (batch, seq_len, cfg.latent_dim)
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32).reshape(-1), (batch,))
    e = eps_forward(w, cfg, x2.reshape(shape), t, remat=False)
    return e.reshape(x2.shape)


def eps_flash(w, cfg, batch: int, seq_len: int, x2, t):
    """The same dense trunk assembled from the inlined kernel bodies.

    RMSNorm uses ``kernels/rmsnorm.rms_norm_body``; attention streams each
    (batch, head) through ``kernels/flash_attention``'s online-softmax
    recurrence instead of materializing the (S, S) score block. Math-equal
    to ``eps_exact`` (fp32-tight, not bitwise — see module docstring).
    """
    from repro.models.common import (apply_rope, rope_freqs,
                                     sinusoidal_time_embedding, swiglu)

    a = cfg.arch
    shape = (batch, seq_len, cfg.latent_dim)
    x = x2.reshape(shape)
    t = jnp.broadcast_to(jnp.asarray(t, jnp.int32).reshape(-1), (batch,))
    temb = sinusoidal_time_embedding(t, cfg.time_dim).astype(x.dtype)
    temb = jax.nn.silu(temb @ w["time_w1"]) @ w["time_w2"]
    h = x @ w["w_in"] + temb[:, None, :]

    B, S = batch, seq_len
    H, Hkv, D = a.n_heads, a.n_kv_heads, a.hd()
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                 (B, S))
    cos, sin = rope_freqs(positions, D, a.rope_theta)
    attend = jax.vmap(functools.partial(
        streaming_attention_body, scale=1.0 / (D ** 0.5), causal=False))

    for i in range(a.n_layers):
        layer = jax.tree.map(lambda p: p[i], w["layers"])
        ap = layer["attn"]
        xn = rms_norm_body(h, layer["attn_norm"], a.norm_eps)
        q = apply_rope((xn @ ap["wq"]).reshape(B, S, H, D), cos, sin)
        k = apply_rope((xn @ ap["wk"]).reshape(B, S, Hkv, D), cos, sin)
        v = (xn @ ap["wv"]).reshape(B, S, Hkv, D)
        if Hkv != H:                       # GQA: share each kv head
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v, H // Hkv, axis=2)
        qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, D).astype(jnp.float32)
        kf = k.transpose(0, 2, 1, 3).reshape(B * H, S, D).astype(jnp.float32)
        vf = v.transpose(0, 2, 1, 3).reshape(B * H, S, D).astype(jnp.float32)
        out = attend(qf, kf, vf).astype(h.dtype)
        out = out.reshape(B, H, S, D).transpose(0, 2, 1, 3)
        h = h + out.reshape(B, S, H * D) @ ap["wo"]
        h = h + swiglu(rms_norm_body(h, layer["mlp_norm"], a.norm_eps),
                       layer["w_gate"], layer["w_up"], layer["w_down"])

    h = rms_norm_body(h, w["out_norm"], a.norm_eps)
    return (h @ w["w_out"]).reshape(x2.shape)


def _eps_body(attn_impl: str):
    return {"exact": eps_exact, "flash": eps_flash}[attn_impl]


# ------------------------------------------------------- kernel bodies
def _mega_kernel(coef_ref, t_ref, *refs, eps_jaxpr, n_leaves, n_consts, K,
                 clip):
    """K fused steps: trunk eps + Eq. 12, state held in a VMEM value.

    The K-step loop is a python ``for`` (K is static): each iteration
    evaluates the trunk at the prefetched t[k] and applies that step's
    coefficient row via the sampler_step ``_update`` body — identical
    float32 arithmetic to one tile-resident scan step, so K=1 chunks and
    K>1 chunks produce the same bits.
    """
    leaves = [r[...] for r in refs[:n_leaves]]
    consts = [r[...] for r in refs[n_leaves:n_leaves + n_consts]]
    x_ref, out_ref = refs[n_leaves + n_consts], refs[n_leaves + n_consts + 1]
    x = x_ref[...]
    for k in range(K):
        eps2 = eps_jaxpr(*consts, x, t_ref[k], *leaves)
        # SMEM holds scalars only: read the row's coefficients one by one
        coef = [coef_ref[k, j] for j in range(5)]
        x = _update(x.astype(jnp.float32), eps2.astype(jnp.float32),
                    coef, clip).astype(x.dtype)
    out_ref[...] = x


def _mega_rows_kernel(coef_ref, t_ref, *refs, eps_jaxpr, n_leaves, n_consts,
                      clip):
    """Per-row flavor: one fused scheduler tick (trunk + per-row update).

    ``t_ref`` holds each SLOT's timestep (the trunk conditions per slot);
    ``coef_ref`` the expanded per-ROW coefficient block — the exact
    arithmetic of ``sampler_step_rows``'s deterministic body.
    """
    leaves = [r[...] for r in refs[:n_leaves]]
    consts = [r[...] for r in refs[n_leaves:n_leaves + n_consts]]
    x_ref, out_ref = refs[n_leaves + n_consts], refs[n_leaves + n_consts + 1]
    x = x_ref[...]
    # SMEM holds scalars only: gather the per-slot timesteps one by one
    t = jnp.stack([t_ref[b] for b in range(t_ref.shape[0])])
    eps2 = eps_jaxpr(*consts, x, t, *leaves)
    _, out = _row_update(x.astype(jnp.float32), eps2.astype(jnp.float32),
                         coef_ref[...], clip, want_x0=False)
    out_ref[...] = out.astype(x.dtype)


# ----------------------------------------------------------- launchers
# trunk-trace cache: one jaxpr per (impl, static config, geometry, weight
# avals) signature — WITHOUT it every chunk of every trajectory would
# re-trace the whole trunk on the host, which is exactly the per-step
# overhead this kernel exists to remove. The hoisted consts (frequency
# tables, iotas) depend only on the static signature, never on weight
# VALUES, so caching them is sound. Bounded by distinct model configs per
# process.
_EPS_TRACE_CACHE = {}


def _convert_eps(attn_impl, cfg, batch, seq_len, treedef, leaves, x2,
                 t_shape):
    """Close the eps trunk over (x2, t, *leaves) with constants hoisted.

    The trunk trace materializes small helper constants (rope/time
    frequency tables, position iotas) that a Pallas kernel cannot capture;
    pre-tracing with ``jax.make_jaxpr`` surfaces every array constant in
    ``jaxpr.consts`` so they ride into VMEM as explicit inputs alongside
    the weights. Returns (fn, extra_consts) with
    ``fn(extra_consts..., x2, t, *leaves)`` replaying the identical op
    sequence (the bit-identity contract is preserved: eval_jaxpr re-emits
    the very equations the outside-the-kernel eps_fn traces to).
    """
    key = (attn_impl, cfg, batch, seq_len, treedef,
           tuple((tuple(l.shape), jnp.dtype(l.dtype).name) for l in leaves),
           tuple(x2.shape), jnp.dtype(x2.dtype).name, tuple(t_shape))
    hit = _EPS_TRACE_CACHE.get(key)
    if hit is not None:
        return hit
    body = _eps_body(attn_impl)

    def eps_call(x2_, t_, *lv):
        w = jax.tree.unflatten(treedef, list(lv))
        return body(w, cfg, batch, seq_len, x2_, t_)

    closed = jax.make_jaxpr(eps_call)(
        jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        jax.ShapeDtypeStruct(t_shape, jnp.int32), *leaves)
    n_consts = len(closed.consts)

    def replay(*consts_x_t_leaves):
        consts = consts_x_t_leaves[:n_consts]
        out = jax.core.eval_jaxpr(closed.jaxpr, consts,
                                  *consts_x_t_leaves[n_consts:])
        return out[0]

    # cache consts as HOST numpy: a jnp.asarray here would be staged into
    # whatever jit trace triggered the first conversion, and caching that
    # tracer would leak it into later traces
    _EPS_TRACE_CACHE[key] = (replay,
                             [np.asarray(c) for c in closed.consts])
    return _EPS_TRACE_CACHE[key]


def megastep_call(x2: jnp.ndarray, leaves, treedef, cfg, batch: int,
                  seq_len: int, coefs: jnp.ndarray, ts: jnp.ndarray, *,
                  clip=None, attn_impl: str = "exact",
                  interpret: bool = True) -> jnp.ndarray:
    """One fused K-step launch over the (R, C) tile view.

    Args:
      x2: (R, C) padded tile state (ops.to_tile_layout's layout; for the
        granule-aligned mega-eligible shapes the pad is empty and the view
        is a pure reshape of the natural state).
      leaves/treedef: the flattened eps-trunk weight pytree (streamed into
        VMEM once per launch, amortized over the K fused steps).
      coefs: (K, 5+) float32 — K rows of the SamplerPlan's canonical
        table, prefetched via SMEM.
      ts: (K,) int32 — the matching timesteps for the trunk.
      clip: static |x0| bound or None (compile-time specialization).
    """
    K = int(ts.shape[0])
    closed, consts = _convert_eps(attn_impl, cfg, batch, seq_len, treedef,
                                  leaves, x2, ())
    n_args = len(leaves) + len(consts)
    kernel = functools.partial(
        _mega_kernel, eps_jaxpr=closed, n_leaves=len(leaves),
        n_consts=len(consts), K=K,
        clip=None if clip is None else float(clip))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        in_specs=[smem, smem] + [vmem] * (n_args + 1),
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret,
    )(coefs.astype(jnp.float32), ts.astype(jnp.int32), *leaves, *consts,
      x2)


def megastep_rows_call(x2: jnp.ndarray, leaves, treedef, cfg, batch: int,
                       seq_len: int, row_coefs: jnp.ndarray,
                       slot_ts: jnp.ndarray, *, clip=None,
                       attn_impl: str = "exact",
                       interpret: bool = True) -> jnp.ndarray:
    """One fused scheduler tick: per-slot timesteps, per-row coefficients.

    row_coefs: (R, COEF_COLS) float32 (ops.expand_slot_coefs layout);
    slot_ts: (B,) int32, one timestep per resident slot.
    """
    closed, consts = _convert_eps(attn_impl, cfg, batch, seq_len, treedef,
                                  leaves, x2, (batch,))
    n_args = len(leaves) + len(consts)
    kernel = functools.partial(
        _mega_rows_kernel, eps_jaxpr=closed, n_leaves=len(leaves),
        n_consts=len(consts),
        clip=None if clip is None else float(clip))
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        in_specs=[vmem, smem] + [vmem] * (n_args + 1),
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret,
    )(row_coefs.astype(jnp.float32), slot_ts.astype(jnp.int32), *leaves,
      *consts, x2)
