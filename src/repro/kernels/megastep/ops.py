"""MegaSpec + eligibility rule + jit-friendly wrappers for the megakernel.

A ``MegaSpec`` is the metadata a tile-aware eps model attaches to itself
(``diffusion_lm.make_tile_eps_fn`` sets ``eps_fn.mega_spec``) to declare
"my trunk can run inside the fused sampler step": the trunk weight pytree,
the static model config, and the (batch, seq_len) geometry the weights
were bound for.

Eligibility (the automatic backend-selection rule, documented in
docs/sampling.md):

  * the eps model carries a ``mega_spec`` (tile-aware, dense-family trunk,
    granule-aligned latent — make_tile_eps_fn only attaches one when all
    hold), AND
  * weights + activations + state fit the VMEM budget
    (``vmem_bytes() <= MEGA_VMEM_BUDGET``, override via the
    ``budget`` argument), AND
  * the plan is deterministic, order 1, and no trajectory is requested
    (the K-step chunk has no per-step outputs).

Anything else falls back to the 'tile_resident' backend — same results,
one eps round trip per step.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import kernel as _k

# Default VMEM budget for weights + activations + 2x state. Real cores
# have ~16 MB; leave headroom for Mosaic's own buffers and double
# buffering of the coefficient rows.
MEGA_VMEM_BUDGET = 12 * 2 ** 20

DEFAULT_K_FUSE = 8

# Why the megakernel runs only in interpret mode: the TPU compiler (Mosaic,
# JAX 0.9) cannot lower the trunk inside one kernel. Compiled for a v5e,
# attn_impl='exact' fails on the layer scan over stacked weights (Pallas
# scan lowering: NotImplementedError for extensive inputs) and 'flash' on
# the float time-embedding iota ("'tpu.iota' op result #0 must be vector
# of integer or index values").
TPU_REFUSAL = ("the TPU compiler refuses the megakernel (layer scan with "
               "stacked weights; float iota in the time embedding) — it "
               "runs in interpret mode only")


@dataclasses.dataclass
class MegaSpec:
    """Everything the megakernel needs to run one eps trunk in-kernel.

    ``params`` holds ONLY the eps-path weights (w_in, time conditioning,
    stacked trunk layers, out head) — embedding/rounding tables stay in
    HBM, they never enter the sampler loop.
    """

    params: Dict[str, Any]        # eps-trunk weight pytree (jnp leaves)
    cfg: Any                      # DiffusionLMConfig (hashable, static)
    batch: int
    seq_len: int
    attn_impl: str = "exact"      # 'exact' | 'flash' (see kernel.py)

    def __post_init__(self):
        if self.attn_impl not in _k.ATTN_IMPLS:
            raise ValueError(f"attn_impl must be one of {_k.ATTN_IMPLS}, "
                             f"got {self.attn_impl!r}")

    # ------------------------------------------------------------ memory
    def weight_bytes(self) -> int:
        return int(sum(np.prod(l.shape) * l.dtype.itemsize
                       for l in jax.tree.leaves(self.params)))

    def state_bytes(self, dtype=jnp.float32) -> int:
        n = self.batch * self.seq_len * self.cfg.latent_dim
        return n * jnp.dtype(dtype).itemsize

    def activation_bytes(self) -> int:
        """Peak live activation estimate for one trunk pass, float32.

        Residual stream + a handful of layer temporaries (qkv, gate/up)
        plus the attention score block for the 'exact' impl; 'flash'
        streams KV blocks so the score term drops to one block row.
        """
        a = self.cfg.arch
        B, S = self.batch, self.seq_len
        tokens = B * S
        live = tokens * (4 * a.d_model + 2 * a.d_ff)     # h, xn, q-ish, ffn
        if self.attn_impl == "exact":
            live += B * a.n_heads * S * S                # full score block
        else:
            live += B * a.n_heads * S * 128              # one KV block
        return int(live * 4)

    def vmem_bytes(self, dtype=jnp.float32) -> int:
        """The budget number: weights + activations + state in/out."""
        return (self.weight_bytes() + self.activation_bytes()
                + 2 * self.state_bytes(dtype))

    # ------------------------------------------------------- eligibility
    def fits(self, budget: Optional[int] = None, dtype=jnp.float32) -> bool:
        return self.vmem_bytes(dtype) <= (MEGA_VMEM_BUDGET if budget is None
                                          else budget)

    def flat(self):
        leaves, treedef = jax.tree.flatten(self.params)
        return leaves, treedef


def eligible(spec: Optional[MegaSpec], x_T: jnp.ndarray,
             budget: Optional[int] = None, *,
             interpret: bool = True) -> Tuple[bool, str]:
    """(ok, reason) — can this (eps model, state) pair run the megakernel?

    Plan-level conditions (deterministic, order 1, no trajectory) are the
    backend's to check; this covers the model/geometry/VMEM half and the
    compile target (compiled for the chip it is refused: TPU_REFUSAL).
    """
    if not interpret:
        return False, TPU_REFUSAL
    if spec is None:
        return False, "eps model carries no mega_spec (not a fused-capable "\
                      "tile-aware trunk)"
    shape = (spec.batch, spec.seq_len, spec.cfg.latent_dim)
    if tuple(x_T.shape) != shape:
        return False, (f"state shape {tuple(x_T.shape)} != the spec's "
                       f"bound geometry {shape}")
    if not spec.fits(budget, x_T.dtype):
        return False, (f"weights+activations+state "
                       f"{spec.vmem_bytes(x_T.dtype)} B exceed the VMEM "
                       f"budget {MEGA_VMEM_BUDGET if budget is None else budget} B")
    return True, "ok"


# --------------------------------------------------------------- wrappers
def megastep_tiles(x2: jnp.ndarray, spec: MegaSpec, coefs: jnp.ndarray,
                  ts: jnp.ndarray, *, clip=None,
                  interpret: bool = True) -> jnp.ndarray:
    """One fused K-step chunk over the (R, C) tile view (lockstep)."""
    leaves, treedef = spec.flat()
    return _k.megastep_call(x2, leaves, treedef, spec.cfg, spec.batch,
                            spec.seq_len, coefs, ts, clip=clip,
                            attn_impl=spec.attn_impl, interpret=interpret)


def megastep_rows(x2: jnp.ndarray, spec: MegaSpec, row_coefs: jnp.ndarray,
                  slot_ts: jnp.ndarray, *, clip=None,
                  interpret: bool = True) -> jnp.ndarray:
    """One fused scheduler tick (per-slot t, per-row coefficients)."""
    leaves, treedef = spec.flat()
    return _k.megastep_rows_call(x2, leaves, treedef, spec.cfg, spec.batch,
                                 spec.seq_len, row_coefs, slot_ts,
                                 clip=clip, attn_impl=spec.attn_impl,
                                 interpret=interpret)
