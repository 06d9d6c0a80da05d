"""Mesh-parallel eps trunks for sharded slot pools.

One slot pool's eps model runs across a ``("data", "model")`` mesh
(launch/mesh.make_host_mesh / make_fleet_mesh): tile-state rows and the
batch split over the DATA axes, weight matrices split by the name-based
rules in ``sharding/rules.py`` over the MODEL axis (wq column-sharded,
wo row-sharded, MoE expert weights expert-sharded). Two wiring styles:

``shard_map`` (:func:`make_sharded_eps`) — explicit SPMD: the trunk body
  sees LOCAL weight shards and a LOCAL row block, contracts over the
  model axis with one ``psum``. The in/out specs are derived from the
  SAME rule-resolved ``NamedSharding``s used to place the weights, so
  placement and program agree by construction. On a 1-device mesh the
  psum is an identity and the trunk is BIT-IDENTICAL to the unsharded
  apply — the fleet's cross-backend equivalence anchor (tested).

GSPMD (:func:`sharded_eps_from_apply`) — automatic: any existing apply
  function, weights placed by the rules, batch constrained to the data
  axes; the partitioner inserts the collectives. Use for trunks whose
  body you don't control (U-Net, diffusion-LM).

CPU simulation recipe (no TPU needed, used by CI):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m pytest tests/test_fleet.py

Everything here is functions over explicit params — importing the module
never touches jax device state (the launch/mesh.py convention).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.schedules import NoiseSchedule
from repro.sharding import batch_spec, shard_params


# --------------------------------------------------------- demo eps trunk
# The fleet bench/test trunk: the same weight-heavy shrinkage-plus-
# residual eps as benchmarks/scheduler_throughput.make_eps, but with its
# weights as an explicit pytree whose leaf names hit the sharding rules
# (wq -> column-sharded, wo -> row-sharded, time_w -> replicated), so one
# trunk definition serves the unsharded engine, the shard_map pool, and
# the GSPMD pool.

def make_trunk_params(schedule: NoiseSchedule, dim: int, hidden: int,
                      seed: int = 0):
    """Weight-heavy demo trunk params. ``alpha_bar`` rides along so the
    apply is a pure function of (params, x, t)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "trunk": {
            "wq": jax.random.normal(k1, (dim, hidden))
            * (1.0 / np.sqrt(dim)),
            "wo": jax.random.normal(k2, (hidden, dim))
            * (1.0 / np.sqrt(hidden)),
            "time_w": jnp.ones((1,), jnp.float32),
        },
        "alpha_bar": jnp.asarray(schedule.alpha_bar, jnp.float32),
    }


def trunk_apply(params, x, t, *, model_axis: Optional[str] = None):
    """eps_theta(x, t) for the demo trunk.

    ``model_axis`` names the mesh axis the hidden dim is sharded over —
    inside ``shard_map`` the weights are LOCAL shards and the wo
    contraction finishes with a psum over that axis; ``None`` is the
    plain single-device apply. A psum over an axis of size 1 is an
    identity, so the 1-device shard_map trunk is bit-identical to the
    ``model_axis=None`` apply.
    """
    w = params["trunk"]
    a = params["alpha_bar"][t].reshape((-1,) + (1,) * (x.ndim - 1))
    base = x * jnp.sqrt(1 - a) / (1 - a + a * 0.25)
    h = jnp.tanh(x @ w["wq"])
    r = h @ w["wo"]
    if model_axis is not None:
        r = jax.lax.psum(r, model_axis)
    return base + 0.05 * jnp.sqrt(1 - a) * w["time_w"] * r


def make_unsharded_eps(params) -> Callable:
    """The single-device reference eps over the demo trunk."""
    def eps_fn(x, t):
        return trunk_apply(params, x, t)
    return eps_fn


def make_sharded_eps(mesh: Mesh, params) -> Callable:
    """The demo trunk under ``shard_map`` on ``mesh`` (explicit SPMD).

    Weights are placed by ``sharding.rules.shard_params`` (wq
    column-sharded, wo row-sharded over "model"); x/t/out split over the
    data axes. The returned eps_fn closes over the PLACED params and is
    safe to call inside the engine's jitted tick — the shard_map region
    nests in the tick program, so the whole tick still traces once.
    """
    shardings = shard_params(params, mesh)
    placed = jax.device_put(params, shardings)
    pspecs = jax.tree.map(lambda s: s.spec, shardings)
    data = tuple(a for a in ("pod", "data") if a in mesh.shape)

    def local_apply(p, x, t):
        return trunk_apply(p, x, t, model_axis="model")

    mapped = jax.shard_map(local_apply, mesh=mesh,
                           in_specs=(pspecs, P(data, None), P(data)),
                           out_specs=P(data, None))

    def eps_fn(x, t):
        return mapped(placed, x, t)

    eps_fn.mesh = mesh
    eps_fn.params = placed
    return eps_fn


# ------------------------------------------------------------- GSPMD path
def sharded_eps_from_apply(mesh: Mesh, params, apply_fn: Callable
                           ) -> Callable:
    """Wrap ANY eps apply for a mesh pool via GSPMD auto-partitioning.

    ``apply_fn(params, x, t)`` is unchanged user code; the weights are
    placed by the name-based rules and the batch is constrained to the
    data axes, then XLA's partitioner propagates shardings and inserts
    the collectives. Less predictable than the shard_map path but works
    for any trunk (U-Net, diffusion-LM) without rewriting its body.
    """
    shardings = shard_params(params, mesh)
    placed = jax.device_put(params, shardings)

    def eps_fn(x, t):
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, batch_spec(mesh, x.shape[0], x.ndim)))
        return apply_fn(placed, x, t)

    eps_fn.mesh = mesh
    eps_fn.params = placed
    return eps_fn
