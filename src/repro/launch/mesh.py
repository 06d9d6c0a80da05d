"""Production mesh construction (TPU v5e pods).

Defined as FUNCTIONS so importing this module never touches jax device
state — the dry-run sets XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips with a leading 'pod'
    axis for cross-pod data parallelism (DCN-attached)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Tiny mesh over the real local devices (CPU smoke / examples).

    Works on the forced-multi-device CPU path too: run under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` and the N
    simulated host devices form the ("data", "model") mesh.
    """
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(
            f"local device count {n} is not divisible by model={model}; "
            f"pick a model-axis size that divides {n} (e.g. force more "
            "host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=<k*model>)")
    return jax.make_mesh((n // model, model), ("data", "model"))


def make_fleet_mesh(n_pools: int, model: int = 1, devices=None):
    """Split the local devices into ``n_pools`` disjoint pool meshes.

    Each pool gets its own ("data", "model") mesh over a contiguous,
    non-overlapping slice of ``devices`` (default ``jax.devices()``;
    with as many devices as pools, one chip each) — the device-level view of
    a data-parallel slot-pool fleet (serving/fleet): tensor/data sharding
    INSIDE a pool, pure data parallelism ACROSS pools. Returns a list of
    ``n_pools`` meshes. CPU simulation recipe: force 8 host devices and
    ``make_fleet_mesh(2, model=2)`` yields two (2, 2) pool meshes.
    """
    import numpy as np
    from jax.sharding import Mesh

    devs = list(jax.devices() if devices is None else devices)
    n = len(devs)
    if n_pools < 1 or n % n_pools:
        raise ValueError(
            f"local device count {n} is not divisible by n_pools="
            f"{n_pools}; pick a pool count that divides {n} (e.g. force "
            "more host devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count="
            f"<k*{n_pools}>)")
    per = n // n_pools
    if model < 1 or per % model:
        raise ValueError(
            f"per-pool device count {per} (= {n} devices / {n_pools} "
            f"pools) is not divisible by model={model}")
    return [Mesh(np.asarray(devs[p * per:(p + 1) * per])
                 .reshape(per // model, model), ("data", "model"))
            for p in range(n_pools)]


# Per-chip peaks for the roofline analysis, keyed by jax's
# ``Device.device_kind``. Source: Google Cloud documentation, "TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect over 4 links (50 GB/s each).
V5E = "TPU v5 lite"
PEAKS = {
    V5E: {"flops_bf16": 197e12, "hbm_bw": 819e9, "ici_link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peak numbers for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
