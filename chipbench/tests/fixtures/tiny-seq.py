"""A stand-in trunk that is no U-Net: eps over a (seq, d) sample from one
single-head self-attention block. It serves as the program's trunk and as
the plain reference alike, so it tests the harness's trunk contract
(``chipbench/run.py``), not a model."""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from reference import seed_to_u32


def sample_shape(cfg: Dict) -> Tuple[int, int]:
    return (cfg["seq_len"], cfg["d_sample"])


def _shapes(cfg: Dict) -> Dict[str, Tuple[int, ...]]:
    d, D = cfg["d_sample"], cfg["d_model"]
    return {"w_in": (d, D), "w_t": (D, D), "wq": (D, D), "wk": (D, D),
            "wv": (D, D), "w_out": (D, d)}


def make_params(cfg: Dict, seed: int):
    """Fan-in normal weights, float32, in one jitted call from ``seed``."""
    shapes = _shapes(cfg)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(shapes))
        return {name: jax.random.normal(k, s, jnp.float32)
                * np.float32(s[0] ** -0.5)
                for k, (name, s) in zip(keys, sorted(shapes.items()))}

    return build(jax.random.PRNGKey(seed_to_u32(seed)))


def _time_embedding(t, dim):
    half = dim // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    arg = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(arg), jnp.sin(arg)], axis=1)


def eps_net(params, cfg: Dict, x, t):
    """eps(x_t, t): x (N, seq, d), t (N,) integer timesteps in [1, T]."""
    D = cfg["d_model"]
    temb = _time_embedding(t, D).astype(x.dtype) @ params["w_t"]
    h = x @ params["w_in"] + temb[:, None, :]
    q, k, v = h @ params["wq"], h @ params["wk"], h @ params["wv"]
    w = jax.nn.softmax(jnp.einsum("nqc,nkc->nqk", q, k) / float(np.sqrt(D)),
                       axis=-1)
    h = h + jnp.einsum("nqk,nkc->nqc", w, v)
    return jnp.tanh(h) @ params["w_out"]


def eps_flops(cfg: Dict) -> int:
    L, d, D = cfg["seq_len"], cfg["d_sample"], cfg["d_model"]
    return 2 * (D * D + 2 * L * d * D + 3 * L * D * D + 2 * L * L * D)


def build_gateway(cfg: Dict, params, *, devices, pools: int, slots: int,
                  obs, **engine_kw):
    """The program's GatewayCore over this eps, every pool on the default
    device."""
    from repro.core import make_schedule
    from repro.serving.gateway import GatewayCore, OverloadPolicy

    return GatewayCore.build(
        make_schedule("linear", T=cfg["schedule"]["T"]),
        lambda p, x, t: eps_net(p, cfg, x, t), sample_shape(cfg),
        models={cfg["name"]: params}, pools_per_model=pools, slots=slots,
        policy=OverloadPolicy(), obs=obs, **engine_kw)
