"""The U-Net trunk of the DDIM cells (the trunk a configuration names
when it names none): its weights, its plain reference eps, its operations,
and the program's gateway over it.

The reference is the DDPM/DDIM U-Net of Ho et al. 2020 as in
ermongroup/ddim, written from the configuration files' description in
straightforward ``jax.numpy``; it imports nothing of the program. The
weight pytree uses the served program's key layout, so one set of weights
made here from ``--seed`` feeds both the program and this reference. Every
weight (the output heads included) has fan-in scale, so eps is O(1) and a
comparison exercises the whole network.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flops import unet_flops
from reference import seed_to_u32

# the configuration keys that are the program's UNetConfig fields
UNET_KEYS = ("in_channels", "base_width", "width_mults", "n_res_blocks",
             "attn_levels", "time_dim", "groups")


def sample_shape(cfg: Dict) -> Tuple[int, int, int]:
    """One request's sample: an image, (H, W, C)."""
    return (cfg["image_size"], cfg["image_size"], cfg["in_channels"])


def eps_flops(cfg: Dict) -> int:
    """Operations of one eps evaluation of one image
    (``flops.unet_flops``)."""
    return unet_flops(cfg)


def build_gateway(cfg: Dict, params, *, devices, pools: int, slots: int,
                  obs, **engine_kw):
    """The program's U-Net gateway (``launch.serve.build_unet_gateway``)
    over ``pools`` slot pools of ``slots`` on ``devices``, serving
    ``params`` under the configuration's name."""
    from repro.launch.serve import build_unet_gateway
    from repro.models.unet import UNetConfig

    ucfg = UNetConfig(**{k: (tuple(cfg[k]) if isinstance(cfg[k], list)
                             else cfg[k]) for k in UNET_KEYS})
    return build_unet_gateway(
        ucfg, cfg["image_size"], {cfg["name"]: params},
        T=cfg["schedule"]["T"], pools_per_model=pools, slots=slots,
        devices=devices, obs=obs, **engine_kw)


# ----------------------------------------------------------------- weights
def _layout(cfg: Dict) -> Dict:
    """The weight pytree as nested dicts of shapes (a leaf is a tuple)."""
    W0, tdim, cin = cfg["base_width"], cfg["time_dim"], cfg["in_channels"]

    def res(ci, co):
        p = {"gn1_s": (ci,), "gn1_b": (ci,), "conv1": (3, 3, ci, co),
             "time_w": (tdim, co), "time_b": (co,), "gn2_s": (co,),
             "gn2_b": (co,), "conv2": (3, 3, co, co)}
        if ci != co:
            p["skip"] = (1, 1, ci, co)
        return p

    def attn(c):
        return {"gn_s": (c,), "gn_b": (c,), "wq": (c, c), "wk": (c, c),
                "wv": (c, c), "wo": (c, c)}

    widths = [W0 * m for m in cfg["width_mults"]]
    out = {"time_w1": (W0, tdim), "time_b1": (tdim,),
           "time_w2": (tdim, tdim), "time_b2": (tdim,),
           "conv_in": (3, 3, cin, W0)}
    ch, skips, downs = W0, [W0], []
    for lvl, w in enumerate(widths):
        blocks = []
        for _ in range(cfg["n_res_blocks"]):
            blk = {"res": res(ch, w)}
            if lvl in cfg["attn_levels"]:
                blk["attn"] = attn(w)
            blocks.append(blk)
            ch = w
            skips.append(ch)
        entry = {"blocks": blocks}
        if lvl < len(widths) - 1:
            entry["down"] = (3, 3, ch, ch)
            skips.append(ch)
        downs.append(entry)
    out["downs"] = downs
    out["mid_res1"], out["mid_attn"], out["mid_res2"] = (
        res(ch, ch), attn(ch), res(ch, ch))
    ups = []
    for lvl, w in reversed(list(enumerate(widths))):
        blocks = []
        for _ in range(cfg["n_res_blocks"] + 1):
            blk = {"res": res(ch + skips.pop(), w)}
            if lvl in cfg["attn_levels"]:
                blk["attn"] = attn(w)
            blocks.append(blk)
            ch = w
        entry = {"blocks": blocks}
        if lvl > 0:
            entry["up"] = (3, 3, ch, ch)
        ups.append(entry)
    out["ups"] = ups
    out["gn_out_s"], out["gn_out_b"] = (ch,), (ch,)
    out["conv_out"] = (3, 3, ch, cin)
    return out


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def param_count(cfg: Dict) -> int:
    leaves = jax.tree.leaves(_layout(cfg), is_leaf=_is_shape)
    return int(sum(np.prod(s) for s in leaves))


def _init_leaf(key, name: str, shape: Tuple[int, ...]):
    """Fan-in normal for kernels; GroupNorm scales near 1; small biases."""
    if name.endswith("_s"):                       # GroupNorm scale
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if len(shape) == 1:                           # biases, GroupNorm shift
        return 0.1 * jax.random.normal(key, shape, jnp.float32)
    fan_in = int(np.prod(shape[:-1]))
    return (jax.random.truncated_normal(key, -3.0, 3.0, shape, jnp.float32)
            * np.float32(fan_in ** -0.5))


def make_params(cfg: Dict, seed: int):
    """All weights, float32, made on the default device in one jitted
    call from ``seed``."""
    layout = _layout(cfg)
    paths = jax.tree_util.tree_flatten_with_path(layout,
                                                 is_leaf=_is_shape)[0]
    treedef = jax.tree.structure(layout, is_leaf=_is_shape)

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(paths))
        leaves = [_init_leaf(k, jax.tree_util.keystr(p).split("'")[-2],
                             shape)
                  for k, (p, shape) in zip(keys, paths)]
        return jax.tree.unflatten(treedef, leaves)

    return build(jax.random.PRNGKey(seed_to_u32(seed)))


# --------------------------------------------------------- the eps network
def _conv(x, w, stride=1, pad=None):
    if pad is None:
        k = w.shape[0]
        pad = ((k // 2, k // 2), (k // 2, k // 2))
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _group_norm(x, scale, shift, groups, eps=1e-5):
    n, h, w, c = x.shape
    g = x.reshape(n, h, w, groups, c // groups)
    mean = jnp.mean(g, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(g - mean), axis=(1, 2, 4), keepdims=True)
    g = (g - mean) / jnp.sqrt(var + eps)
    return g.reshape(n, h, w, c) * scale + shift


def _timestep_embedding(t, dim):
    """[cos | sin] of t times frequencies 10000^(-i / (dim/2))."""
    half = dim // 2
    freqs = jnp.exp(-np.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    arg = t.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.cos(arg), jnp.sin(arg)], axis=1)


def _res_block(p, x, temb, groups):
    h = _conv(jax.nn.silu(_group_norm(x, p["gn1_s"], p["gn1_b"], groups)),
              p["conv1"])
    h = h + (jax.nn.silu(temb) @ p["time_w"] + p["time_b"])[:, None, None]
    h = _conv(jax.nn.silu(_group_norm(h, p["gn2_s"], p["gn2_b"], groups)),
              p["conv2"])
    if "skip" in p:
        x = _conv(x, p["skip"])
    return x + h


def _attn_block(p, x, groups):
    n, hh, ww, c = x.shape
    h = _group_norm(x, p["gn_s"], p["gn_b"], groups).reshape(n, hh * ww, c)
    q, k, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
    w = jax.nn.softmax(jnp.einsum("nqc,nkc->nqk", q, k) / float(np.sqrt(c)),
                       axis=-1)
    out = jnp.einsum("nqk,nkc->nqc", w, v) @ p["wo"]
    return x + out.reshape(n, hh, ww, c)


def eps_net(params, cfg: Dict, x, t):
    """eps(x_t, t): x (N, H, W, C), t (N,) integer timesteps in [1, T]."""
    groups = cfg["groups"]
    dt = x.dtype
    temb = _timestep_embedding(t, cfg["base_width"]).astype(dt)
    temb = jax.nn.silu(temb @ params["time_w1"] + params["time_b1"])
    temb = temb @ params["time_w2"] + params["time_b2"]
    h = _conv(x, params["conv_in"])
    skips = [h]
    for entry in params["downs"]:
        for blk in entry["blocks"]:
            h = _res_block(blk["res"], h, temb, groups)
            if "attn" in blk:
                h = _attn_block(blk["attn"], h, groups)
            skips.append(h)
        if "down" in entry:     # stride 2, padded on the bottom/right only
            h = _conv(h, entry["down"], stride=2, pad=((0, 1), (0, 1)))
            skips.append(h)
    h = _res_block(params["mid_res1"], h, temb, groups)
    h = _attn_block(params["mid_attn"], h, groups)
    h = _res_block(params["mid_res2"], h, temb, groups)
    for entry in params["ups"]:
        for blk in entry["blocks"]:
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = _res_block(blk["res"], h, temb, groups)
            if "attn" in blk:
                h = _attn_block(blk["attn"], h, groups)
        if "up" in entry:       # nearest-neighbour x2, then a 3x3 conv
            h = jnp.repeat(jnp.repeat(h, 2, axis=1), 2, axis=2)
            h = _conv(h, entry["up"])
    h = jax.nn.silu(_group_norm(h, params["gn_out_s"], params["gn_out_b"],
                                groups))
    return _conv(h, params["conv_out"])
