# The harness's reference.py as it stood before eps trunks were resolved by name
# (chipbench/trunks/): test_chipbench_trunks.py holds the U-Net trunk's paths
# to it bit for bit. A frozen copy; do not edit.
"""Plain reference for the DDIM U-Net cells: weights, eps network, sampler.

Written from the configuration files' description (DDPM/DDIM U-Net of Ho
et al. 2020 as in ermongroup/ddim, the DDIM generalized update, Eq. 12 of
Song et al. 2021, and the Adams-Bashforth order-2 combine over eps), in
straightforward ``jax.numpy`` and numpy. It imports nothing of the program.

The weight pytree uses the served program's key layout, so one set of
weights made here from ``--seed`` feeds both the program and this
reference. Every weight (the output heads included) has fan-in scale, so
eps is O(1) and a comparison exercises the whole network.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


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


def seed_to_u32(seed: int) -> int:
    """Any whole number (negative or past 64 bits) to a uint32 key seed."""
    return int(seed) % (2 ** 32)


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


# ------------------------------------------------------------- the sampler
def alpha_bar(schedule: Dict) -> np.ndarray:
    """(T+1,) float64 cumulative products, alpha_bar[0] = 1."""
    betas = np.linspace(schedule["beta_start"], schedule["beta_end"],
                        schedule["T"], dtype=np.float64)
    return np.concatenate([[1.0], np.cumprod(1.0 - betas)])


def timesteps(T: int, S: int, kind: str) -> np.ndarray:
    """Increasing (S,) timesteps in [1, T] (DDIM App. D.2): floor(c i) for
    'linear', floor(c i^2) for 'quadratic', c so the last is T; collisions
    are removed and the smallest unused timesteps fill the gap."""
    i = np.arange(1, S + 1, dtype=np.float64)
    raw = np.floor(T / S * i) if kind == "linear" else np.floor(
        T / S ** 2 * i * i)
    tau = sorted(set(int(v) for v in np.clip(raw, 1, T)))
    fill = (t for t in range(1, T + 1) if t not in set(tau))
    while len(tau) < S:
        tau.append(next(fill))
    return np.asarray(sorted(tau), np.int64)


AB_WEIGHTS = {1: (1.0,), 2: (1.5, -0.5)}


def plan_rows(ab: np.ndarray, S: int, tau_kind: str, order: int):
    """Per-step (t, c_x0, c_dir, sqrt_a_t, sqrt_1m_a_t, weights) in sampling
    order for an eta = 0 trajectory; the first step of an order-2 plan is
    an Euler step."""
    tau = timesteps(len(ab) - 1, S, tau_kind)
    prev = np.concatenate([[0], tau[:-1]])
    rows = []
    for j in range(S - 1, -1, -1):
        a_t, a_s = ab[tau[j]], ab[prev[j]]
        k = S - 1 - j
        w = AB_WEIGHTS[min(k + 1, order)]
        rows.append((int(tau[j]), np.sqrt(a_s), np.sqrt(1.0 - a_s),
                     np.sqrt(a_t), np.sqrt(1.0 - a_t), w))
    return rows


def initial_noise(seed: int, shape: Sequence[int]) -> np.ndarray:
    """x_T of a request: a standard normal draw keyed by its seed."""
    return np.asarray(jax.random.normal(jax.random.PRNGKey(seed),
                                        (1,) + tuple(shape), jnp.float32))[0]


@functools.lru_cache(maxsize=None)
def _jit_eps(cfg_key, dtype_name: str):
    cfg = dict(cfg_key)
    dt = jnp.dtype(dtype_name)

    def f(params, x, t):
        p = jax.tree.map(lambda a: a.astype(dt), params)
        return eps_net(p, cfg, x.astype(dt), t).astype(jnp.float32)

    return jax.jit(f)


def _cfg_key(cfg: Dict):
    keys = ("in_channels", "base_width", "width_mults", "n_res_blocks",
            "attn_levels", "time_dim", "groups")
    return tuple((k, tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k])
                 for k in keys)


def sample(params, cfg: Dict, requests: List[Dict], *,
           dtype: str = "float32", precision: str = "highest",
           batch: int = 8) -> List[np.ndarray]:
    """Run eta = 0 trajectories; returns each request's x_0 (float64).

    ``requests``: dicts with S, tau, order and seed. Requests run together
    in blocks of ``batch`` rows, each row at its own timestep. The update
    runs on the host in float64; with ``dtype="bfloat16"`` the network,
    its weights and the carried state x_t are bfloat16 (the control).
    """
    shape = (cfg["image_size"], cfg["image_size"], cfg["in_channels"])
    ab = alpha_bar(cfg["schedule"])
    eps_fn = _jit_eps(_cfg_key(cfg), dtype)
    low = dtype != "float32"
    out: List[np.ndarray] = []
    for b0 in range(0, len(requests), batch):
        block = requests[b0:b0 + batch]
        plans = [plan_rows(ab, r["S"], r["tau"], r["order"]) for r in block]
        x = np.zeros((batch,) + shape, np.float64)
        for i, r in enumerate(block):
            x[i] = initial_noise(r["seed"], shape)
        hist = [None] * batch
        for k in range(max(len(p) for p in plans)):
            t = np.ones((batch,), np.int32)
            for i, p in enumerate(plans):
                if k < len(p):
                    t[i] = p[k][0]
            xin = x.astype(jnp.bfloat16 if low else np.float32)
            with jax.default_matmul_precision(precision):
                eps = np.asarray(eps_fn(params, jnp.asarray(xin),
                                        jnp.asarray(t)), np.float64)
            for i, p in enumerate(plans):
                if k >= len(p):
                    continue
                _, c_x0, c_dir, sa, s1a, w = p[k]
                e = eps[i] if len(w) == 1 or hist[i] is None else (
                    w[0] * eps[i] + w[1] * hist[i])
                hist[i] = eps[i]
                x0 = (x[i] - s1a * e) / sa
                x[i] = c_x0 * x0 + c_dir * e
                if low:
                    x[i] = np.asarray(x[i], np.float32).astype(
                        jnp.bfloat16).astype(np.float64)
        out.extend(x[i].copy() for i in range(len(block)))
    return out


def rel_errors(got: np.ndarray, ref: np.ndarray) -> Tuple[float, float]:
    """(RMS of the error over RMS of ref, max |error| over max |ref|)."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    d = got - ref
    return (float(np.sqrt(np.mean(d * d)) / np.sqrt(np.mean(ref * ref))),
            float(np.abs(d).max() / np.abs(ref).max()))
