"""Plain reference for the DDIM cells: the sampler, shared by every trunk.

The DDIM generalized update (Eq. 12 of Song et al. 2021) and the
Adams-Bashforth order-2 combine over eps, in straightforward numpy on the
host in float64, over the plain eps of the configuration's trunk
(``chipbench/trunks/<trunk>.py``, named by the configuration's ``"trunk"``
key, ``"unet"`` where it names none). It imports nothing of the program.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TRUNKS = Path(__file__).resolve().parent / "trunks"


# ----------------------------------------------------------------- trunks
@functools.lru_cache(maxsize=None)
def _load_trunk(name: str) -> ModuleType:
    path = TRUNKS / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no trunk module {path} for trunk "
                                f"{name!r}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_trunk_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def trunk(cfg: Dict) -> ModuleType:
    """The configuration's trunk module, ``trunks/<cfg["trunk"]>.py``."""
    return _load_trunk(cfg.get("trunk", "unet"))


def make_params(cfg: Dict, seed: int):
    """The trunk's weights from ``seed``, on the device, in the program's
    key layout."""
    return trunk(cfg).make_params(cfg, seed)


def seed_to_u32(seed: int) -> int:
    """Any whole number (negative or past 64 bits) to a uint32 key seed."""
    return int(seed) % (2 ** 32)


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
def _jit_eps(cfg_json: str, dtype_name: str):
    cfg = json.loads(cfg_json)
    eps_net = trunk(cfg).eps_net
    dt = jnp.dtype(dtype_name)

    def f(params, x, t):
        p = jax.tree.map(lambda a: a.astype(dt), params)
        return eps_net(p, cfg, x.astype(dt), t).astype(jnp.float32)

    return jax.jit(f)


def sample(params, cfg: Dict, requests: List[Dict], *,
           dtype: str = "float32", precision: str = "highest",
           batch: int = 8) -> List[np.ndarray]:
    """Run eta = 0 trajectories; returns each request's x_0 (float64).

    ``requests``: dicts with S, tau, order and seed. Requests run together
    in blocks of ``batch`` rows, each row at its own timestep. The update
    runs on the host in float64; with ``dtype="bfloat16"`` the network,
    its weights and the carried state x_t are bfloat16 (the control).
    """
    shape = tuple(trunk(cfg).sample_shape(cfg))
    ab = alpha_bar(cfg["schedule"])
    eps_fn = _jit_eps(json.dumps(cfg, sort_keys=True), dtype)
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
