"""The launch layer: the U-Net gateway that ``serve.py --gateway`` and
``chip_smoke.py`` build and drive, and the persistent compile cache."""
import asyncio
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from repro import configs
from repro.launch import compile_cache
from repro.launch.serve import UNETS, build_unet_gateway, gateway_round_trip
from repro.models import unet

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_uses_the_env_dir_and_sets_nothing(monkeypatch,
                                                         tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_checkout_dir(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert compile_cache.enable_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_serve_selects_the_two_unet_configs():
    assert UNETS == {"toy": (configs.TOY_UNET, 16),
                     "cifar10": (configs.CIFAR10_UNET, 32)}


def test_unet_gateway_round_trip_json_and_sse():
    """One pool on its own one-device mesh serves a JSON order-2 request
    and an SSE request with previews through a live aiohttp client; the
    weights and slot state live on the pool's device."""
    ucfg, size = UNETS["toy"]
    params = unet.init_params(jax.random.PRNGKey(0), ucfg)
    dev = jax.devices()[0]
    core = build_unet_gateway(ucfg, size, {"toy": params}, slots=2,
                              devices=[dev], max_order=2)
    specs = [{"S": 4, "order": 2, "seed": 1},
             {"S": 6, "seed": 2, "stream": True, "preview_every": 2}]
    outcomes, stats, bridge = asyncio.run(gateway_round_trip(core, specs))
    assert bridge.error is None
    js, sse = outcomes
    assert js["status"] == 200 and js["events"] == ["result"]
    assert sse["events"][0] == "accepted" and sse["events"][-1] == "result"
    assert sse["events"].count("result") == 1 and sse["previews"] == 2
    for o in outcomes:
        assert o["result"]["x0"].shape == (size, size, 3)
        assert np.isfinite(o["result"]["x0"]).all()
    assert stats["requests"] == 2
    (pool,) = core.fleet.pools
    eng = pool.engine
    assert eng.stats()["compiled_ticks"] == 1
    assert eng.devices() == {dev}
    assert all(isinstance(w.sharding, NamedSharding)
               and w.sharding.mesh == eng.mesh
               for w in jax.tree.leaves(eng.eps_params))


def test_unet_gateway_rejects_a_mesh_count_mismatch():
    from repro.core import make_schedule
    from repro.launch.mesh import make_fleet_mesh
    from repro.serving.gateway import GatewayCore

    ucfg, size = UNETS["toy"]
    params = unet.init_params(jax.random.PRNGKey(0), ucfg)
    meshes = make_fleet_mesh(1) * 2
    with pytest.raises(ValueError, match="2 meshes for 1 pools"):
        GatewayCore.build(make_schedule("linear", T=1000),
                          lambda p, x, t: unet.forward(p, ucfg, x, t),
                          (size, size, 3), models={"toy": params},
                          meshes=meshes, warm=False)
