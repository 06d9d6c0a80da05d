"""Eps trunks are resolved by name from files.

The U-Net trunk module reads exactly what the harness read before trunks
were resolved by name (``fixtures/pre_trunks_*.py`` are frozen copies of
it), and a trunk that is no U-Net runs a whole cell as new files only: a
copy of the harness, unedited, with a trunk module, a configuration, a
traffic mix, a checks file and ``BENCHMARK.json`` entries added."""
import functools
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parent
FIX = Path(__file__).resolve().parent / "fixtures"
sys.path.insert(0, str(HERE))

import flops  # noqa: E402
import measure  # noqa: E402
import reference  # noqa: E402

SEED = 2 ** 33 + 11


def frozen(name):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_pre_trunks_{name}", FIX / f"pre_trunks_{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


OLD_REF, OLD_FLOPS = frozen("reference"), frozen("flops")


def config(name):
    path = (FIX / f"{name}.json" if name == "tiny-unet"
            else HERE / "configs" / f"{name}.json")
    return json.loads(path.read_text())


def same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == \
        b.tobytes()


@functools.lru_cache(maxsize=1)
def old_params(name):
    return OLD_REF.make_params(config(name), SEED)


# config by config, so the x0 case reuses the params case's weights
@pytest.mark.parametrize("quantity,name", [
    (q, n) for n in ("tiny-unet", "cifar10-unet", "celeba64-unet")
    for q in ("params", "x0", "unet_flops", "slot_rows",
              "step_kernel_bytes")])
def test_unet_trunk_reads_as_before(quantity, name):
    cfg = config(name)
    assert "trunk" not in cfg and reference.trunk(cfg).__file__.endswith(
        "trunks/unet.py")
    if quantity == "params":
        new = reference.make_params(cfg, SEED)
        old = old_params(name)
        assert jax.tree.structure(new) == jax.tree.structure(old)
        assert all(same_bits(a, b) for a, b in
                   zip(jax.tree.leaves(new), jax.tree.leaves(old)))
    elif quantity == "x0":
        # an order-2 and an order-1 request of different lengths, so the
        # block holds rows at different timesteps and the AB-2 combine
        reqs = [{"S": 3, "tau": "quadratic", "order": 2, "seed": 7},
                {"S": 2, "tau": "linear", "order": 1, "seed": 2 ** 30 + 1}]
        params = old_params(name)
        new = reference.sample(params, cfg, reqs, batch=2)
        old = OLD_REF.sample(params, cfg, reqs, batch=2)
        assert all(same_bits(a, b) for a, b in zip(new, old))
    elif quantity == "unet_flops":
        old = OLD_FLOPS.unet_flops(cfg)
        assert flops.unet_flops(cfg) == old
        assert reference.trunk(cfg).eps_flops(cfg) == old
        run = SimpleNamespace(config=cfg, c0=[{"slot_steps": 3}] * 2,
                              c1=[{"slot_steps": 10}, {"slot_steps": 5}])
        assert measure.slot_step_flops(run) == 9 * old
    elif quantity == "slot_rows":
        assert flops.slot_rows(cfg) == OLD_FLOPS.slot_rows(cfg)
    else:
        for slots in (4, 32):
            for stochastic in (False, True):
                for preview in (False, True):
                    kw = dict(stochastic=stochastic, preview=preview)
                    assert (flops.step_kernel_bytes(cfg, slots, **kw)
                            == OLD_FLOPS.step_kernel_bytes(cfg, slots, **kw))


# One process runs the copied harness twice: sound, then with every pool's
# tick returning its state unchanged.
DRIVER = textwrap.dedent("""
    import json, sys
    from pathlib import Path
    root = Path(sys.argv[1])
    sys.path.insert(0, str(root / "chipbench"))
    import flops, measure, reference, run
    from types import SimpleNamespace

    def state_unchanged(core):
        for p in core.fleet.pools:
            orig = p.engine._tick_fn
            def broken(x2, *rest, orig=orig):
                out = orig(x2, *rest)
                if isinstance(out, tuple) and len(out) == 2 and isinstance(
                        out[0], tuple):
                    (_, x0), hist = out
                    return (x2, x0), hist
                return (x2, out[1]) if isinstance(out, tuple) else x2
            p.engine._tick_fn = broken

    cell = run.load_cell("tiny-seq.open", root=root)
    cfg = cell.config
    fake = SimpleNamespace(config=cfg, c0=[{"slot_steps": 0}],
                           c1=[{"slot_steps": 10}])
    out = {"shape": list(reference.trunk(cfg).sample_shape(cfg)),
           "slot_rows": flops.slot_rows(cfg),
           "slot_step_flops": measure.slot_step_flops(fake),
           "eps_flops": reference.trunk(cfg).eps_flops(cfg)}
    for name, fault in (("sound", None), ("state_unchanged",
                                          state_unchanged)):
        out[name] = run.run_cell(cell, int(sys.argv[2]), 1.5, False,
                                 require_tpu=False, root=root,
                                 cache_root=root, after_build=fault)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def stand_in(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(HERE, root / "chipbench", ignore=shutil.ignore_patterns(
        "__pycache__", ".jax_cache", "tests"))
    (root / "src").symlink_to(ROOT / "src")
    before = {p.relative_to(root) for p in (root / "chipbench").rglob("*")}
    added = {
        "chipbench/trunks/tiny-seq.py": FIX / "tiny-seq.py",
        "chipbench/configs/tiny-seq.json": FIX / "tiny-seq.json",
        "chipbench/traffic/tiny-open.json": FIX / "tiny-open.json",
        "chipbench/checks/tiny-seq.open.json": FIX / "tiny-check.json",
    }
    for dst, src in added.items():
        assert Path(dst) not in before, dst      # new files, no edits
        shutil.copy(src, root / dst)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "tiny-seq", "source": "a CPU test stand-in",
        "file": "chipbench/configs/tiny-seq.json", "reduced": [],
        "why": "a trunk that is no U-Net"})
    bench["workloads"].append({
        "name": "tiny-seq.open", "config": "tiny-seq",
        "traffic": "tiny-open", "chips": 1, "why": "the stand-in, open"})
    for m in bench["end_to_end"]:
        if m["name"] in ("latency_p95_s", "setup_s") and "workloads" in m:
            m["workloads"].append("tiny-seq.open")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", DRIVER, str(root), str(SEED)],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_stand_in_trunk_lays_out_and_counts_its_own_sample(stand_in):
    assert stand_in["shape"] == [12, 16]
    assert stand_in["slot_rows"] == 8          # 192 floats: one row, 8-padded
    assert stand_in["slot_step_flops"] == 10 * stand_in["eps_flops"]


@pytest.mark.parametrize("run,correct", [("sound", True),
                                         ("state_unchanged", False)])
def test_stand_in_trunk_runs_a_cell_as_new_files_only(stand_in, run,
                                                      correct):
    out = stand_in[run]
    assert out["correct"] is correct, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"latency_p95_s", "setup_s"}
