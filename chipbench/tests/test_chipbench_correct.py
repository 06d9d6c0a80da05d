"""The correctness check can fail: a run with the timed path broken
underneath comes out not correct, and so does the control (the reference
computed in bfloat16), at a size the CPU holds. The harness's look for a
chip is skipped; everything else of a run happens: the gateway over HTTP,
the load generator's process, the window, the reference."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parents[1]
FIX = Path(__file__).resolve().parent / "fixtures"
sys.path.insert(0, str(HERE))

import control  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402

SEED = 2 ** 33 + 5


def tiny_cell():
    return SimpleNamespace(
        name="tiny", chips=1,
        config=json.loads((FIX / "tiny-unet.json").read_text()),
        traffic=traffic.load(FIX / "tiny-open.json"),
        check=json.loads((FIX / "tiny-check.json").read_text()),
        end_to_end=[{"name": "latency_p95_s", "unit": "s"},
                    {"name": "setup_s", "unit": "s"}],
        per_layer=[])


def run_tiny(tmp_path, after_build=None):
    return run.run_cell(tiny_cell(), SEED, 1.5, False, require_tpu=False,
                        cache_root=tmp_path, after_build=after_build)


def each_tick(core, broken):
    """Replace every pool's compiled tick with ``broken(orig, *args)``."""
    for p in core.fleet.pools:
        orig = p.engine._tick_fn
        p.engine._tick_fn = lambda *a, orig=orig: broken(orig, *a)


def split(out):
    """(x2, x0 preview or None, hist or None) of a tick's return value."""
    if isinstance(out, tuple) and len(out) == 2 and isinstance(
            out[0], tuple):
        (x2, x0), hist = out
        return x2, x0, hist
    return out, None, None


def join(x2, x0, hist):
    if hist is not None:
        return (x2, x0), hist
    return x2 if x0 is None else (x2, x0)


def state_unchanged(core):
    def broken(orig, x2, *rest):
        _, x0, hist = split(orig(x2, *rest))
        return join(x2, x0, hist)
    each_tick(core, broken)


def half_the_batch_left_out(core):
    def broken(orig, x2, *rest):
        new, x0, hist = split(orig(x2, *rest))
        half = x2.shape[0] // 2
        return join(jnp.concatenate([new[:half], x2[half:]]), x0, hist)
    each_tick(core, broken)


def answer_altered(core):
    for p in core.fleet.pools:
        orig = p.engine._read_slot

        def altered(b, orig=orig):
            x = orig(b)
            return x + 0.05 * np.sqrt(np.mean(x * x)) * np.sign(x)
        p.engine._read_slot = altered


def test_sound_run_is_correct(tmp_path):
    out = run_tiny(tmp_path)
    assert out["correct"] is True, out["compared"]
    assert list(out)[-1] == "compared"
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", [state_unchanged, half_the_batch_left_out,
                                   answer_altered])
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    out = run_tiny(tmp_path, after_build=fault)
    assert out["correct"] is False, (fault.__name__, out["compared"])


def test_control_reads_above_the_limit():
    cell = tiny_cell()
    got = control.control_reading(cell, SEED, 1.5)
    lim = cell.check["limits"]
    assert got["x0_rel_rms"] > lim["x0_rel_rms"]
    assert got["x0_rel_max"] > lim["x0_rel_max"]
    # the float32 reference at the default precision, standing in for a
    # sound program on the CPU, reads far below the same limits
    reqs = control.compared_requests(cell, SEED, 1.5)
    params = reference.make_params(cell.config, SEED)
    a = reference.sample(params, cell.config, reqs, batch=4)
    b = reference.sample(params, cell.config, reqs, precision="default",
                         batch=4)
    worst = max(reference.rel_errors(x, y)[0] for x, y in zip(b, a))
    assert worst < lim["x0_rel_rms"] / 3
