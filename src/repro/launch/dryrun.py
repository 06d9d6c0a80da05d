import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, print memory/cost analysis, and dump roofline terms.

This proves the distribution config is coherent without real hardware: 512
placeholder host devices let GSPMD partition the exact production programs;
sharding mismatches, compile-time OOMs, or unsupported collectives fail here.

Usage:
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
"""
import argparse
import functools
import gc
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch import shapes as shp
from repro.launch.mesh import V5E, make_production_mesh
from repro.launch.roofline import (analyze, lm_model_flops, memory_report)
from repro.models import get_api
from repro.models.common import ArchConfig
from repro.sharding import (replicated, shard_batch, shard_cache,
                            shard_params)
from repro.training import (AdafactorConfig, AdamWConfig, TrainState,
                            init_train_state, make_decode_step,
                            make_lm_train_step, make_prefill_step)
from repro.training.optim import adafactor_init, adamw_init

ADAFACTOR_THRESHOLD = 50e9  # params; above this, train uses Adafactor


def _count(tree) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(tree)))


def active_params(param_shapes, cfg: ArchConfig) -> int:
    """Active parameter count (MoE: top_k of n_experts routed)."""
    total, expert = 0, 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(param_shapes)[0]:
        n = int(np.prod(leaf.shape))
        total += n
        pstr = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if "/moe/" in pstr and not pstr.split("/")[-1].startswith("sw"):
            if pstr.split("/")[-1] != "router":
                expert += n
    if cfg.n_experts:
        return total - expert + int(expert * cfg.top_k / cfg.n_experts)
    return total


def build_abstract(combo: shp.Combo, mesh, dtype=jnp.bfloat16):
    """Abstract (ShapeDtypeStruct) args + shardings for this combo."""
    cfg = combo.arch
    api = get_api(cfg)
    param_shapes = jax.eval_shape(
        functools.partial(api.init_params, cfg=cfg, dtype=dtype),
        jax.random.PRNGKey(0))
    p_shard = shard_params(param_shapes, mesh)
    inputs = shp.input_specs(combo, dtype)
    in_shard = shard_batch(inputs, mesh)
    return param_shapes, p_shard, inputs, in_shard


def lower_train(combo: shp.Combo, mesh):
    cfg = combo.arch
    dtype = jnp.bfloat16
    param_shapes, p_shard, inputs, in_shard = build_abstract(combo, mesh,
                                                             dtype)
    n_params = _count(param_shapes)
    if n_params > ADAFACTOR_THRESHOLD:
        opt_cfg = AdafactorConfig()
        opt_init = adafactor_init
    else:
        opt_cfg = AdamWConfig()
        opt_init = adamw_init
    opt_shapes = jax.eval_shape(opt_init, param_shapes)
    opt_shard = shard_params(opt_shapes, mesh)
    rng_shape = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state_shapes = TrainState(param_shapes, opt_shapes, rng_shape)
    state_shard = TrainState(p_shard, opt_shard, replicated(mesh))
    metrics_shard = {k: replicated(mesh) for k in
                     ("loss", "aux", "grad_norm", "lr")}
    if isinstance(opt_cfg, AdafactorConfig):
        metrics_shard = {k: replicated(mesh) for k in
                         ("loss", "aux", "grad_norm")}
    from repro.models.runtime_flags import FLAGS as _PF
    train_step = make_lm_train_step(cfg, opt_cfg,
                                    accum_steps=_PF.accum_steps)
    jitted = jax.jit(train_step,
                     in_shardings=(state_shard, in_shard),
                     out_shardings=(state_shard, metrics_shard))
    with mesh:
        lowered = jitted.lower(state_shapes, inputs)
    return lowered, n_params, active_params(param_shapes, cfg)


def lower_prefill(combo: shp.Combo, mesh):
    cfg = combo.arch
    dtype = jnp.bfloat16
    param_shapes, p_shard, inputs, in_shard = build_abstract(combo, mesh,
                                                             dtype)
    cache_shapes = shp.cache_specs(combo, dtype)
    c_shard = shard_cache(cache_shapes, mesh, combo.batch)
    step = make_prefill_step(cfg)
    jitted = jax.jit(
        step,
        in_shardings=(p_shard, in_shard["tokens"], c_shard,
                      in_shard.get("embeds")),
        out_shardings=(replicated(mesh)
                       if combo.batch % mesh.devices.size else None,
                       c_shard))
    with mesh:
        lowered = jitted.lower(param_shapes, inputs["tokens"], cache_shapes,
                               inputs.get("embeds"))
    return lowered, _count(param_shapes), active_params(param_shapes, cfg)


def lower_decode(combo: shp.Combo, mesh):
    cfg = combo.arch
    dtype = jnp.bfloat16
    param_shapes, p_shard, inputs, in_shard = build_abstract(combo, mesh,
                                                             dtype)
    cache_shapes = shp.cache_specs(combo, dtype)
    c_shard = shard_cache(cache_shapes, mesh, combo.batch)
    step = make_decode_step(cfg)
    jitted = jax.jit(step,
                     in_shardings=(p_shard, in_shard["tokens"], c_shard),
                     out_shardings=(None, c_shard))
    with mesh:
        lowered = jitted.lower(param_shapes, inputs["tokens"], cache_shapes)
    return lowered, _count(param_shapes), active_params(param_shapes, cfg)


def _opt_flags(mesh, combo):
    """§Perf lever settings for --opt mode (see models/runtime_flags.py)."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding import data_axes
    daxes = data_axes(mesh)
    batch_ax = daxes if combo.batch % int(
        np.prod([mesh.shape[a] for a in daxes])) == 0 else None
    return dict(
        seq_parallel_spec=P(batch_ax, "model", None),
        attn_chunk=2048,
        moe_group=512,
        exp_in_spec=P("model", batch_ax, None, None),
        dispatch_spec=P(batch_ax, None, "model", None),
        decode_inplace=True,
        mesh=mesh,
    )


def run_combo(arch_id: str, shape_id: str, multi_pod: bool,
              compile_: bool = True, opt: bool = False) -> dict:
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    combo = shp.resolve(configs.get(arch_id), shape_id)
    lower_fn = {"train": lower_train, "prefill": lower_prefill,
                "decode": lower_decode}[combo.kind]
    if opt:
        from repro.models.runtime_flags import perf_flags
        with perf_flags(**_opt_flags(mesh, combo)):
            lowered, n_params, n_active = lower_fn(combo, mesh)
    else:
        lowered, n_params, n_active = lower_fn(combo, mesh)
    rec = {
        "arch": arch_id, "shape": shape_id,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": combo.kind, "windowed": combo.windowed, "opt": opt,
        "n_params": n_params, "n_active": n_active,
        "lower_s": round(time.time() - t0, 1),
    }
    if not compile_:
        return rec
    t1 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t1, 1)
    rec["memory"] = memory_report(compiled)
    n_chips = int(mesh.devices.size)
    n_tokens = combo.batch * (combo.seq_len if combo.kind == "train"
                              else combo.seq_len if combo.kind == "prefill"
                              else 1)
    mflops = lm_model_flops(n_active, n_tokens,
                            "train" if combo.kind == "train" else "serve")
    hlo = compiled.as_text()
    terms = analyze(compiled, hlo, n_chips, V5E, model_flops=mflops)
    rec["roofline"] = terms.as_dict()
    rec["total_s"] = round(time.time() - t0, 1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_IDS)
    ap.add_argument("--shape", choices=shp.SHAPE_IDS)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"],
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="JSONL output path")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--opt", action="store_true",
                    help="enable the §Perf levers (seq-parallel residual, "
                         "chunked attention, MoE constraints)")
    args = ap.parse_args()

    combos = []
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[
        args.mesh]
    if args.all:
        for a in configs.ARCH_IDS:
            for s in shp.SHAPE_IDS:
                for mp in meshes:
                    combos.append((a, s, mp))
    else:
        assert args.arch and args.shape
        combos = [(args.arch, args.shape, mp) for mp in meshes]

    out_f = open(args.out, "a") if args.out else None
    failures = 0
    for a, s, mp in combos:
        tag = f"{a} x {s} x {'2x16x16' if mp else '16x16'}"
        try:
            rec = run_combo(a, s, mp, compile_=not args.no_compile,
                            opt=args.opt)
            r = rec.get("roofline", {})
            print(f"OK   {tag}: bottleneck={r.get('bottleneck')} "
                  f"compute={r.get('compute_s', 0):.3e}s "
                  f"memory={r.get('memory_s', 0):.3e}s "
                  f"coll={r.get('collective_s', 0):.3e}s "
                  f"(lower {rec['lower_s']}s compile "
                  f"{rec.get('compile_s')}s)", flush=True)
        except Exception as e:
            failures += 1
            rec = {"arch": a, "shape": s, "mesh": mp, "error": repr(e),
                   "traceback": traceback.format_exc()}
            print(f"FAIL {tag}: {e!r}", flush=True)
        if out_f:
            out_f.write(json.dumps(rec) + "\n")
            out_f.flush()
        gc.collect()
    if out_f:
        out_f.close()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
