"""Profiling hooks: jax.profiler annotations + modeled-HBM attribution.

Two bridges between the repo's MODELED perf accounting (BENCH_*.json
counts state-sized array traffic analytically) and a REAL device profile:

* :func:`annotate` — a trace-annotation context manager. Engines built
  with ``Observability(profile=True)`` wrap every tick in
  ``annotate("repro/tick/<variant>")`` (variant = mega | rows |
  multistep), so a ``jax.profiler.trace(...)`` capture groups device time
  under the same names the benchmarks report. Engines skip it entirely
  when profiling is off.
* :func:`modeled_hbm_table` — the per-tick modeled-HBM attribution for a
  live engine: which arrays the tick variant moves through HBM and how
  many bytes each, from the engine's actual geometry. Cross-check a
  captured profile's memory-bandwidth numbers against this table to
  validate (or falsify) the BENCH modeled-HBM claims.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from jax import tree_util as _tree_util
from jax.profiler import TraceAnnotation


def annotate(name: str):
    """Context manager marking a host-side region in profiler traces."""
    return TraceAnnotation(name)


def _itemsize(dtype) -> int:
    # np.dtype resolves numpy names AND ml_dtypes extension types
    # (bfloat16) without pulling jax.numpy into this host-only module
    # (scripts/lint_serving.py: only obs/probes.py may touch JAX ops)
    return int(np.dtype(dtype).itemsize)


def _pytree_bytes(tree) -> int:
    return int(sum(np.prod(x.shape) * _itemsize(x.dtype)
                   for x in _tree_util.tree_leaves(tree)
                   if hasattr(x, "shape")))


def modeled_hbm_table(engine) -> List[Dict]:
    """Per-tick modeled-HBM rows for a ContinuousBatchingEngine.

    Returns ``[{"component", "bytes", "note"}, ..., {"component":
    "total", ...}]``; ``bytes`` is None for traffic the model cannot see
    (an opaque eps trunk's weight streaming) — the total sums the known
    rows and says so in its note.
    """
    R = engine.slots * engine._rps
    C = engine._tile_c
    item = _itemsize(engine.dtype)
    state = R * C * item
    B = engine.slots
    variant = engine.tick_variant
    rows: List[Dict] = [
        {"component": "state_read", "bytes": state,
         "note": f"(R={R}, C={C}) slot tile in, {engine.dtype} "
                 f"({'donated' if engine.donate else 'copied'})"},
        {"component": "state_write", "bytes": state,
         "note": "updated slot tile out"},
    ]
    n_coef = 6 + (1 if engine.stochastic else 0)
    coef = B * 4 * n_coef + (B * 4 * engine.max_order
                             if engine.max_order > 1 else 0)
    rows.append({"component": "coef_rows", "bytes": coef,
                 "note": f"per-slot step coefficients ({B} slots)"})
    if variant == "mega":
        spec = getattr(engine.eps_fn, "mega_spec", None)
        w = _pytree_bytes(spec.params) if spec is not None else None
        rows.append({"component": "trunk_weights", "bytes": w,
                     "note": "eps trunk streamed HBM->VMEM once per "
                             "launch (VMEM-resident inside)"})
        rows.append({"component": "eps_roundtrip", "bytes": 0,
                     "note": "fused in-kernel: eps never touches HBM"})
    else:
        rows.append({"component": "eps_roundtrip", "bytes": 2 * R * C * 4,
                     "note": "fp32 eps written by the trunk, read by the "
                             "step kernel"})
        rows.append({"component": "trunk_weights", "bytes": None,
                     "note": "opaque eps_fn: weight traffic not modeled "
                             "(see BENCH_sampler.json rationale)"})
    if engine.max_order > 1:
        hbytes = (engine.max_order - 1) * R * C * 4
        rows.append({"component": "eps_history", "bytes": 2 * hbytes,
                     "note": f"(max_order-1={engine.max_order - 1}, R, C) "
                             "fp32 AB history read + write"})
    if engine.preview:
        rows.append({"component": "x0_preview", "bytes": R * C * item,
                     "note": "predicted-x0 second output"})
    spec = getattr(engine, "probe_spec", None)
    if spec is not None:
        from repro.obs.schema import PROBE_COLUMNS
        rows.append({"component": "probe_frame",
                     "bytes": B * len(PROBE_COLUMNS) * 4,
                     "note": f"({B}, {len(PROBE_COLUMNS)}) fp32 per-slot "
                             "probe reductions out (device->host once "
                             "per tick)"})
        if getattr(engine, "_probe_prev", None) is not None:
            rows.append({"component": "probe_prev_eps",
                         "bytes": 2 * R * C * 4,
                         "note": "fp32 previous-eps carry for the defect "
                                 "proxy, read + write (order-1 engines "
                                 "only; multistep reuses the AB history "
                                 "row already counted above)"})
    known = sum(r["bytes"] for r in rows if r["bytes"] is not None)
    unknown = sum(1 for r in rows if r["bytes"] is None)
    rows.append({"component": "total", "bytes": known,
                 "note": ("sum of modeled rows"
                          + (f" ({unknown} unmodeled row)" if unknown
                             else ""))})
    return rows


def format_hbm_table(rows: List[Dict]) -> str:
    """The attribution table as aligned text (CLI / docs output)."""
    w = max(len(r["component"]) for r in rows)
    out = []
    for r in rows:
        b = "?" if r["bytes"] is None else f"{r['bytes']:,}"
        out.append(f"{r['component']:<{w}}  {b:>14}  {r['note']}")
    return "\n".join(out)
