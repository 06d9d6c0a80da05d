"""Continuous batching across diffusion timesteps (step-multiplexed slots).

DDIM's accelerated sampler makes the per-request step count S a first-class
quality/latency dial (paper Eq. 12 / §4.2), which makes STEP-HETEROGENEOUS
batching the serving primitive: a request wanting S=20 must not wait on a
batchmate running S=100, and new arrivals must not wait for a whole batch
scan to drain.

The engine keeps B resident SLOTS. Each slot holds one request at its own
position in its own trajectory — described by its own frozen
``repro.sampling.SamplerPlan``: tau spacing (uniform/quadratic/explicit-
learned), sigma schedule (scalar eta, per-step eta, explicit sigmas),
solver order, and noise stream. One engine TICK advances every resident
slot one step with a single jitted step function built on the
per-row-coefficient kernel (kernels/sampler_step.sampler_step_rows): each
tile row gathers its slot's Eq. 12 coefficients, PRNG seed, and — on
multistep-capable engines — its slot's Adams–Bashforth weight row over a
shared eps-history stack, so arbitrary trajectory AND solver mixes run in
one kernel launch. Finished slots are retired and refilled from the
admission queue MID-FLIGHT — no lockstep drain, and no recompilation:
slot contents only change array values, never the tick's trace (asserted
in tests/test_scheduler.py and tests/test_sampler_plan.py).

State residency: the slot batch lives in the padded (B * rows_per_slot, C)
slot-tile layout for a request's whole residency — x_T is written into the
slot's rows at admission, every tick runs tile-resident, and the natural
sample shape is read back once at retirement (the PR-1 layout contract
extended across requests). Multistep engines additionally carry a
(max_order-1, R, C) float32 eps-history stack; warm-up is baked into each
plan's per-step weight rows, so freshly admitted slots never read a
predecessor's stale history (its weights are zero there).

Per-request extras: absolute deadlines (expired requests are dropped at
admission, finished-late ones flagged), progressive x0-preview streaming
(the kernel's second output, delivered through ``on_preview`` callbacks
every ``preview_every`` ticks), and queue-wait/service/latency accounting
per request plus engine-level throughput/occupancy stats.
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import NoiseSchedule, StepStates
from repro.core.sampler import slot_tile_step
from repro.obs import Observability
from repro.obs.profiling import engine_span_names
from repro.obs.registry import SLACK_BUCKETS_S
from repro.obs.trace import plan_digest as _plan_digest
from repro.sampling import MAX_ORDER, SamplerPlan
# the kernel's murmur3 finalizer is plain operator arithmetic — it mixes
# host-side numpy uint32 arrays just as well, so the per-tick seed stream
# can never drift from the kernel/oracle definition
from repro.kernels.sampler_step.kernel import _GOLDEN, _fmix32

from ..errors import RejectCode, RequestError
from .queue import AdmissionQueue
from .request import SampleRequest, SampleResult, SlotCheckpoint


@dataclasses.dataclass
class _Slot:
    """Host-side bookkeeping for one resident request."""

    req: SampleRequest
    table: Dict[str, np.ndarray]   # per-step coefficient rows, sampling order
    k: int                         # next step index to run (0..S-1)
    admit_t: float
    previews: int = 0
    headroom_s: Optional[float] = None   # deadline - admit time (if any)
    # probe-quality accumulators (filled per tick by the device-probe
    # frame path when probes are on; summarized into SampleResult.quality
    # at retirement — see obs/probes.py for column semantics)
    q_frames: int = 0
    q_eps_rms: Optional[float] = None    # last tick's eps RMS
    q_finite_min: Optional[float] = None
    q_defect_max: Optional[float] = None
    q_defect_sum: float = 0.0
    q_defect_n: int = 0


class ContinuousBatchingEngine:
    """Slot-based continuous-batching server for DDIM-family sampling.

    One engine == one compiled tick program per (slots, sample_shape,
    dtype, stochastic, clip_x0, preview, max_order) configuration. Run
    several engines for a slot-count bucket ladder; within an engine,
    admission, retirement and arbitrary per-request plan mixes (tau
    spacing x sigma schedule x solver order) never retrace.

    Args:
      schedule: the T-step noise schedule the eps model was trained with.
        Per-request plans must be built on this same schedule (validated
        by digest at submit).
      eps_fn: eps_theta(x_t, t), t an int32 (B,) vector (every slot at its
        own timestep). Models may declare ``slot_tile_aware = True`` to
        consume the (R, C) slot-tile view directly and skip the per-tick
        eps repack (see diffusion_lm.make_tile_eps_fn).
      sample_shape: per-request sample shape.
      slots: number of resident requests B advanced per tick.
      stochastic: compile the in-kernel-noise tick. A deterministic engine
        (the default) serves only noise-free plans and its tick provably
        contains no PRNG ops; a stochastic engine serves ANY sigma mix
        (deterministic rows ride along with c_noise = 0).
      clip_x0: engine-level |x0| clip applied to every request (a
        compile-time kernel specialization, so it is a slot-pool property
        rather than a per-request field). Plan requests must carry the
        matching X0Policy.
      preview: compile the x0-preview tick variant (kernel emits predicted
        x0 as a second output; requests opt in via ``preview_every``).
        Preview ticks use the explicit-x0 arithmetic (the clip path), which
        costs eta=0 bit-exactness against the scan — see kernel docs.
      max_order: highest Adams–Bashforth solver order the tick supports
        (1..4). max_order=1 compiles the history-free tick; higher values
        carry a (max_order-1, R, C) eps-history stack and let slots mix
        solver orders freely (order-1 slots ride along with weight rows
        [1, 0, ...]).
      eps_params: a pytree of model weights passed INTO the jitted tick
        as an argument (eps_fn signature becomes ``eps(params, x, t)``).
        None (the default) keeps the closure-captured convention —
        weights bake into the compiled tick as constants. Passing a
        pytree makes the weights HOT-SWAPPABLE: ``install_eps_params``
        replaces them between ticks, and because a same-treedef/
        shape/dtype pytree hits the existing jit cache, a swap never
        retraces the tick (the gateway's drain -> install -> restore
        rollout is built on this; see docs/gateway.md).
      max_queue: admission-queue depth bound (None = unbounded).
      donate: donate the slot state into the tick (default: on TPU/GPU).
      interpret: Pallas interpret mode; None = compiled on TPU only.
      use_mega: run the MEGAKERNEL tick (kernels/megastep): the eps trunk
        and the per-row Eq. 12 update fuse into ONE Pallas launch per tick,
        trunk weights VMEM-resident. None (default) auto-detects: the tick
        fuses when the eps model carries a VMEM-fitting ``mega_spec`` bound
        to this engine's exact (slots, *sample_shape) geometry and the
        engine is deterministic, history-free, and preview-free; True
        raises if any of those fail, False forces the unfused tick. A
        compiled (non-interpret) tick never fuses: the TPU compiler
        refuses the megakernel (megastep.TPU_REFUSAL), so None resolves
        to False there and True raises.
      plan_bank: a ``repro.autoplan.PlanBank`` searched on this engine's
        noise schedule (digest-validated).  Requests submitted with
        ``auto_plan=True`` get their SamplerPlan chosen AT ADMISSION:
        the largest-NFE bank row that fits the request's deadline
        headroom at the measured EWMA tick latency (one tick advances a
        resident request one step); deadline-free requests are served the
        quality end of the frontier.  Rows incompatible with this engine
        (stochastic rows on a deterministic engine, order > max_order,
        clip mismatch) are never selected.
      select_margin: safety factor on the deadline fit — a bank row fits
        when NFE * tick_ewma_s <= headroom * select_margin.
      tick_ewma_alpha: smoothing factor for the per-tick latency EWMA
        that feeds the selection policy (``stats()['tick_ewma_s']``);
        0.0 freezes a seeded ``tick_ewma_s`` (virtual-clock replays).
      mesh: a ``("data", "model")`` jax.sharding.Mesh this pool's tick
        runs on (serving/fleet). The (R, 256) slot-tile state (and the
        multistep eps-history stack) shards its row dimension over the
        mesh's data axes when divisible; the eps trunk is expected to
        carry mesh-placed weights (see serving.fleet.sharded — name-based
        rules from sharding/rules.py under shard_map, or GSPMD via
        NamedSharding). Output shardings are pinned inside the tick so
        the state round-trips with a STABLE sharding — the one-trace-per-
        engine contract holds under a mesh too. None = single-device
        placement (the default, bit-identical to pre-fleet behavior).
      pool_id: fleet identity surfaced in ``stats()`` and stamped on
        every SampleResult this engine produces.
      obs: a ``repro.obs.Observability`` telemetry handle. The engine's
        throughput counters/histograms live in ``obs.registry`` (the
        ``stats()`` dict is a thin view over them, so callers see the
        same numbers either way); attaching a trace sink turns on
        per-request span events (submit/admit/first_tick/preview/retire/
        drop) through the request's TraceContext; ``profile=True`` puts
        ``jax.profiler`` spans ``repro/pool<i>/{tick, admit, states,
        launch, block, previews, retire, checkpoint}`` (``repro/engine/``
        when ``pool_id`` is None) around the tick and its phases
        (obs/profiling.py). All telemetry is host-side by contract
        — no JAX op is ever added to the tick program, so the
        one-compiled-tick and bit-identity guarantees are unaffected
        (tests/test_obs.py). None builds a private, sink-less handle:
        metrics only, near-zero cost.
      probes: the opt-in DEVICE-side probe tier (obs/probes.py): None
        (default) compiles nothing extra; True / a frozen ProbeSpec
        compiles ONE additional tick variant with per-slot numerics
        reductions fused in (eps RMS, x0 range stats, finite fraction,
        the one-eval step-doubling defect proxy), landing as a (slots, 6)
        float32 frame per tick. The plain tick program is untouched, so
        probes-off stays bit-identical to a probe-less engine, and
        ``set_probes`` switches between the two compiled programs without
        retracing (<= 2 traces total). Unavailable with use_mega.
      flight: an optional ``obs.flight.FlightRecorder`` — the engine
        pushes every probe frame (+ the slot->request map) into its ring
        so the resilience layer can dump a postmortem on quarantine or a
        nonfinite terminal (docs/resilience.md).
    """

    def __init__(self, schedule: NoiseSchedule, eps_fn: Callable,
                 sample_shape: Tuple[int, ...], slots: int,
                 dtype=jnp.float32, *, stochastic: bool = False,
                 clip_x0: Optional[float] = None, preview: bool = False,
                 max_order: int = 1,
                 eps_params=None,
                 max_queue: Optional[int] = None,
                 donate: Optional[bool] = None,
                 interpret: Optional[bool] = None,
                 use_mega: Optional[bool] = None,
                 plan_bank=None, select_margin: float = 0.9,
                 tick_ewma_alpha: float = 0.2,
                 mesh=None, pool_id: Optional[int] = None,
                 obs: Optional[Observability] = None,
                 probes=None, flight=None):
        from repro.kernels.sampler_step import ops as tile_ops
        from repro.obs.probes import normalize_probes

        if not 1 <= max_order <= MAX_ORDER:
            raise ValueError(f"max_order must be in 1..{MAX_ORDER}, got "
                             f"{max_order}")
        self.schedule = schedule
        self.eps_fn = eps_fn
        self.shape = tuple(sample_shape)
        self.slots = int(slots)
        self.dtype = dtype
        self.stochastic = stochastic
        self.clip_x0 = clip_x0
        self.preview = preview
        self.max_order = int(max_order)
        if interpret is None:
            interpret = tile_ops.default_interpret()
        self.interpret = interpret
        self.hw_prng = tile_ops.default_hw_prng(interpret)
        if donate is None:  # XLA:CPU can't donate — avoid the warning spam
            donate = jax.default_backend() in ("tpu", "gpu")
        self.donate = donate

        self.plan_bank = plan_bank
        self.select_margin = float(select_margin)
        self.tick_ewma_alpha = float(tick_ewma_alpha)
        self.tick_ewma_s: Optional[float] = None
        if plan_bank is not None:
            from repro.sampling.plan import _schedule_digest
            if (_schedule_digest(plan_bank.schedule)
                    != _schedule_digest(schedule)):
                raise ValueError(
                    "plan_bank was searched on a different noise schedule "
                    "than this engine serves — re-search or load the "
                    "matching bank")

        self.mesh = mesh
        self.pool_id = pool_id
        self.eps_params = self._place_params(eps_params)
        self.use_mega = self._resolve_mega(use_mega)
        self.tick_variant = ("mega" if self.use_mega else
                             "multistep" if self.max_order > 1 else "rows")
        # device-probe tier (obs/probes.py): a STATIC spec selecting the
        # per-slot reductions fused into a SECOND compiled tick variant;
        # probes_on switches between the two already-compiled programs at
        # runtime (<= 2 traces total, never a retrace). ``flight`` is an
        # optional obs.flight.FlightRecorder fed one frame per probed tick.
        self.probe_spec = normalize_probes(probes)
        if self.probe_spec is not None and self.use_mega:
            raise ValueError(
                "probes are unavailable on the mega tick variant: the eps "
                "evaluation never leaves the fused megastep kernel, so the "
                "device probes have nothing to reduce — build the engine "
                "with use_mega=False to probe it")
        self.probes_on = self.probe_spec is not None
        self.flight = flight
        self.last_frame: Optional[Dict] = None
        # telemetry (repro.obs): registry instruments back every counter
        # stats() reports. Host-side int/numpy state only — attaching
        # telemetry can never add a JAX op to the tick program.
        self.obs = obs if obs is not None else Observability()
        reg = self.obs.registry
        self._c_ticks = reg.counter("engine_ticks_total",
                                    "engine ticks executed",
                                    variant=self.tick_variant)
        self._c_slot_steps = reg.counter(
            "engine_slot_steps_total", "active slot-steps advanced")
        self._c_completed = reg.counter(
            "engine_completed_total", "requests retired with a sample")
        self._c_dropped = reg.counter(
            "engine_dropped_total",
            "requests dropped (expiry or back-pressure)")
        self._c_previews = reg.counter(
            "engine_previews_total", "x0 previews delivered")
        self._c_bank_selected = reg.counter(
            "engine_bank_selected_total",
            "auto_plan requests served a bank row")
        self._c_compiled = reg.counter(
            "engine_compiled_ticks_total",
            "tick traces compiled (the zero-retrace contract: 1)")
        self._c_miss = reg.counter(
            "engine_deadline_miss_total",
            "requests finished or dropped past their deadline")
        self._c_installs = reg.counter(
            "engine_weight_installs_total",
            "eps_params hot-swaps installed (zero-retrace each)")
        self._c_cancelled = reg.counter(
            "engine_cancelled_total",
            "requests cancelled by the client (slot or queue freed)")
        self._c_resumed = reg.counter(
            "engine_resumed_total",
            "checkpointed trajectories resumed mid-flight")
        self._c_ck_fetches = reg.counter(
            "engine_checkpoint_fetches_total",
            "device->host fetches of the slot state by checkpoint sweeps")
        self._c_wall = reg.counter(
            "engine_tick_wall_seconds",
            "accumulated wall time inside the jitted tick")
        self._g_active = reg.gauge(
            "engine_active_slots", "resident requests after the last tick")
        self._c_frames = reg.counter(
            "engine_probe_frames_total",
            "device probe frames transferred to the host")
        self._g_defect = reg.gauge(
            "engine_probe_defect_max",
            "max per-slot step-doubling defect proxy, last probed tick")
        self._g_finite = reg.gauge(
            "engine_probe_finite_frac_min",
            "min per-slot finite fraction, last probed tick")
        self._last_defect_max: Optional[float] = None
        self._last_finite_min: Optional[float] = None
        self._g_ewma = reg.gauge(
            "engine_tick_ewma_seconds",
            "EWMA per-tick latency (compile ticks excluded)")
        self._h_tick = reg.histogram(
            "engine_tick_seconds",
            "per-tick wall latency (compile ticks excluded)")
        self._h_wait = reg.histogram(
            "engine_queue_wait_seconds", "submit -> admit queue wait")
        self._h_service = reg.histogram(
            "engine_service_seconds", "admit -> retire service time")
        self._h_latency = reg.histogram(
            "engine_request_latency_seconds",
            "submit -> retire end-to-end latency")
        self._h_slack = reg.histogram(
            "engine_deadline_slack_seconds",
            "deadline - finish at retirement (negative = missed)",
            edges=SLACK_BUCKETS_S)
        self._last_outcome: Optional[str] = None
        self._n = int(np.prod(self.shape))
        self._rps = tile_ops.slot_rows(self.shape)
        self._tile_c = tile_ops.TILE_C
        self._x2 = jnp.zeros((self.slots * self._rps, self._tile_c), dtype)
        self._state_sharding = None
        if mesh is not None:
            # the (R, 256) slot-tile state shards its ROW dim over the
            # mesh's data axes (rows belong to slots — pure data
            # parallelism); indivisible row counts replicate. The sharding
            # is pinned on the tick's outputs too (_constrain), so the
            # jit cache sees ONE stable (aval, sharding) signature and the
            # zero-retrace contract survives the mesh.
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.sharding import data_axes
            axes = data_axes(mesh)
            dsize = int(np.prod([mesh.shape[a] for a in axes]))
            rows = self.slots * self._rps
            spec = P(axes if dsize > 1 and rows % dsize == 0 else None,
                     None)
            self._state_sharding = NamedSharding(mesh, spec)
            self._x2 = jax.device_put(self._x2, self._state_sharding)
        # shared eps-history stack for the multistep tick (fp32 policy)
        self._hist2 = (jnp.zeros((self.max_order - 1,) + self._x2.shape,
                                 jnp.float32)
                       if self.max_order > 1 else None)
        if self._hist2 is not None and mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P
            self._hist_sharding = NamedSharding(
                mesh, P(None, *self._state_sharding.spec))
            self._hist2 = jax.device_put(self._hist2, self._hist_sharding)
        self._slots: List[Optional[_Slot]] = [None] * self.slots
        self._free: List[int] = list(range(self.slots))[::-1]
        self.queue = AdmissionQueue(max_queue, obs=self.obs)
        self._tables: Dict[SamplerPlan, Dict[str, np.ndarray]] = {}
        self._schedule_digest = None   # filled lazily from the first plan
        self._traces = 0
        # inactive-slot filler row: an EXACT identity update on the no-clip
        # path (a = c_x0/sqrt_a = 1, b = c_dir - a*sqrt_1m_a = 0 => x' = x),
        # so idle slots never drift; the clip path divides by sqrt_1m_a, so
        # there use 1.0 — idle slots then hold clip(x - eps), finite and
        # bounded by the clip. Idle rows are never read back either way.
        self._idle_row = dict(t=1, c_x0=1.0, c_dir=0.0, c_noise=0.0,
                              sqrt_a_t=1.0,
                              sqrt_1m_a_t=1.0 if clip_x0 is not None
                              else 0.0)
        # probe-only previous-eps buffer for the defect proxy on order-1
        # engines (multistep engines read the pre-update newest history
        # row for free; see obs/probes.py on the one-eval proxy)
        self._probe_prev = None
        if (self.probe_spec is not None and self.probe_spec.defect
                and self.max_order == 1):
            self._probe_prev = jnp.zeros(self._x2.shape, jnp.float32)
            if mesh is not None:
                self._probe_prev = jax.device_put(self._probe_prev,
                                                  self._state_sharding)
        self._tick_fn = self._make_tick()
        self._tick_probed = (self._make_tick_probed()
                             if self.probe_spec is not None else None)
        self._write_fn = self._make_write()
        self._hist_write_fn = (self._make_hist_write()
                               if self._hist2 is not None else None)
        self._xT_fn = self._make_xT()

    # ----------------------------------- registry-backed counters (views)
    # The legacy counter attributes read straight from the obs instruments
    # so existing callers (and the stats() dict) see identical numbers.
    @property
    def ticks(self) -> int:
        return int(self._c_ticks.value)

    @property
    def slot_steps(self) -> int:
        return int(self._c_slot_steps.value)

    @property
    def completed(self) -> int:
        return int(self._c_completed.value)

    @property
    def dropped(self) -> int:
        return int(self._c_dropped.value)

    @property
    def previews_sent(self) -> int:
        return int(self._c_previews.value)

    @property
    def bank_selected(self) -> int:
        return int(self._c_bank_selected.value)

    @property
    def deadline_missed(self) -> int:
        return int(self._c_miss.value)

    @property
    def weight_installs(self) -> int:
        return int(self._c_installs.value)

    @property
    def pool_id(self) -> Optional[int]:
        return self._pool_id

    @pool_id.setter
    def pool_id(self, pool_id: Optional[int]) -> None:
        # span names follow the pool's identity; built here, not per tick
        self._pool_id = pool_id
        self._span = engine_span_names(pool_id)

    @property
    def _tick_wall_s(self) -> float:
        return float(self._c_wall.value)

    # ------------------------------------------------------- jitted pieces
    def _resolve_mega(self, use_mega: Optional[bool]) -> bool:
        """Megakernel-tick eligibility (the 'mega' backend rule + the
        engine-specific half).

        The model/geometry/VMEM checks are ``megastep.eligible`` — the
        single source shared with ``plan.run(backend='mega')`` — applied
        to this engine's (slots, *sample_shape) state signature; the tick
        additionally needs to be deterministic, history-free, and
        preview-free (those are plan-level conditions on the backend
        side).
        """
        if use_mega is False:
            return False
        from repro.kernels import megastep as mega_ops

        spec = getattr(self.eps_fn, "mega_spec", None)
        if self.eps_params is not None:
            ok, why = False, ("megakernel tick bakes its trunk weights "
                              "into the VMEM spec; a hot-swappable "
                              "eps_params engine runs the unfused tick")
        elif self.stochastic or self.preview or self.max_order > 1:
            ok, why = False, ("megakernel tick is deterministic/order-1/"
                              "preview-free only")
        else:
            ok, why = mega_ops.eligible(
                spec, jax.ShapeDtypeStruct((self.slots,) + self.shape,
                                           self.dtype),
                interpret=self.interpret)
        if ok:
            return True
        if use_mega:                       # explicitly requested: loud
            raise ValueError(f"use_mega=True but {why}")
        return False

    def _place_params(self, params):
        """Put eps weights on this pool's mesh, replicated over it, so
        the tick reads them from the pool's own devices (None stays
        None; off-mesh the arrays stay where the caller put them)."""
        if params is None or self.mesh is None:
            return params
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(params, NamedSharding(self.mesh, P()))

    def devices(self) -> set:
        """Every device holding this pool's slot state or weights."""
        arrays = [self._x2] + jax.tree.leaves(self.eps_params)
        return set().union(*(a.devices() for a in arrays
                             if isinstance(a, jax.Array)))

    def _constrain(self, arr2):
        """Pin an (R, C)-shaped tick output to the slot-state sharding.

        No-op off-mesh. On a mesh this keeps the state's sharding STABLE
        across ticks (GSPMD would otherwise be free to hand back a
        replicated result, and the next tick's changed input sharding
        would re-trace).
        """
        if self._state_sharding is None or arr2 is None:
            return arr2
        return jax.lax.with_sharding_constraint(arr2, self._state_sharding)

    def _constrain_hist(self, hist2):
        if self._state_sharding is None or hist2 is None:
            return hist2
        return jax.lax.with_sharding_constraint(hist2, self._hist_sharding)

    def _bind_eps(self, params):
        """The eps callable a tick trace sees: the raw closure-weight fn,
        or — on an eps_params engine — a partial binding the (traced)
        params argument, preserving the ``slot_tile_aware`` marker the
        slot-tile step dispatches on."""
        if params is None:
            return self.eps_fn
        raw = self.eps_fn

        def bound(x, t):
            return raw(params, x, t)

        bound.slot_tile_aware = getattr(raw, "slot_tile_aware", False)
        return bound

    def install_eps_params(self, new_params) -> None:
        """Hot-swap the model weights WITHOUT retracing the tick.

        Only legal on an engine built with ``eps_params=`` (closure
        weights are baked into the compiled program). The replacement
        pytree must match the resident one in treedef and per-leaf
        shape/dtype — that is exactly the condition under which the next
        tick hits the existing jit cache entry, so the zero-retrace
        contract (``stats()['compiled_ticks']``) is preserved by
        construction. The fleet tier swaps only on a drained (STOPPED)
        pool; see SlotPool.install.
        """
        if self.eps_params is None:
            raise RuntimeError(
                "engine has no eps_params to swap: closure-captured "
                "weights are compiled into the tick — build the engine "
                "with eps_params= to make weights installable")
        old_l, old_t = jax.tree_util.tree_flatten(self.eps_params)
        new_l, new_t = jax.tree_util.tree_flatten(new_params)
        if old_t != new_t:
            raise ValueError(
                "install_eps_params: new pytree structure differs from "
                f"the resident weights ({new_t} vs {old_t})")
        for i, (o, n) in enumerate(zip(old_l, new_l)):
            if (jnp.shape(o) != jnp.shape(n)
                    or jnp.result_type(o) != jnp.result_type(n)):
                raise ValueError(
                    f"install_eps_params: leaf {i} is "
                    f"{jnp.shape(n)}/{jnp.result_type(n)}, resident is "
                    f"{jnp.shape(o)}/{jnp.result_type(o)} — a swap must "
                    "preserve shapes/dtypes to reuse the compiled tick")
        self.eps_params = self._place_params(new_params)
        self._c_installs.inc()

    def _make_tick(self):
        shape = self.shape

        if self.use_mega:
            from repro.kernels import megastep as mega_ops
            from repro.kernels.sampler_step import ops as tile_ops
            spec, rps = self.eps_fn.mega_spec, self._rps

            def tick(x2, states):
                self._traces += 1   # host side effect: fires once per trace
                self._c_compiled.inc()
                row_coefs = tile_ops.expand_slot_coefs(
                    states.coef_matrix(), rps)
                return self._constrain(mega_ops.megastep_rows(
                    x2, spec, row_coefs, states.t, clip=self.clip_x0,
                    interpret=self.interpret))

            kw = dict(donate_argnums=(0,)) if self.donate else {}
            return jax.jit(tick, **kw)

        if self.max_order == 1:
            def tick(x2, states, params=None):
                self._traces += 1   # host side effect: fires once per trace
                self._c_compiled.inc()
                out = slot_tile_step(
                    self._bind_eps(params), x2, states, shape,
                    clip_x0=self.clip_x0,
                    stochastic=self.stochastic, want_x0=self.preview,
                    hw_prng=self.hw_prng, interpret=self.interpret)
                if self.preview:
                    return (self._constrain(out[0]),
                            self._constrain(out[1]))
                return self._constrain(out)

            # weights are a tick ARGUMENT, never donated: they are reused
            # verbatim by every subsequent tick until a swap replaces them
            kw = dict(donate_argnums=(0,)) if self.donate else {}
            return jax.jit(tick, **kw)

        def tick(x2, hist2, states, params=None):
            self._traces += 1       # host side effect: fires once per trace
            self._c_compiled.inc()
            out, new_hist2 = slot_tile_step(
                self._bind_eps(params), x2, states, shape, hist2=hist2,
                clip_x0=self.clip_x0, stochastic=self.stochastic,
                want_x0=self.preview, hw_prng=self.hw_prng,
                interpret=self.interpret)
            if self.preview:
                out = (self._constrain(out[0]), self._constrain(out[1]))
            else:
                out = self._constrain(out)
            return out, self._constrain_hist(new_hist2)

        kw = dict(donate_argnums=(0, 1)) if self.donate else {}
        return jax.jit(tick, **kw)

    def _make_tick_probed(self):
        """The SECOND compiled tick: identical step math + fused probes.

        The plain tick program above is byte-identical to a probe-less
        engine's (probes-off output is bit-identical by construction);
        this variant additionally asks the slot-tile step for the raw eps
        evaluation and folds it — with the pre/post-step state — into a
        (slots, 6) float32 probe frame on device (obs/probes.py). Order-1
        engines with the defect probe carry the previous eps evaluation
        as an explicit donated argument/output; multistep engines read it
        for free from the pre-update newest history row. Both variants
        trace exactly once, so an engine toggling probes compiles at most
        2 tick programs (tests/test_probes.py pins the count).
        """
        from repro.obs.probes import device_frame
        shape, spec = self.shape, self.probe_spec
        rps, n = self._rps, self._n

        if self.max_order == 1:
            if self._probe_prev is not None:
                def tick(x2, prev, states, params=None):
                    self._traces += 1   # host side effect: once per trace
                    self._c_compiled.inc()
                    out, eps2 = slot_tile_step(
                        self._bind_eps(params), x2, states, shape,
                        clip_x0=self.clip_x0, stochastic=self.stochastic,
                        want_x0=self.preview, want_eps=True,
                        hw_prng=self.hw_prng, interpret=self.interpret)
                    x_new = out[0] if self.preview else out
                    frame = device_frame(spec, x2, x_new, eps2, prev,
                                         states, rps=rps, n_live=n)
                    if self.preview:
                        out = (self._constrain(out[0]),
                               self._constrain(out[1]))
                    else:
                        out = self._constrain(out)
                    new_prev = self._constrain(eps2.astype(jnp.float32))
                    return out, frame, new_prev

                kw = dict(donate_argnums=(0, 1)) if self.donate else {}
                return jax.jit(tick, **kw)

            def tick(x2, states, params=None):
                self._traces += 1       # host side effect: once per trace
                self._c_compiled.inc()
                out, eps2 = slot_tile_step(
                    self._bind_eps(params), x2, states, shape,
                    clip_x0=self.clip_x0, stochastic=self.stochastic,
                    want_x0=self.preview, want_eps=True,
                    hw_prng=self.hw_prng, interpret=self.interpret)
                x_new = out[0] if self.preview else out
                frame = device_frame(spec, x2, x_new, eps2, None, states,
                                     rps=rps, n_live=n)
                if self.preview:
                    out = (self._constrain(out[0]), self._constrain(out[1]))
                else:
                    out = self._constrain(out)
                return out, frame

            kw = dict(donate_argnums=(0,)) if self.donate else {}
            return jax.jit(tick, **kw)

        def tick(x2, hist2, states, params=None):
            self._traces += 1           # host side effect: once per trace
            self._c_compiled.inc()
            out, new_hist2, eps2 = slot_tile_step(
                self._bind_eps(params), x2, states, shape, hist2=hist2,
                clip_x0=self.clip_x0, stochastic=self.stochastic,
                want_x0=self.preview, want_eps=True,
                hw_prng=self.hw_prng, interpret=self.interpret)
            x_new = out[0] if self.preview else out
            # hist2 is the PRE-update stack: row 0 is the previous tick's
            # raw eval — exactly the defect proxy's reference, for free
            eps_prev = hist2[0] if spec.defect else None
            frame = device_frame(spec, x2, x_new, eps2, eps_prev, states,
                                 rps=rps, n_live=n)
            if self.preview:
                out = (self._constrain(out[0]), self._constrain(out[1]))
            else:
                out = self._constrain(out)
            return out, self._constrain_hist(new_hist2), frame

        kw = dict(donate_argnums=(0, 1)) if self.donate else {}
        return jax.jit(tick, **kw)

    def set_probes(self, on: bool) -> None:
        """Toggle which ALREADY-COMPILED tick variant runs (no retrace).

        Only meaningful on an engine built with ``probes=``: the probed
        program is compiled against the construction-frozen ProbeSpec,
        not synthesized on demand, so enabling probes on a spec-less
        engine raises instead of silently retracing.
        """
        if on and self.probe_spec is None:
            raise RuntimeError(
                "engine was built without probes= — the probed tick is a "
                "construction-time compiled variant, not a runtime add-on")
        self.probes_on = bool(on)

    def _make_write(self):
        def write(x2, xT2, row0):
            return self._constrain(
                jax.lax.dynamic_update_slice(x2, xT2, (row0, 0)))

        kw = dict(donate_argnums=(0,)) if self.donate else {}
        return jax.jit(write, **kw)

    def _make_hist_write(self):
        def write(hist2, rows3, row0):
            return self._constrain_hist(
                jax.lax.dynamic_update_slice(hist2, rows3, (0, row0, 0)))

        kw = dict(donate_argnums=(0,)) if self.donate else {}
        return jax.jit(write, **kw)

    def _make_xT(self):
        from repro.kernels.sampler_step import ops as tile_ops

        def draw(seed):
            x = jax.random.normal(jax.random.PRNGKey(seed),
                                  (1,) + self.shape, self.dtype)
            return tile_ops.to_slot_tile_layout(x)[0]

        return jax.jit(draw)

    # ------------------------------------------------------------ plumbing
    def _table_for(self, req: SampleRequest) -> Dict[str, np.ndarray]:
        plan = req.resolved_plan(self.schedule, self.clip_x0)
        if plan not in self._tables:
            self._tables[plan] = plan.steps()
        return self._tables[plan]

    def _validate_plan(self, req: SampleRequest) -> None:
        plan = req.plan
        if plan is None:
            return
        if self._schedule_digest is None:
            from repro.sampling.plan import _schedule_digest
            self._schedule_digest = _schedule_digest(self.schedule)
        if plan.schedule_digest() != self._schedule_digest:
            raise RequestError(
                RejectCode.SCHEDULE_MISMATCH,
                f"request {req.request_id}: plan built on a different "
                "noise schedule than this engine serves")
        if plan.clip_x0 != self.clip_x0:
            raise RequestError(
                RejectCode.CLIP_MISMATCH,
                f"request {req.request_id}: plan clip_x0={plan.clip_x0} != "
                f"engine clip_x0={self.clip_x0} (the clip is a compile-time "
                "slot-pool property)")

    def validate_request(self, req: SampleRequest) -> None:
        """Raise if this engine can never serve ``req`` (capability check).

        Public API (docs/gateway.md): every refusal is a typed
        :class:`repro.serving.errors.RequestError` whose ``.code`` is a
        stable :class:`RejectCode` and whose ``.status`` is the HTTP
        status a gateway maps it to. RequestError subclasses ValueError,
        so pre-gateway callers keep working.

        Shared with the fleet tier: a PoolFleet validates against one pool
        at submit (pools are capability-homogeneous) so an unservable
        request fails loudly at the front door, not at dispatch.
        """
        if req.auto_plan:
            if req.plan is not None:
                raise RequestError(
                    RejectCode.AUTO_PLAN_CONFLICT,
                    f"request {req.request_id}: auto_plan=True and an "
                    "explicit plan are mutually exclusive (the engine "
                    "fills plan in at admission)")
            if req.solver_order != 1:
                raise RequestError(
                    RejectCode.AUTO_PLAN_CONFLICT,
                    f"request {req.request_id}: auto_plan=True picks the "
                    "solver order with the plan; order must stay 1")
            if self.plan_bank is None:
                raise RequestError(
                    RejectCode.NO_PLAN_BANK,
                    f"request {req.request_id}: auto_plan=True needs an "
                    "engine built with plan_bank=")
            if self._bank_candidates() == 0:
                raise RequestError(
                    RejectCode.BANK_INCOMPATIBLE,
                    f"request {req.request_id}: the plan bank has no entry "
                    "compatible with this engine (stochastic rows need a "
                    f"stochastic engine; order <= max_order="
                    f"{self.max_order}; clip == {self.clip_x0})")
        else:
            if req.stochastic and not self.stochastic:
                raise RequestError(
                    RejectCode.STOCHASTIC_UNSUPPORTED,
                    f"request {req.request_id}: a stochastic plan (sigma > "
                    "0 somewhere) needs a stochastic=True engine "
                    "(deterministic tick has no PRNG)")
            self._validate_plan(req)
            if req.order > self.max_order:
                raise RequestError(
                    RejectCode.ORDER_UNSUPPORTED,
                    f"request {req.request_id}: solver order={req.order} "
                    f"exceeds engine max_order={self.max_order} (build the "
                    "engine with max_order >= the largest solver order it "
                    "must serve)")
            if not 1 <= req.steps <= self.schedule.T:
                raise RequestError(
                    RejectCode.BAD_STEPS,
                    f"request {req.request_id}: S={req.steps} "
                    f"outside [1, T={self.schedule.T}]")
            if req.plan is None and req.order > 1 and req.stochastic:
                raise RequestError(
                    RejectCode.BAD_REQUEST,
                    f"request {req.request_id}: multistep (order > 1) "
                    "plans are deterministic — use eta = 0 or order = 1")

    def submit(self, req: SampleRequest,
               now: Optional[float] = None) -> bool:
        """Enqueue a request; False means rejected (queue back-pressure)."""
        self.validate_request(req)
        now = time.perf_counter() if now is None else now
        self.obs.trace_submit(req, now, deadline=req.deadline)
        return self.queue.submit(req, now)

    # ------------------------------------------------- deadline-aware bank
    def _bank_candidates(self) -> int:
        """How many bank rows this engine could actually serve."""
        return len(self.plan_bank.compatible(
            deterministic=None if self.stochastic else True,
            max_order=self.max_order, clip=self.clip_x0))

    def _select_plan(self, req: SampleRequest, now: float):
        """The admission-time bank pick (the deadline-aware policy).

        headroom = deadline - now (infinite without a deadline); the
        per-step latency estimate is the EWMA tick time — a resident
        request advances exactly one step per tick, so a plan fits when
        NFE * tick_ewma_s <= headroom * select_margin.  Before the first
        measured tick the policy is conservative (smallest row) for
        deadline requests and quality-greedy for deadline-free ones.
        """
        headroom = (math.inf if req.deadline is None
                    else max(req.deadline - now, 0.0))
        return self.plan_bank.select(
            headroom, self.tick_ewma_s, margin=self.select_margin,
            deterministic=None if self.stochastic else True,
            max_order=self.max_order, clip=self.clip_x0,
            on_outcome=self._bank_outcome)

    def _bank_outcome(self, outcome: str, plan) -> None:
        """PlanBank.select telemetry hook: count WHY each row was picked
        (quality / conservative / fit / degraded / none) and WHAT it was
        (per-NFE counter) — the selection-policy feed ROADMAP item 4's
        background re-search reads."""
        self._last_outcome = outcome
        reg = self.obs.registry
        reg.counter("engine_bank_outcome_total",
                    "auto_plan selections by policy outcome",
                    outcome=outcome).inc()
        if plan is not None:
            reg.counter("engine_bank_nfe_total",
                        "auto_plan selections by chosen NFE",
                        nfe=plan.S).inc()

    @property
    def active(self) -> int:
        return self.slots - len(self._free)

    @property
    def capacity(self) -> int:
        """Dispatchable headroom: free slots not already spoken for by the
        local queue (what a fleet router may send without deep-queueing
        behind this pool)."""
        return max(len(self._free) - len(self.queue), 0)

    def pending_steps(self) -> int:
        """Remaining step budget resident + queued (the router's load
        signal). Queued ``auto_plan`` requests count their S field — an
        estimate; the real NFE is picked at admission."""
        rem = sum(s.req.steps - s.k for s in self._slots if s is not None)
        rem += sum(r.steps for r in self.queue.pending_requests())
        return rem

    def _drop(self, req: SampleRequest, now: float, missed: bool = True,
              reason: Optional[str] = None) -> SampleResult:
        """Account one never-ran request. ``reason`` set emits the span's
        terminal ``drop`` event; back-pressure drops pass None because the
        queue already closed the span with ``reject``."""
        self._c_dropped.inc()
        if missed:
            self._c_miss.inc()
        if reason is not None and req.trace is not None:
            req.trace.emit("drop", now, reason=reason)
        return SampleResult.drop(req, now, missed=missed,
                                 pool_id=self.pool_id)

    def _fill_auto_plan(self, req: SampleRequest, now: float) -> None:
        """The queue's pop-time ``select`` hook: fill an auto_plan
        request's plan from the bank using THIS engine's tick EWMA — in a
        fleet, always the destination pool's estimate, never a global
        one."""
        if req.auto_plan and req.plan is None:
            req.plan = self._select_plan(req, now)
            self._c_bank_selected.inc()
            ctx = req.trace
            if ctx is not None and req.plan is not None:
                ctx.nfe = req.plan.S
                ctx.plan_digest = _plan_digest(req.plan)
                ctx.emit("select", now, outcome=self._last_outcome)

    def _admit(self, now: float, results: List[SampleResult]) -> None:
        with self.obs.span(self._span["admit"]):
            while self._free and len(self.queue):
                req, missed = self.queue.pop(now, select=self._fill_auto_plan)
                results.extend(self._drop(m, now, reason="expired")
                               for m in missed)
                if req is None:
                    break
                headroom = (req.deadline - now if req.deadline is not None
                            else None)
                b = self._free.pop()
                ck = req.resume
                slot = _Slot(req=req, table=self._table_for(req), k=0,
                             admit_t=now, headroom_s=headroom)
                self._slots[b] = slot
                if ck is None:
                    self._x2 = self._write_fn(self._x2, self._xT_fn(req.seed),
                                              b * self._rps)
                else:
                    # mid-trajectory restore: refill the slot's tile rows from
                    # the checkpoint and continue from step k — same tables,
                    # same compiled tick, so the remaining steps are the exact
                    # computation the uninterrupted run would have done
                    req.resume = None
                    if not 0 <= ck.k < req.steps:
                        raise ValueError(
                            f"request {req.request_id}: checkpoint k={ck.k} "
                            f"outside [0, {req.steps})")
                    self.write_slot_rows(b, ck.x_rows, ck.hist_rows)
                    slot.k = int(ck.k)
                    slot.previews = int(ck.previews)
                    self._c_resumed.inc()
                wait = (now - req.submit_t if req.submit_t is not None
                        else 0.0)
                self._h_wait.observe(wait)
                ctx = req.trace
                if ctx is not None:
                    if self.pool_id is not None:
                        ctx.pool_id = self.pool_id
                    if ctx.nfe is None:
                        ctx.nfe = req.steps
                    if ctx.plan_digest is None:
                        ctx.plan_digest = _plan_digest(
                            req.resolved_plan(self.schedule, self.clip_x0))
                    ctx.emit("admit", now, slot=b, wait_s=wait,
                             headroom_s=headroom)
                    if ck is not None:
                        ctx.emit("resume", now, k=int(ck.k),
                                 from_pool=ck.pool_id)

    def _states(self) -> StepStates:
        with self.obs.span(self._span["states"]):
            B = self.slots
            t = np.full((B,), self._idle_row["t"], np.int32)
            cols = {k: np.full((B,), v, np.float32)
                    for k, v in self._idle_row.items() if k != "t"}
            seeds = np.zeros((B,), np.uint32)
            ks = np.zeros((B,), np.uint32)
            solver_w = None
            if self.max_order > 1:
                solver_w = np.zeros((B, self.max_order), np.float32)
                solver_w[:, 0] = 1.0       # idle slots: identity combine
            for b, slot in enumerate(self._slots):
                if slot is None:
                    continue
                tab, k = slot.table, slot.k
                t[b] = tab["t"][k]
                for name in cols:
                    cols[name][b] = tab[name][k]
                seeds[b] = np.uint32(slot.req.seed & 0xFFFFFFFF)
                ks[b] = np.uint32(k)
                if solver_w is not None:
                    w = tab["solver_w"][k]     # (order,): plan's own order
                    solver_w[b, :] = 0.0
                    solver_w[b, :len(w)] = w
            seed = None
            if self.stochastic:
                # per-slot per-tick stream seed: full-avalanche mix of the
                # request seed and the step index (placement-invariant)
                seed = jnp.asarray(
                    _fmix32(seeds ^ (ks * _GOLDEN)).astype(np.int32))
            return StepStates(t=jnp.asarray(t),
                              c_x0=jnp.asarray(cols["c_x0"]),
                              c_dir=jnp.asarray(cols["c_dir"]),
                              c_noise=jnp.asarray(cols["c_noise"]),
                              sqrt_a_t=jnp.asarray(cols["sqrt_a_t"]),
                              sqrt_1m_a_t=jnp.asarray(cols["sqrt_1m_a_t"]),
                              seed=seed,
                              solver_w=(None if solver_w is None
                                        else jnp.asarray(solver_w)))

    def _read_slot(self, b: int) -> np.ndarray:
        with self.obs.span(self._span["retire"]):
            rows = self._x2[b * self._rps:(b + 1) * self._rps]
            if self.dtype == jnp.bfloat16:   # numpy has no bf16
                rows = rows.astype(jnp.float32)
            return np.asarray(rows).ravel()[:self._n].reshape(self.shape)

    # --------------------------------------- checkpoint / migrate / cancel
    @property
    def slot_rows_shape(self) -> Tuple[int, int]:
        """One slot's tile-row block shape: (rows_per_slot, 256)."""
        return (self._rps, self._tile_c)

    def resident_requests(self) -> List[Tuple[int, SampleRequest]]:
        """(slot index, request) for every resident slot."""
        return [(b, s.req) for b, s in enumerate(self._slots)
                if s is not None]

    def resident_step(self, b: int) -> int:
        """Next step index of resident slot ``b`` (a snapshot's ``k``),
        read from the host record without touching the device."""
        slot = self._slots[b]
        if slot is None:
            raise ValueError(f"slot {b} is not resident")
        return slot.k

    def write_slot_rows(self, b: int, rows, hist_rows=None) -> None:
        """Overwrite slot ``b``'s tile rows (and optionally its
        eps-history rows) with host-provided values — the checkpoint
        restore primitive (also what the fault injector's NaN poison
        uses). Values round-trip bit-exactly: the rows are written by the
        same jitted ``dynamic_update_slice`` that admission uses, in the
        engine's own dtype, so a snapshot written back reproduces the
        uninterrupted trajectory exactly."""
        rows = jnp.asarray(np.asarray(rows), self.dtype)
        if rows.shape != (self._rps, self._tile_c):
            raise ValueError(
                f"slot rows must be {(self._rps, self._tile_c)}, got "
                f"{rows.shape}")
        self._x2 = self._write_fn(self._x2, rows, b * self._rps)
        if hist_rows is not None and self._hist_write_fn is not None:
            h = jnp.asarray(np.asarray(hist_rows), jnp.float32)
            self._hist2 = self._hist_write_fn(self._hist2, h,
                                              b * self._rps)

    def _checkpoint(self, b: int, x2, hist2,
                    now: Optional[float]) -> SlotCheckpoint:
        """Slot ``b``'s checkpoint, its rows copied out of ``x2`` /
        ``hist2`` (the device state or a host copy of it): the copies own
        only the slot's bytes, exact bits (bfloat16 via ml_dtypes)."""
        slot = self._slots[b]
        lo, hi = b * self._rps, (b + 1) * self._rps
        return SlotCheckpoint(
            request_id=slot.req.request_id, k=slot.k,
            x_rows=np.array(x2[lo:hi]),
            hist_rows=None if hist2 is None else np.array(hist2[:, lo:hi]),
            previews=slot.previews, pool_id=self.pool_id, taken_t=now)

    def snapshot_slot(self, b: int,
                      now: Optional[float] = None) -> SlotCheckpoint:
        """Copy slot ``b``'s full trajectory state to the host.

        Reads happen between ticks (single-threaded contract), so the
        slices observe a settled state."""
        if self._slots[b] is None:
            raise ValueError(f"slot {b} is not resident")
        return self._checkpoint(b, self._x2, self._hist2, now)

    def snapshot_slots(self,
                       now: Optional[float] = None) -> List[SlotCheckpoint]:
        """Checkpoint every resident slot (the supervisor's sweep).

        The whole state (``_x2``, and ``_hist2`` on multistep engines)
        comes to the host in ONE ``device_get`` per sweep, however many
        slots are resident, and each checkpoint is cut from that host
        copy: the same bits as ``snapshot_slot``, one transfer."""
        with self.obs.span(self._span["checkpoint"]):
            resident = [b for b, s in enumerate(self._slots)
                        if s is not None]
            if not resident:
                return []
            x2, hist2 = jax.device_get((self._x2, self._hist2))
            self._c_ck_fetches.inc()
            return [self._checkpoint(b, x2, hist2, now) for b in resident]

    def evict_residents(self) -> List[SampleRequest]:
        """Free every resident slot and hand back its request (no terminal
        accounting — the caller re-routes the work, typically with a
        ``resume`` checkpoint attached; see serving/resilience)."""
        out: List[SampleRequest] = []
        for b, slot in enumerate(self._slots):
            if slot is not None:
                out.append(slot.req)
                self._slots[b] = None
                self._free.append(b)
        self._g_active.set(self.active)
        return out

    def cancel(self, request_id, now: Optional[float] = None) -> bool:
        """Client-initiated cancellation: free the request's slot (or
        remove it from the local queue). Emits a terminal ``cancel`` span
        event; returns False when the request is not here (idempotent)."""
        now = time.perf_counter() if now is None else now
        for b, slot in enumerate(self._slots):
            if slot is not None and slot.req.request_id == request_id:
                self._slots[b] = None
                self._free.append(b)
                self._g_active.set(self.active)
                self._c_cancelled.inc()
                if slot.req.trace is not None:
                    slot.req.trace.emit("cancel", now, k=slot.k)
                return True
        removed = self.queue.remove_if(
            lambda r: r.request_id == request_id)
        for r in removed:
            self._c_cancelled.inc()
            if r.trace is not None:
                r.trace.emit("cancel", now)
        return bool(removed)

    def _deliver_previews(self, x0_2, now: float) -> None:
        with self.obs.span(self._span["previews"]):
            for b, slot in enumerate(self._slots):
                if slot is None:
                    continue
                req, done = slot.req, slot.k + 1
                if (req.preview_every > 0 and req.on_preview is not None
                        and done < req.steps
                        and done % req.preview_every == 0):
                    rows = x0_2[b * self._rps:(b + 1) * self._rps]
                    x0 = (np.asarray(rows).ravel()[:self._n]
                          .reshape(self.shape))
                    req.on_preview(req.request_id, done, x0)
                    slot.previews += 1
                    self._c_previews.inc()
                    if req.trace is not None:
                        req.trace.emit("preview", now, k=done)

    # -------------------------------------------------- device-probe host
    def _record_frame(self, vals: np.ndarray, now: float) -> None:
        """Host side of the probe path (one tiny frame per probed tick).

        Folds the (slots, 6) float32 matrix into per-slot quality
        accumulators (summarized into SampleResult.quality at retire),
        the probe gauges, ``last_frame``, and the flight recorder's ring.
        The defect column needs a previous eps evaluation from the SAME
        request — at k == 0 the buffer/history row still holds a
        predecessor's (or zero) eval, so the first step's value is
        discarded here rather than cleared on device.
        """
        from repro.obs.schema import PROBE_COLUMNS
        i_eps = PROBE_COLUMNS.index("eps_rms")
        i_fin = PROBE_COLUMNS.index("finite_frac")
        i_def = PROBE_COLUMNS.index("defect")
        spec = self.probe_spec
        self._c_frames.inc()
        slot_map: List[Optional[Dict]] = []
        defect_max = finite_min = None
        for b, slot in enumerate(self._slots):
            if slot is None:
                slot_map.append(None)
                continue
            slot_map.append({"slot": b, "request_id": slot.req.request_id,
                             "k": slot.k})
            row = vals[b]
            slot.q_frames += 1
            if spec.eps_norm and math.isfinite(row[i_eps]):
                slot.q_eps_rms = float(row[i_eps])
            if spec.finite and math.isfinite(row[i_fin]):
                f = float(row[i_fin])
                slot.q_finite_min = (f if slot.q_finite_min is None
                                     else min(slot.q_finite_min, f))
                finite_min = (f if finite_min is None
                              else min(finite_min, f))
            if spec.defect and slot.k >= 1 and math.isfinite(row[i_def]):
                d = float(row[i_def])
                slot.q_defect_sum += d
                slot.q_defect_n += 1
                slot.q_defect_max = (d if slot.q_defect_max is None
                                     else max(slot.q_defect_max, d))
                defect_max = (d if defect_max is None
                              else max(defect_max, d))
        if defect_max is not None:
            self._last_defect_max = defect_max
            self._g_defect.set(defect_max)
        if finite_min is not None:
            self._last_finite_min = finite_min
            self._g_finite.set(finite_min)
        frame = {"tick": self.ticks, "now": now, "pool": self.pool_id,
                 "slots": slot_map, "values": vals.tolist()}
        self.last_frame = frame
        if self.flight is not None:
            self.flight.record(frame)

    @staticmethod
    def _slot_quality(slot: _Slot) -> Optional[Dict]:
        """Per-request probe summary attached to SampleResult.quality."""
        if slot.q_frames == 0:
            return None
        return {
            "frames": slot.q_frames,
            "eps_rms_last": slot.q_eps_rms,
            "finite_frac_min": slot.q_finite_min,
            "defect_max": slot.q_defect_max,
            "defect_mean": (slot.q_defect_sum / slot.q_defect_n
                            if slot.q_defect_n else None),
        }

    # ----------------------------------------------------------- the loop
    def tick(self, now: Optional[float] = None) -> List[SampleResult]:
        """One engine tick: admit, advance every resident slot, retire.

        ``now`` drives all timestamps/deadlines (virtual-clock replay); in
        wall-clock mode (now=None) retirement re-stamps AFTER the step so
        finish_t/deadline checks include the compute that finished it.
        """
        with self.obs.span(self._span["tick"]):
            return self._tick(now)

    def _tick(self, now: Optional[float]) -> List[SampleResult]:
        wall = now is None
        now = time.perf_counter() if wall else now
        results: List[SampleResult] = []
        self._admit(now, results)
        if self.active == 0:
            return results
        states = self._states()
        traces0 = self._traces
        frame_dev = None
        probed = self.probes_on and self._tick_probed is not None
        t0 = time.perf_counter()
        with self.obs.span(self._span["launch"]):
            if probed:
                p = (() if self.eps_params is None else (self.eps_params,))
                if self.max_order == 1:
                    if self._probe_prev is not None:
                        out, frame_dev, self._probe_prev = self._tick_probed(
                            self._x2, self._probe_prev, states, *p)
                    else:
                        out, frame_dev = self._tick_probed(
                            self._x2, states, *p)
                else:
                    out, self._hist2, frame_dev = self._tick_probed(
                        self._x2, self._hist2, states, *p)
            elif self.max_order == 1:
                out = (self._tick_fn(self._x2, states)
                       if self.eps_params is None
                       else self._tick_fn(self._x2, states,
                                          self.eps_params))
            else:
                out, self._hist2 = (
                    self._tick_fn(self._x2, self._hist2, states)
                    if self.eps_params is None
                    else self._tick_fn(self._x2, self._hist2, states,
                                       self.eps_params))
            self._x2, x0_2 = out if self.preview else (out, None)
        with self.obs.span(self._span["block"]):
            jax.block_until_ready(self._x2)
        t1 = time.perf_counter()
        self._c_wall.inc(t1 - t0)
        # EWMA per-step tick latency — the deadline-selection policy's
        # latency input (a resident request advances one step per tick).
        # Compile ticks are excluded: XLA tracing is a one-off 100-1000x
        # a steady tick, and folding it in would make deadline admissions
        # pick the cheapest bank row for dozens of requests afterwards.
        # (The tick-latency histogram gates the same way.)
        if self._traces == traces0:
            self._h_tick.observe(t1 - t0)
            if self.tick_ewma_s is None:
                self.tick_ewma_s = t1 - t0
            else:
                a = self.tick_ewma_alpha
                self.tick_ewma_s = (a * (t1 - t0)
                                    + (1.0 - a) * self.tick_ewma_s)
            self._g_ewma.set(self.tick_ewma_s)
        if wall:
            now = t1
        self._c_ticks.inc()
        self._c_slot_steps.inc(self.active)
        if frame_dev is not None:
            # before the retire loop: every occupied slot's recorded k is
            # the step index this frame measured (k increments below)
            self._record_frame(np.asarray(frame_dev), now)
        if x0_2 is not None:
            self._deliver_previews(x0_2, now)
        for b, slot in enumerate(self._slots):
            if slot is None:
                continue
            slot.k += 1
            if slot.k == 1 and slot.req.trace is not None:
                slot.req.trace.emit("first_tick", now)
            if slot.k >= slot.req.steps:
                req = slot.req
                missed = (req.deadline is not None and now > req.deadline)
                results.append(SampleResult(
                    request_id=req.request_id, x0=self._read_slot(b),
                    S=req.steps, eta=req.eta_label, submit_t=req.submit_t,
                    admit_t=slot.admit_t, finish_t=now,
                    previews=slot.previews, deadline_missed=missed,
                    deadline_headroom_s=slot.headroom_s,
                    auto_plan=req.auto_plan, pool_id=self.pool_id,
                    quality=self._slot_quality(slot)))
                self._c_completed.inc()
                if missed:
                    self._c_miss.inc()
                service = now - slot.admit_t
                self._h_service.observe(service)
                if req.submit_t is not None:
                    self._h_latency.observe(now - req.submit_t)
                if req.deadline is not None:
                    self._h_slack.observe(req.deadline - now)
                if req.trace is not None:
                    req.trace.emit("retire", now, service_s=service,
                                   missed=True if missed else None)
                self._slots[b] = None
                self._free.append(b)
        self._g_active.set(self.active)
        return results

    def run(self, max_ticks: Optional[int] = None,
            now_fn: Optional[Callable[[], float]] = None
            ) -> List[SampleResult]:
        """Tick until the queue and every slot drain (or max_ticks)."""
        results: List[SampleResult] = []
        n = 0
        while len(self.queue) or self.active:
            if max_ticks is not None and n >= max_ticks:
                break
            results.extend(self.tick(now_fn() if now_fn else None))
            n += 1
        return results

    def serve(self, requests: Sequence[SampleRequest],
              now: Optional[float] = None) -> List[SampleResult]:
        """Submit a request list and drain it — the one-call entry.

        Back-pressure rejections (queue depth bound) come back as dropped
        results, so every submitted request_id has exactly one result.
        """
        results: List[SampleResult] = []
        for r in requests:
            if not self.submit(r, now=now):
                t = time.perf_counter() if now is None else now
                r.submit_t = t if r.submit_t is None else r.submit_t
                results.append(self._drop(r, t, missed=False))
        results.extend(self.run())
        return results

    def reset_stats(self) -> None:
        """Zero the throughput instruments (e.g. after a warm-up trace).

        Keeps what warm-up exists to build: the compiled-program cache,
        ``compiled_ticks``, the measured ``tick_ewma_s`` the deadline-
        selection policy consults, and the live gauges (occupancy/EWMA
        mirrors — re-set every tick). Queue arrival counters are the
        queue's own and are untouched, matching the pre-registry
        behavior.
        """
        keep = {"engine_compiled_ticks_total",
                "engine_weight_installs_total"}
        for inst in self.obs.registry.instruments():
            if (inst.name.startswith("engine_") and inst.kind != "gauge"
                    and inst.name not in keep):
                inst.reset()

    def stats(self) -> Dict:
        denom = max(self.ticks * self.slots, 1)
        return {
            "pool_id": self.pool_id,
            "mesh": (None if self.mesh is None
                     else dict(self.mesh.shape)),
            "state_sharded": (self._state_sharding is not None
                              and any(ax is not None for ax in
                                      self._state_sharding.spec)),
            "slots": self.slots,
            "active": self.active,
            "ticks": self.ticks,
            "tick_variant": self.tick_variant,
            "slot_steps": self.slot_steps,
            "occupancy": self.slot_steps / denom,
            "completed": self.completed,
            "dropped": self.dropped,
            "cancelled": int(self._c_cancelled.value),
            "resumed": int(self._c_resumed.value),
            "deadline_missed": self.deadline_missed,
            "previews_sent": self.previews_sent,
            "queued": len(self.queue),
            "queue_rejected": self.queue.rejected,
            "tick_wall_s": self._tick_wall_s,
            "tick_ewma_s": self.tick_ewma_s,
            "steps_per_s": self.slot_steps / max(self._tick_wall_s, 1e-9),
            "compiled_ticks": self._traces,
            "plan_bank": (None if self.plan_bank is None
                          else len(self.plan_bank)),
            "bank_selected": self.bank_selected,
            "stochastic": self.stochastic,
            "preview": self.preview,
            "max_order": self.max_order,
            "mega_tick": self.use_mega,
            "dtype": jnp.dtype(self.dtype).name,
            "donated": self.donate,
            "probes": (None if self.probe_spec is None
                       else (self.probe_spec.describe() if self.probes_on
                             else "off")),
            "probe_frames": int(self._c_frames.value),
            "probe_defect_max": self._last_defect_max,
            "probe_finite_min": self._last_finite_min,
        }
