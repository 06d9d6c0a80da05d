"""Request/result records for the continuous-batching scheduler.

A :class:`SampleRequest` is one sampling job with its OWN quality/latency
dial. The first-class way to say what to run is a frozen
``repro.sampling.SamplerPlan`` (``plan=``): any tau spacing (uniform /
quadratic / explicit-learned), any sigma schedule (scalar eta, per-step
eta, explicit sigmas), and any solver order the engine was built for —
the scheduler multiplexes arbitrary mixes of these through one resident
slot batch with zero retraces. The legacy scalar knobs (S, eta, tau_kind,
sigma_hat, order) remain as a convenience and compile to the equivalent
plan at admission.

Timestamps are in the CALLER's clock (whatever ``now`` the engine is driven
with — wall time by default, a virtual clock in trace-replay benchmarks).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional

import numpy as np

from repro.core import SamplerConfig
from repro.sampling import SamplerPlan


@dataclasses.dataclass
class SlotCheckpoint:
    """A resident slot's full trajectory state at step ``k``.

    DDIM's generative process is deterministic given the plan and the
    per-step noise stream seed (paper Eq. 12): ``(x_t rows, k,
    eps-history rows)`` fully determine the rest of the trajectory, so a
    checkpoint restored into ANY capability-homogeneous pool resumes the
    run exactly — for eta=0 order-1 the resumed output is bit-identical
    to the uninterrupted one (asserted in tests/test_resilience.py and
    gated by benchmarks/chaos_recovery.py). Arrays are host-side numpy
    copies in the engine's exact dtypes: ``x_rows`` is the slot's
    (rows_per_slot, 256) tile block, ``hist_rows`` the matching
    (max_order-1, rows_per_slot, 256) float32 eps-history block (None on
    history-free engines).
    """

    request_id: int
    k: int                             # next step index to run (0..S-1)
    x_rows: np.ndarray                 # slot-tile rows, engine dtype
    hist_rows: Optional[np.ndarray]    # eps-history rows (fp32) or None
    previews: int = 0                  # previews already streamed
    pool_id: Optional[int] = None      # pool that took the snapshot
    taken_t: Optional[float] = None    # caller-clock snapshot time


@dataclasses.dataclass
class SampleRequest:
    """One sampling job for the continuous-batching engine."""

    request_id: int
    S: int = 50                        # per-request step budget (dim tau)
    eta: float = 0.0                   # 0 = DDIM, 1 = DDPM (Eq. 16)
    tau_kind: str = "linear"           # per-request sub-sequence spacing
    sigma_hat: bool = False            # over-dispersed DDPM variant
    solver_order: int = 1              # Adams–Bashforth solver order of
    #                                     the scalar-knob plan (eta = 0)
    plan: Optional[SamplerPlan] = None  # full per-request trajectory plan;
    #                                     overrides the scalar knobs above
    auto_plan: bool = False            # let the engine pick the plan from
    #                                     its PlanBank at ADMISSION, using
    #                                     the deadline headroom and the
    #                                     measured tick latency (the
    #                                     engine fills ``plan`` in)
    seed: int = 0                      # x_T + noise-stream seed
    deadline: Optional[float] = None   # absolute completion deadline
    preview_every: int = 0             # stream x0-previews every k ticks
    on_preview: Optional[Callable] = None  # f(request_id, step_k, x0: np)
    submit_t: Optional[float] = None   # stamped by the admission queue
    affinity_key: Optional[int] = None  # fleet routing: requests sharing a
    #                                     key prefer the same slot pool
    #                                     (session/user stickiness); falls
    #                                     back to least-loaded when that
    #                                     pool is draining or full
    model: Optional[str] = None        # multi-model routing: restrict this
    #                                     request to pools serving the named
    #                                     resident checkpoint (gateway
    #                                     ModelRegistry); None = any pool
    #                                     (single-model fleets ignore it)
    trace: Optional[object] = None     # obs.TraceContext: the request's
    #                                     span head, created by whichever
    #                                     telemetry-enabled tier first sees
    #                                     the request and carried through
    #                                     queue / routing / engine; None =
    #                                     untraced (events cost nothing)
    resume: Optional[SlotCheckpoint] = None  # mid-trajectory restore: the
    #                                     admitting engine writes the
    #                                     checkpoint's rows instead of
    #                                     drawing x_T and continues from
    #                                     step k (quarantine migration —
    #                                     see serving/resilience); cleared
    #                                     at admission

    @property
    def stochastic(self) -> bool:
        if self.plan is not None:
            return self.plan.stochastic
        return self.eta > 0.0 or self.sigma_hat

    @property
    def steps(self) -> int:
        """The step budget actually executed (plan-aware S)."""
        return self.plan.S if self.plan is not None else self.S

    @property
    def order(self) -> int:
        """The solver order actually executed (plan-aware)."""
        return (self.plan.order if self.plan is not None
                else self.solver_order)

    @property
    def eta_label(self) -> float:
        """Scalar eta for result bookkeeping (NaN for non-scalar specs)."""
        if self.plan is None:
            return self.eta
        return (self.plan.sigma.eta if self.plan.sigma.kind == "eta"
                else float("nan"))

    def sampler_config(self, clip_x0: Optional[float] = None
                       ) -> SamplerConfig:
        """The equivalent whole-trajectory config (engine-level clip_x0).

        Legacy-knob requests only; plan requests carry their own policy.
        """
        return SamplerConfig(S=self.S, eta=self.eta, tau_kind=self.tau_kind,
                             sigma_hat=self.sigma_hat, clip_x0=clip_x0)

    def resolved_plan(self, schedule, clip_x0: Optional[float] = None
                      ) -> SamplerPlan:
        """The plan this request executes on the given engine schedule."""
        if self.plan is not None:
            return self.plan
        return self.sampler_config(clip_x0).to_plan(schedule,
                                                    order=self.solver_order)


@dataclasses.dataclass
class SampleResult:
    """Completed (or dropped) request with latency accounting.

    The derived latency fields decompose exactly:
    ``queue_wait_s + service_s == latency_s`` for every result —
    completed requests split at ``admit_t``; requests dropped before
    admission count their whole life as queue wait (service 0). The obs
    summary tables and the trace-span wait_s/service_s event fields are
    built on this identity (asserted in tests/test_obs.py).
    """

    request_id: int
    x0: Optional[np.ndarray]           # None iff dropped before running
    S: Optional[int]                   # None iff dropped before an
    #                                     auto_plan selection happened
    eta: float
    submit_t: float
    admit_t: Optional[float]           # None iff never admitted
    finish_t: float
    previews: int = 0
    deadline_missed: bool = False      # finished (or dropped) past deadline
    dropped: bool = False              # never ran: expired in the queue
    # --- selection-policy observability (the deadline-aware admission's
    # inputs, recorded per request): the deadline headroom measured AT
    # ADMISSION (deadline - admit time; None without a deadline) and
    # whether the plan came from the bank.
    deadline_headroom_s: Optional[float] = None
    auto_plan: bool = False
    pool_id: Optional[int] = None      # which slot pool served it (fleet);
    #                                     None = single engine, or dropped
    #                                     at the fleet tier before routing
    # per-request device-probe summary (None unless the serving engine
    # ran with probes on): frames / eps_rms_last / finite_frac_min /
    # defect_max / defect_mean — see obs/probes.py for column semantics
    quality: Optional[Dict] = None

    @classmethod
    def drop(cls, req: SampleRequest, now: float, *, missed: bool = True,
             pool_id: Optional[int] = None) -> "SampleResult":
        """The result record for a request that never ran.

        An ``auto_plan`` request dropped before admission never had a plan
        selected, so it reports no step budget rather than the dataclass
        default S.
        """
        steps = (None if req.auto_plan and req.plan is None else req.steps)
        return cls(request_id=req.request_id, x0=None, S=steps,
                   eta=req.eta_label, submit_t=req.submit_t, admit_t=None,
                   finish_t=now, deadline_missed=missed, dropped=True,
                   auto_plan=req.auto_plan, pool_id=pool_id)

    @property
    def nfe(self) -> Optional[int]:
        """NFE of the plan actually executed (alias of ``S``; None when
        the request was dropped before an auto_plan selection)."""
        return self.S

    @property
    def queue_wait_s(self) -> float:
        start = self.admit_t if self.admit_t is not None else self.finish_t
        return start - self.submit_t

    @property
    def service_s(self) -> float:
        return (self.finish_t - self.admit_t
                if self.admit_t is not None else 0.0)

    @property
    def latency_s(self) -> float:
        return self.finish_t - self.submit_t
