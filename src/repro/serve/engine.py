"""Multi-stream separation service.

``SeparationService`` admits/evicts separation *sessions* into the slots of
a ``repro.stream.SeparatorBank`` (continuous batching: a slot free-list, new
sessions drop into freed slots between ticks); every tick steps all live
sessions with one fused bank program (the multi-stream analogue of the
paper's single always-on FPGA datapath).

Session lifecycle state machine (``SeparationService``)::

        admit()                 admit() [no free slot]
           │                        │
           ▼                        ▼
        ACTIVE ◄── backfill ──── QUEUED ──── evict() ──► (dequeued, None)
           │                        ▲
           │  step(): conv stat     │ waiting room is a pluggable
           │  < threshold for       │ ``AdmissionScheduler`` (FIFO default;
           │  `patience` ticks      │ priority + per-tenant quotas; EDF) —
           ▼                        │ a full queue raises (backpressure)
        CONVERGED ──────────────────┘ freed slot backfilled from the
           │                          scheduler IN THE SAME TICK
           │
           ├─ no DriftPolicy ──────────────────────► EVICTED — final
           │                                         ``SMBGDState`` + stats
           │                                         retained in ``finished``
           │
           ├─ DriftPolicy(mode="boost"), source bound, nobody queued:
           │    stay HOT in the slot (status ``"converged"``), still served
           │    every tick; live conv EMA > ``retrigger`` ──► ``DriftEvent``:
           │    μ × ``boost`` for ``boost_ticks`` ticks (per-stream
           │    ``BankHyperparams`` row, no retrace) and back to ACTIVE
           │    (re-adapting).  Waiting admissions PREEMPT the most-converged
           │    hot session (──► EVICTED, reason ``"preempted"``), so keeping
           │    sessions warm never starves the queue.
           │
           └─ DriftPolicy(mode="readmit"), source bound: slot evicts as
                usual but the session PARKS (frozen state + its source);
                every ``probe_every`` ``run_tick``s the watchdog probes ALL
                parked sessions in BATCHES: the due sessions' frozen states
                are stacked into a transient probe bank (``probe_batch``
                sessions per launch, ragged tails padded + masked inactive)
                and one no-commit bank launch computes every VIRTUAL conv
                statistic (same ‖ΔB‖/‖B‖ formula, out of band, no slot,
                frozen separators never mutated) — O(parked / probe_batch)
                dispatches per probe tick, not O(parked).  A parked source
                that drains mid-probe EVICTS the session (reason
                ``"exhausted"``).  EMA > ``retrigger`` ──► ``DriftEvent``:
                re-admitted through the scheduler, warm-started from the
                frozen state (ACTIVE, or back to PARKED under contention).
                ``probe_batch=0`` selects the legacy one-dispatch-per-session
                loop (the batched engine's differential-test oracle).

Fault containment (``HealthPolicy`` — orthogonal to the drift watchdog;
see ``serve.health``)::

        ACTIVE ── health word ≠ 0 (kernel refused the commit) ──┐
           ▲                                                    ▼
           │  rollback to shadow + μ × ``mu_cut``          [escalation]
           ◄── ≤ ``max_rollbacks`` offenses / ``window`` ───────┤
           ▲                                                    ▼
           │  probation: ``probation`` healthy probes      QUARANTINED
           ◄── (warm re-admission, ladder memory kept) ◄────────┤
                                                                ▼
                              > ``max_quarantines`` quarantines │
                 EVICTED, reason ``"diverged"`` (+ provenance) ◄┘

    Detection is free: the megakernel folds a per-stream health word
    (non-finite B′/Ĥ′/Y bits + an update-magnitude blow-up bit) into the
    same in-register reduction as ``conv``, and REFUSES the offender's
    commit in-kernel — the slot keeps its pre-tick state like a frozen one.
    The service keeps a per-slot last-known-good SHADOW snapshot
    (copy-on-healthy every ``shadow_every`` ticks, re-seeded per slot at
    activation) to roll offenders back to; μ cuts ride the same per-stream
    ``BankHyperparams`` traced-operand rows as the drift boost (no retrace).
    Quarantined sessions are probed out of band like parked ones, but the
    probe's VIRTUAL health word (not conv) decides release.  Source-side
    faults never reach the ladder: ``run_tick`` isolates a raising/stalling
    source to its own session (degraded tick via the active mask; wrap
    flaky feeds in ``data.resilience.ResilientSource`` for bounded
    retry/backoff/stall-timeout first).

Latency SLOs (``SLOPolicy`` — see ``serve.slo``; telemetry always on)::

        every tick ── TickTimer: block_until_ready(state.conv) ──► timed dt
           │          (1-in-k under sync_every>1; block_ticks syncs harder)
           ▼
        LatencySketch: p50/p99/p999, exact window + log-binned lifetime
           │
           ├─ no deadline_budget_s ────────────► telemetry only
           │
           └─ dt > deadline_budget_s: MISS ──► n_deadline_misses++, the
                windowed miss rate and every served session's
                ``DeadlineMonitor`` advance; over ``max_miss_rate``:
                  * ``shed=True`` — the worst-missing active session is
                    preempted (reason ``"shed"``, lands in ``finished``)
                  * ``gate_admissions=True`` — backfills and direct
                    admissions HOLD until the miss window recovers

    The tick clock measures TIME-TO-READY regardless of ``block_ticks``:
    the dispatch-only latencies the old clock reported on asynchronous
    backends never enter the books.  ``run_tick`` bills its whole duration
    (pull + step + drain + out-of-band probes) as the tick's latency;
    run_ticks with no data batch count as *empty ticks* (distinct counter,
    still sketched and budget-checked, ``n_ticks`` untouched).  Recorded
    loads replay deterministically: wrap sources in
    ``data.sources.RecordingSource``, persist with ``save_recording``, and
    drive any service through the trace with ``serve.slo.replay`` — the
    ``--slo`` benchmark row gates p99/miss-rate regressions in CI.

Adaptive μ (``MomentPolicy`` — see ``serve.moments``; needs a bank with
``moments=True`` telemetry)::

        every tick ── kernel folds [Σy², Σy⁴] into the conv reduction ──┐
           ▲              (8 bytes/stream of extra HBM — output only)   ▼
           │                                     κ = N·Σy⁴/(Σy²)²  (host-side)
           │                                                            ▼
           │                    MomentController: fast EMA (current output
           │                    distribution) vs slow EMA (converged reference)
           │                                                            ▼
           │    ┌─ warmup (< warmup_ticks) or |dev − 1| ≤ deadband ─► scale 1.0
           │    │
           └────┴─ deviation (drift re-mixed Y; CLT drags kurtosis toward
                   Gaussian) ─► μ × clamp(dev^gain) — ANNEALS back to 1 as
                   re-convergence pulls the fast EMA home (what a fixed
                   ``DriftPolicy.boost`` pulse cannot do)

    Composition of the three μ writers is pinned (and regression-tested):
    a HealthPolicy μ-cut WINS outright while it is live (containment beats
    adaptation — never boost a separator you just rolled back), otherwise
    the DriftPolicy boost and the controller scale MULTIPLY::

        μ_eff = μ_base · (cut_on ? cut_scale : boost_scale · ctrl_scale)

    Rollback, quarantine, eviction and (re-)activation RESET the session's
    controller memory — the old kurtosis reference no longer describes the
    restored/new separator, so the EMAs re-seed from the next usable tick.

Elastic capacity (``AutoscalePolicy`` — see ``serve.elastic``; the bank's
width S is no longer fixed at construction)::

        run_tick ── after the probe phase: autoscaler reads (width, active,
           │        queue depth, windowed deadline_miss_rate, cooldown)
           ▼
        ┌─ queue ≥ grow_queue_depth, or miss rate > grow_miss_rate ──► GROW
        │     width × factor (≤ max_streams): state grows by leaf-wise
        │     prefix copy (new slots blank — NO RNG consumed), free list
        │     gains the new high slots, the queue backfills into them the
        │     same tick; the step function re-resolves autotune geometry at
        │     the new (S, P, m, n, backend) key and is cached per width
        │
        ├─ queue EMPTY + no miss pressure + utilization < shrink band ──►
        │     COMPACT then SHRINK: live slots migrate to the low end
        │     (``SeparatorBank.move_slot`` — every leaf carried verbatim,
        │     μ ladders and the shadow move with them), then the high
        │     half truncates to the smallest ladder width holding
        │     utilization ≤ hold_utilization
        │
        └─ otherwise (or within cooldown_ticks of the last resize) ──► HOLD

    The two bands cannot flap (validated: ``shrink_utilization ≤
    hold_utilization / factor``, so a just-shrunk bank sits above the shrink
    band; growth needs queue/deadline pressure, which growing relieves).
    Resizes are INVISIBLE to co-tenants: surviving sessions' (B, Ĥ, step,
    conv) trajectories are bit-identical to a fixed-width run on both the
    vmap and megakernel paths (property-pinned in tests/test_elastic.py) —
    the persistent layout's trailing dims depend only on (n, m, dtype
    policy), so a resize is always a prefix copy, never a re-layout.
    ``grow``/``shrink``/``compact`` are also direct public methods (manual
    capacity ops need no policy); resize cost lands in the resizing tick's
    recorded latency, and the resize history (tick, action, widths, reason)
    rides ``lifecycle`` snapshots through ``save``/``restore``.  Restores
    accept a checkpoint saved at a DIFFERENT width: live sessions re-place
    into the new free list (prefix-packed, slot map remapped), failing
    loudly only when they exceed the new capacity.

Ingestion: ``run_tick()`` is the scheduler-driven pull loop — sessions bind
a ``data.sources.SignalSource`` at admit time; each tick backfills free
slots, pulls one channel-major ``(m, P)`` block per bound source, advances
every pulling session with ONE fused bank step, evicts drained sources
(reason ``"exhausted"``) and probes parked sessions.  Push-mode ``step()``
remains for callers that assemble their own batches (both can be mixed:
sessions without a source are simply never pulled).

Backpressure semantics: ``admit`` NEVER silently drops a session.  With a
free slot (and an admission the scheduler allows — per-tenant quotas gate
here too) it activates immediately (returns the slot index); otherwise it
enqueues up to ``max_queue`` deep (returns ``None``) and past that raises
``RuntimeError``.  Queued sessions hold no device state — their separator is
initialized at activation time, so the γ step-0 gate applies at the tick
they actually start, and a queued session cancelled via ``evict`` costs
nothing.  (Re-admitted drifters are the exception: they warm-start from
their frozen separator, step counter and all — no γ re-gate.)

Convergence detection rides the bank's in-kernel statistic
(``BankState.conv`` — relative update magnitude ``‖ΔB‖_F/‖B‖_F``, computed at
commit time inside the megakernel, so detection costs one (S,)-float host
read per tick, not a state round-trip).  ``ConvergencePolicy`` turns the raw
statistic into an eviction decision: optional EMA smoothing, a threshold the
smoothed statistic must stay under for ``patience`` consecutive data ticks,
a ``min_ticks`` floor, and an optional Amari-index confirmation for sessions
whose true mixing matrix was registered via ``set_mixing`` (the blind
statistic can dip early; the Amari check vetoes eviction until the separator
actually separates).

Tracing: ``run_tick`` and ``step`` mark their phases with
``jax.profiler.TraceAnnotation`` spans (names in ``SPANS``), so a profiler
trace shows the host's phases on the device operations' clock::

    serve.run_tick            (metadata tick=<n>)
      serve.backfill          free slots filled from the scheduler
      serve.pull              one block from every bound source
      serve.step              also a root when a caller pushes batches
        serve.stage           checks, staging copy
          serve.h2d           host→device copy of the staged batch and
                              mask (metadata bytes=<staged bytes>)
        serve.launch          the jitted bank step's dispatch (metadata
                              block_s=<streams per grid cell>
                              stream_blocks=<grid cells over the streams>)
        serve.ready           the tick timer's sync on the conv leaf
        serve.outputs         one host read of the step's Y, cut to
                              per-session views (metadata sessions=<k>
                              width=<S> bytes=<bytes read to the host>)
        serve.moments         moments readback and controller
        serve.health          the health sweep
        serve.policy          the convergence and drift sweep
      serve.release           drained sources released
      serve.probe             parked and quarantined probes
      serve.autoscale         the autoscaler

    A span is entered once per tick, never per session, and costs under a
    microsecond when no profiler session is active; tracing adds no sync.

Memory-system knobs (PR 6) — all set on the ``SeparatorBank`` the service
wraps; the engine threads them to every bank it derives (probe banks pin the
serving bank's resolved geometry with ``autotune=False``):

  * ``dtype_policy="bf16"`` halves the persistent per-session HBM footprint
    (``bank.layout.persistent_bytes_per_session``) — the capacity lever for
    "how many sessions fit per device".  Gradient fold and commit
    accumulation stay f32 in VMEM; only stored ``B``/``Ĥ`` shrink.  The
    per-stream hyperparameter rows (μ boost) and the conv statistic remain
    f32 operands regardless of policy — they are compute-side, not
    persistent state.  Worth it on real TPU at scale; on CPU interpret it
    only changes bytes, not speed.
  * tile geometry (``block_p``/``block_s``) resolves from the
    persisted autotune cache (``AUTOTUNE.json``, see ``stream.autotune``)
    when left unset — run ``benchmarks/stream_throughput.py --autotune`` on
    the target backend once per deployment shape.  ``dtype_policy`` is never
    auto-applied: precision is a caller decision.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import math
import time
from typing import Any, Callable, Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import metrics as metrics_lib
from repro.core import smbgd as smbgd_lib
from repro.core.smbgd import BankHyperparams, SMBGDState
from repro.data import sources as sources_lib
from repro.serve.drift import DriftEvent, DriftMonitor, DriftPolicy
from repro.serve.elastic import AutoscalePolicy, ResizeDecision
from repro.serve.health import HealthEvent, HealthMonitor, HealthPolicy
from repro.serve.moments import MomentController, MomentPolicy
from repro.serve.scheduling import (
    AdmissionScheduler,
    SchedulerContext,
    SessionMeta,
)
from repro.serve.slo import (
    DeadlineMonitor,
    LatencySketch,
    SLOEvent,
    SLOPolicy,
    TickTimer,
)
from repro.stream.bank import BankState, SeparatorBank

# The phase spans of a tick (module docstring, "Tracing").  Every name has
# the ``serve.`` prefix, so none collides with a span a client opens around
# its own calls.
SPANS = (
    "serve.run_tick",
    "serve.backfill",
    "serve.pull",
    "serve.step",
    "serve.stage",
    "serve.h2d",
    "serve.launch",
    "serve.ready",
    "serve.outputs",
    "serve.moments",
    "serve.health",
    "serve.policy",
    "serve.release",
    "serve.probe",
    "serve.autoscale",
)
_span = jax.profiler.TraceAnnotation


@dataclasses.dataclass
class SessionStats:
    """Per-session serving counters (host-side bookkeeping).

    ``admitted_at`` stamps ``admit()`` (queue entry); ``activated_at`` stamps
    the slot claim (``_activate``) — the gap is ``queue_wait_s``.  Throughput
    divides by SERVICE time (since activation), never by queue wait: a
    session that sat out a full waiting room is not slow, it was waiting."""

    admitted_at: float  # time.perf_counter() at admission (queue entry)
    activated_at: Optional[float] = None  # slot claimed (None = not yet)
    ticks: int = 0
    samples: int = 0

    def queue_wait_s(self) -> float:
        """Seconds between admission and slot activation (0 until active)."""
        if self.activated_at is None:
            return 0.0
        return max(self.activated_at - self.admitted_at, 0.0)

    def samples_per_s(self, now: Optional[float] = None) -> float:
        """Service-time throughput: samples over wall-clock since ACTIVATION
        (falls back to admission time for stats born before activation)."""
        now = time.perf_counter() if now is None else now
        start = (
            self.activated_at
            if self.activated_at is not None
            else self.admitted_at
        )
        return self.samples / max(now - start, 1e-9)


class MetricsView(dict):
    """The service's metrics surface: a plain dict of counters that is ALSO
    callable — ``svc.metrics()`` returns the same mapping as ``svc.metrics``,
    so scrape code written against either the property convention (this
    repo's benchmarks) or the method convention (harness front-ends) reads
    one surface."""

    def __call__(self) -> "MetricsView":
        return self


@dataclasses.dataclass(frozen=True)
class ConvergencePolicy:
    """When is a session done?  Threshold + patience + floor over the bank's
    in-step convergence statistic (``BankState.conv``), with optional EMA
    smoothing and an optional ground-truth Amari confirmation.

    A session auto-evicts at the first data tick where ALL of:
      * it has received at least ``min_ticks`` mini-batches,
      * its (EMA-smoothed when ``ema > 0``) update magnitude has been below
        ``threshold`` for ``patience`` consecutive data ticks,
      * if ``amari_threshold`` is set AND the session's mixing matrix was
        registered via ``SeparationService.set_mixing``: the Amari index of
        ``B·A`` is below ``amari_threshold`` (unknown mixing → the blind
        statistic alone decides).
    """

    threshold: float = 1e-3  # conv stat must stay under this ...
    patience: int = 3  # ... for this many consecutive data ticks
    min_ticks: int = 8  # never evict younger sessions (γ warm-up)
    ema: float = 0.0  # smoothing: s' = ema·s + (1−ema)·x (0 → raw)
    amari_threshold: Optional[float] = None  # optional ground-truth gate

    def __post_init__(self) -> None:
        if self.patience < 1:
            raise ValueError("patience must be >= 1")
        if not (0.0 <= self.ema < 1.0):
            raise ValueError("ema must be in [0, 1)")


@dataclasses.dataclass
class ConvergenceMonitor:
    """Per-session streaming state of the convergence decision (host-side;
    serializable via ``dataclasses.asdict`` for checkpoint round-trips).

    Carries its own data-tick counter so the ``min_ticks`` floor survives a
    checkpoint round-trip exactly (``SessionStats`` deliberately restarts its
    counters at restore — observability describes the restored epoch, the
    convergence decision must not).  The EMA recurrence is the host-side
    twin of ``core.metrics.ema_update`` (kept in plain Python floats — this
    runs per served session per tick; a parity test pins the two)."""

    stat: float = float("inf")  # EMA-smoothed statistic (raw when ema == 0)
    below: int = 0  # consecutive data ticks with stat < threshold
    ticks: int = 0  # data ticks observed (min_ticks floor)
    skipped: int = 0  # NaN samples dropped (faulted ticks never poison)

    def update(self, x: float, policy: ConvergencePolicy) -> None:
        if math.isnan(x):
            # a faulted tick's statistic: skip the sample, count it — the
            # EMA and the below-streak must survive a NaN unharmed (the
            # host-side twin of ``core.metrics.ema_update``'s NaN guard)
            self.skipped += 1
            return
        if policy.ema and math.isfinite(self.stat):
            self.stat = policy.ema * self.stat + (1.0 - policy.ema) * x
        else:
            self.stat = x
        self.below = self.below + 1 if self.stat < policy.threshold else 0
        self.ticks += 1


@dataclasses.dataclass
class EvictionRecord:
    """What the service hands back (or retains) when a session leaves a slot.

    The evicted ``SMBGDState`` is sliced out of the bank *before* the slot is
    re-initialized for a backfill, so ``state`` is exactly the session's state
    at eviction time; ``stats``/``monitor`` preserve the per-session serving
    counters across the eviction (the churn observability surface).
    """

    state: SMBGDState
    stats: SessionStats
    monitor: Optional[ConvergenceMonitor]
    reason: str  # "converged" | "evicted" | "exhausted" | "preempted" |
    #              "diverged" | "quarantined" | "shed"
    tick: int  # service tick counter at eviction
    # divergence provenance: the health-escalation ladder state at eviction
    # (offense stamps, quarantine count, last non-zero health word) — set for
    # reason == "diverged" records, None otherwise
    health: Optional[HealthMonitor] = None


@dataclasses.dataclass
class ParkedSession:
    """A converged-and-evicted session kept under drift watch
    (``DriftPolicy(mode="readmit")``): its eviction record (frozen separator
    state + stats), its still-bound signal source (``None`` right after a
    checkpoint restore, until ``bind_source`` re-attaches one — unbound
    sessions skip probes), the probe monitor, and the scheduling metadata it
    re-admits with."""

    record: EvictionRecord
    source: Any
    monitor: DriftMonitor
    meta: SessionMeta
    # service-assigned park stamp (unique per park): the batched probe engine
    # keys its stacked-state cache on it, so an id re-parked with a NEW
    # frozen state can never alias a stale stack
    park_seq: int = -1


@dataclasses.dataclass
class QuarantinedSession:
    """A session pulled from its slot by the health-escalation ladder: its
    last-known-good state (the shadow snapshot it was rolled back to — the
    corrupted state never leaves the kernel), its still-bound source, the
    escalation monitor (offense history + probation streak), and the
    scheduling metadata it re-admits with after probation.  Probed out of
    band like drift-parked sessions, but the probe's HEALTH word (not its
    conv statistic) decides release."""

    record: EvictionRecord
    source: Any
    monitor: HealthMonitor
    meta: SessionMeta


class SeparationService:
    """Continuous-batching front door for a ``SeparatorBank``.

    Sessions (independent separation problems — one user's sensor stream, one
    channel of an EEG array, ...) are admitted into free bank slots and
    evicted when done; ``step`` advances every live session with ONE fused
    bank program per tick.  Slots without fresh data this tick are frozen via
    the bank's active mask, so intermittent streams don't corrupt their state.

        svc = SeparationService(SeparatorBank(ecfg, ocfg, n_streams=64))
        svc.admit("user-a"); svc.admit("user-b")
        outs = svc.step({"user-a": xa, "user-b": xb})   # one fused launch
        final = svc.evict("user-a")                     # SMBGDState handed back

    The tick is zero-copy on a fused bank (``SeparatorBank(fused=True)``):
    mini-batches are staged host-side into ONE preallocated block-aligned
    buffer (``bank.layout``; reused every tick — stale slots are masked
    inactive and the padding region is never written, so no re-zeroing), the
    jitted step donates the persistent padded state back to the kernel
    outputs (accelerator backends), and the padded Y is read to the host once
    at return and cut there into per-session views — steady-state serving
    allocates no device state per tick (the host→device transfer of the
    staging buffer and the device→host read of Y remain).

    Metrics (the backpressure/observability hook): ``metrics`` (a dict, also
    callable as ``svc.metrics()``) reports per-tick TIME-TO-READY latency
    (last/mean + p50/p99/p999 windowed and lifetime — the tick clock blocks
    on the bank's conv leaf every tick, so the numbers are honest under
    asynchronous dispatch; ``SLOPolicy.sync_every`` samples the sync 1-in-k)
    plus deadline-miss counters; ``session_stats`` reports per-session
    tick/sample counters, queue wait, and SERVICE-TIME samples/sec (queue
    wait excluded).  ``block_ticks=True`` additionally synchronizes on the
    full device result before returning — a stronger guarantee than the
    telemetry sync, kept for lockstep callers.

    Lifecycle (see the module docstring for the full state machine): with
    ``max_queue > 0`` a full bank enqueues admissions instead of raising
    (bounded backpressure) — the waiting room is a pluggable
    ``AdmissionScheduler`` (FIFO by default; ``PriorityScheduler`` adds
    strict priorities + per-tenant quotas, ``DeadlineScheduler`` EDF) — and
    with a ``ConvergencePolicy`` the service watches each active session's
    in-bank convergence statistic and auto-evicts converged sessions at the
    end of the tick — their final ``SMBGDState`` (+ stats) lands in
    ``finished`` / ``pop_finished()`` and the freed slot is backfilled from
    the scheduler within the same tick.  ``on_admit(sid, slot)`` /
    ``on_evict(sid, record)`` / ``on_drift(sid, event)`` callbacks observe
    the transitions (backfills, auto-evictions and watchdog firings
    included).

    Drift (``DriftPolicy``): sessions admitted with a bound ``SignalSource``
    get the re-adaptation lifecycle — converged separators are kept hot with
    a μ boost on re-trigger (``mode="boost"``) or parked and probed
    out-of-band, re-admitted warm when their mixing drifts
    (``mode="readmit"``).  ``run_tick()`` is the pull loop that drives it.
    """

    def __init__(
        self,
        bank: SeparatorBank,
        seed: int = 0,
        block_ticks: bool = False,
        policy: Optional[ConvergencePolicy] = None,
        max_queue: int = 0,
        on_admit: Optional[Callable[[Hashable, int], None]] = None,
        on_evict: Optional[Callable[[Hashable, EvictionRecord], None]] = None,
        scheduler: Optional[AdmissionScheduler] = None,
        drift_policy: Optional[DriftPolicy] = None,
        on_drift: Optional[Callable[[Hashable, DriftEvent], None]] = None,
        health_policy: Optional[HealthPolicy] = None,
        on_health: Optional[Callable[[Hashable, HealthEvent], None]] = None,
        slo: Optional[SLOPolicy] = None,
        moment_policy: Optional[MomentPolicy] = None,
        autoscale: Optional[AutoscalePolicy] = None,
    ):
        self.bank = bank
        if autoscale is not None and bank.hyperparams is not None:
            raise ValueError(
                "autoscale needs a resizable bank: explicit per-stream "
                "hyperparams are (S,)-shaped and cannot follow a resize"
            )
        self.autoscale = autoscale
        self.key = jax.random.PRNGKey(seed)
        self.state: BankState = bank.init(self.key)
        self.policy = policy
        if drift_policy is not None and policy is None:
            raise ValueError(
                "drift_policy needs a ConvergencePolicy: the watchdog only "
                "watches sessions that first converged"
            )
        self.drift_policy = drift_policy
        if health_policy is not None and not bank.health_checks:
            raise ValueError(
                "health_policy needs a bank with health_checks=True: the "
                "escalation ladder consumes the in-kernel health word"
            )
        self.health_policy = health_policy
        self.on_health = on_health
        if moment_policy is not None and not bank.moments:
            raise ValueError(
                "moment_policy needs a bank with moments=True: the adaptive-μ "
                "controller consumes the in-kernel [Σy², Σy⁴] telemetry"
            )
        self.moment_policy = moment_policy
        # per-session kurtosis EMAs over the (S, 2) telemetry leaf; N is the
        # LOGICAL Y entry count P·n (padding contributes zeros to both sums)
        self._moments: Optional[MomentController] = (
            MomentController(
                moment_policy,
                count=bank.opt.batch_size * bank.easi.n_components,
            )
            if moment_policy is not None
            else None
        )
        self.scheduler = (
            scheduler if scheduler is not None else AdmissionScheduler(max_queue)
        )
        self.max_queue = self.scheduler.max_queue
        self.on_admit = on_admit
        self.on_evict = on_evict
        self.on_drift = on_drift
        self._free: List[int] = list(range(bank.n_streams - 1, -1, -1))  # pop() → slot 0 first
        self._slot_of: Dict[Hashable, int] = {}
        self._monitors: Dict[Hashable, ConvergenceMonitor] = {}
        self._mixing: Dict[Hashable, jnp.ndarray] = {}
        self._finished: Dict[Hashable, EvictionRecord] = {}
        self._n_evicted = 0
        self._n_auto_evicted = 0
        # scheduling + drift bookkeeping (all host-side)
        self._meta: Dict[Hashable, SessionMeta] = {}  # ACTIVE sessions only
        self._seq = 0  # admission sequence counter (SessionMeta.order)
        self._sources: Dict[Hashable, Any] = {}  # sid → SignalSource
        self._warm: Dict[Hashable, SMBGDState] = {}  # warm-start states pending activation
        self._hot: Dict[Hashable, DriftMonitor] = {}  # converged-hot drift watches
        self._boost_left: Dict[Hashable, int] = {}  # remaining boosted ticks
        # the three μ ladders write DISJOINT per-slot arrays; composition is
        # pinned in _effective_mu_scale (cut WINS while live, boost and the
        # moment controller MULTIPLY) — one ladder expiring can never clobber
        # another's live multiplier (the PR-9 composition bugfix)
        self._boost_scale = np.ones((bank.n_streams,), dtype=np.float32)
        self._cut_scale = np.ones((bank.n_streams,), dtype=np.float32)
        self._ctrl_scale = np.ones((bank.n_streams,), dtype=np.float32)
        self._cut_on = np.zeros((bank.n_streams,), dtype=bool)
        self._parked: Dict[Hashable, ParkedSession] = {}
        self._drift_events: List[DriftEvent] = []
        self._n_drift_events = 0
        self._probe_ticks = 0  # run_tick counter driving parked probes
        self._probe_fn = None  # lazily-jitted virtual-conv probe (sequential)
        self._probe_banks: Dict[int, Tuple[SeparatorBank, Any]] = {}  # width → (bank, jitted probe)
        self._probe_stacks: Dict[Tuple, BankState] = {}  # chunk stamp → stacked frozen states
        self._park_seq = 0  # monotone park stamp (probe stack-cache keys)
        self._n_probes = 0  # parked sessions probed (any engine)
        self._n_probe_launches = 0  # probe dispatches (the O(parked/batch) win)
        self._restored_positions: Dict[Hashable, int] = {}  # from lifecycle snapshots
        # fault containment (HealthPolicy): escalation monitors, μ-cut
        # countdowns, the quarantine pool, and the per-slot last-known-good
        # shadow snapshot the rollback path restores from
        self._health_mon: Dict[Hashable, HealthMonitor] = {}
        self._cut_left: Dict[Hashable, int] = {}  # remaining μ-cut ticks
        self._quarantined: Dict[Hashable, QuarantinedSession] = {}
        self._shadow: Optional[BankState] = (
            self.state if health_policy is not None else None
        )
        self._health_events: List[HealthEvent] = []
        self._n_health_events = 0
        self._n_rollbacks = 0
        self._n_diverged = 0
        self._n_degraded_ticks = 0  # session-ticks lost to source faults
        self._n_source_retries = 0  # ResilientSource retries folded per tick
        self._last_fault: Dict[Hashable, str] = {}  # sid → last source error
        self._quar_ticks = 0  # run_tick counter driving quarantine probes
        # μ boost (drift), μ cut (health) and the moment controller ride
        # per-stream hyperparameter rows as TRACED operands — only those
        # modes pay for the 4-argument step flavour
        self._hp_step = (
            (drift_policy is not None and drift_policy.mode == "boost")
            or health_policy is not None
            or moment_policy is not None
        )
        if self._hp_step and bank.algorithm != "smbgd_batched":
            raise ValueError(
                "DriftPolicy(mode='boost'), HealthPolicy and MomentPolicy "
                "need per-stream hyperparams, which require "
                "algorithm='smbgd_batched'"
            )
        self._base_hp: Optional[BankHyperparams] = (
            bank._bank_hyperparams() if self._hp_step else None
        )
        # donated state on accelerators: the runtime reuses the old state
        # buffers for the new state — the steady-state tick performs no state
        # allocation (CPU backend opts out; see SeparatorBank.make_step)
        self._step = bank.make_step(with_hyperparams=self._hp_step)
        # elastic machinery: the jitted step is cached per (width, geometry)
        # so an oscillating autoscaler compiles each ladder width once (see
        # prewarm to take even the first compile off the serving path)
        self._step_cache: Dict[Tuple, Any] = {self._step_key(bank): self._step}
        self._n_grows = 0
        self._n_shrinks = 0
        self._n_compactions = 0
        self._resize_history: List[Dict[str, Any]] = []
        self._elastic_ticks = 0  # run_tick counter driving the cooldown
        self._last_resize_tick: Optional[int] = None
        # one staging buffer for every tick: jnp.asarray copies host→device,
        # so the numpy side is free to be overwritten next tick
        if bank.fused:
            lay = bank.layout
            stage_shape = (bank.n_streams, lay.P_pad, lay.m_pad)
        else:
            stage_shape = (bank.n_streams, bank.opt.batch_size, bank.easi.n_features)
        self._stage = np.zeros(stage_shape, dtype=np.float32)
        self.block_ticks = block_ticks
        self._stats: Dict[Hashable, SessionStats] = {}
        self._admit_time: Dict[Hashable, float] = {}  # queue-wait stamps
        self._n_ticks = 0
        self._total_samples = 0
        self._total_tick_s = 0.0
        self._last_tick_s = float("nan")
        # latency SLO machinery (serve.slo): telemetry is always on — the
        # default policy has no deadline budget, so only the time-to-ready
        # sketch runs; a budgeted policy arms misses / shedding / gating
        self.slo = slo if slo is not None else SLOPolicy()
        self._reset_slo()

    def _reset_slo(self) -> None:
        """(Re-)arm the SLO telemetry state — shared by ``__init__`` and
        ``restore`` (serving metrics describe the current epoch only)."""
        pol = self.slo
        self._sketch = LatencySketch(window=pol.window)
        self._timer = TickTimer(sync_every=pol.sync_every)
        self._deadline_mon: Dict[Hashable, DeadlineMonitor] = {}
        self._recent_misses: collections.deque = collections.deque(
            maxlen=pol.miss_window
        )
        self._n_deadline_misses = 0
        self._n_timed_ticks = 0  # ticks with a time-to-ready measurement
        self._timed_samples = 0  # samples served on timed ticks
        self._n_empty_ticks = 0  # run_ticks with no data batch (probe-only)
        self._n_shed = 0
        self._slo_events: List[SLOEvent] = []
        self._n_slo_events = 0
        self._last_shed_tick = -(10**9)
        self._last_probe_s = float("nan")
        # run_tick defers the tick's latency record past the probe phase so
        # probe work is billed to the tick that ran it (see _finish_tick)
        self._pending_tick: Optional[Tuple[List[Hashable], bool, int]] = None
        self._defer_slo = False

    @property
    def n_active(self) -> int:
        return len(self._slot_of)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_queued(self) -> int:
        return len(self.scheduler)

    @property
    def queued(self) -> Tuple[Hashable, ...]:
        """Waiting sessions in the scheduler's pop order (head first)."""
        return self.scheduler.ids()

    @property
    def finished(self) -> Dict[Hashable, EvictionRecord]:
        """Retained eviction records (read-only view; drain with
        ``pop_finished``)."""
        return dict(self._finished)

    def pop_finished(self) -> Dict[Hashable, EvictionRecord]:
        """Drain and return the eviction records accumulated so far."""
        out, self._finished = self._finished, {}
        return out

    @property
    def parked(self) -> Dict[Hashable, ParkedSession]:
        """Sessions under out-of-band drift watch (``mode="readmit"``)."""
        return dict(self._parked)

    @property
    def drift_events(self) -> List[DriftEvent]:
        """Watchdog firings so far (read-only view; drain with
        ``pop_drift_events``)."""
        return list(self._drift_events)

    def pop_drift_events(self) -> List[DriftEvent]:
        out, self._drift_events = self._drift_events, []
        return out

    @property
    def quarantined(self) -> Dict[Hashable, QuarantinedSession]:
        """Sessions pulled from their slots by the health-escalation ladder,
        probed out of band until probation clears (or they diverge)."""
        return dict(self._quarantined)

    @property
    def health_events(self) -> List[HealthEvent]:
        """Containment actions so far (rollback / quarantine / release /
        diverge; read-only view; drain with ``pop_health_events``)."""
        return list(self._health_events)

    def pop_health_events(self) -> List[HealthEvent]:
        out, self._health_events = self._health_events, []
        return out

    def status(self, session_id: Hashable) -> str:
        """Lifecycle state: ``"active" | "converged" | "queued" | "parked" |
        "quarantined" | "finished" | "unknown"`` (``"converged"`` = hot in
        its slot under drift watch; ``"quarantined"`` = pulled from its slot
        by the health ladder, probed out of band until probation clears)."""
        if session_id in self._slot_of:
            return "converged" if session_id in self._hot else "active"
        if session_id in self.scheduler:
            return "queued"
        if session_id in self._parked:
            return "parked"
        if session_id in self._quarantined:
            return "quarantined"
        if session_id in self._finished:
            return "finished"
        return "unknown"

    def set_mixing(self, session_id: Hashable, A: jnp.ndarray) -> None:
        """Register the session's ground-truth mixing matrix ``A (m, n)`` so
        ``ConvergencePolicy.amari_threshold`` can confirm convergence on the
        global system ``B·A`` (benchmarks / synthetic workloads; production
        sessions without ground truth simply never register one).  Sessions
        whose bound source exposes ``true_mixing()`` need no registration —
        the confirmation tracks the source's live mixing instead."""
        if session_id not in self._slot_of and session_id not in self.scheduler:
            raise KeyError(f"session {session_id!r} is neither active nor queued")
        self._mixing[session_id] = jnp.asarray(A)

    def bind_source(self, session_id: Hashable, source, seek: bool = True) -> None:
        """Attach (or replace) a session's ``SignalSource`` — the feed
        ``run_tick`` pulls from (or, for a PARKED session, the feed the drift
        watchdog probes).  After ``restore``, re-bind sources here: the
        cursor positions recorded in the lifecycle snapshot are re-applied
        (``seek=True``, sources exposing ``seek``) so the feed resumes exactly
        where the checkpointed one stopped — restored parked sessions stay
        parked (and un-probeable) until their source is re-bound."""
        if (
            session_id not in self._slot_of
            and session_id not in self.scheduler
            and session_id not in self._parked
            and session_id not in self._quarantined
        ):
            raise KeyError(
                f"session {session_id!r} is neither active nor queued nor "
                f"parked nor quarantined"
            )
        pos = self._restored_positions.pop(session_id, None) if seek else None
        if pos is not None and hasattr(source, "seek"):
            source.seek(pos)
        if session_id in self._parked:
            self._parked[session_id].source = source
            return
        if session_id in self._quarantined:
            self._quarantined[session_id].source = source
            return
        self._sources[session_id] = source

    # -- metrics -----------------------------------------------------------
    @property
    def deadline_miss_rate(self) -> float:
        """Windowed deadline-miss rate: misses over the last ``miss_window``
        timed ticks (0.0 until a budgeted tick has been timed)."""
        if not self._recent_misses:
            return 0.0
        return sum(self._recent_misses) / len(self._recent_misses)

    @property
    def metrics(self) -> "MetricsView":
        """Service-level serving counters (one dict, cheap to scrape; also
        callable — ``svc.metrics()`` works identically).

        Latency keys measure TIME-TO-READY (the tick clock stops after a
        ``block_until_ready`` on the bank's conv leaf — see ``serve.slo``),
        so they are honest on asynchronous backends regardless of
        ``block_ticks``.  ``p50/p99/p999_tick_s`` are exact over the sketch
        window; the ``*_life`` twins are bounded-memory lifetime quantiles.
        ``mean_tick_s``/``samples_per_s`` cover timed DATA ticks;
        probe-only run_ticks count in ``n_empty_ticks`` and land in the
        quantile sketch (they spend wall-clock against the deadline budget
        like any tick) but not in the data-tick means."""
        sk = self._sketch
        return MetricsView({
            "n_active": float(self.n_active),
            "n_free": float(self.n_free),
            "n_queued": float(self.n_queued),
            "n_streams": float(self.bank.n_streams),
            "n_grows": float(self._n_grows),
            "n_shrinks": float(self._n_shrinks),
            "n_compactions": float(self._n_compactions),
            "bank_utilization": self.n_active / self.bank.n_streams,
            "n_hot": float(len(self._hot)),
            "n_parked": float(len(self._parked)),
            "n_drift_events": float(self._n_drift_events),
            "n_probes": float(self._n_probes),
            "n_probe_launches": float(self._n_probe_launches),
            "n_evicted": float(self._n_evicted),
            "n_auto_evicted": float(self._n_auto_evicted),
            "n_quarantined": float(len(self._quarantined)),
            "n_rollbacks": float(self._n_rollbacks),
            "n_diverged": float(self._n_diverged),
            "n_degraded_ticks": float(self._n_degraded_ticks),
            "n_source_retries": float(self._n_source_retries),
            "n_health_events": float(self._n_health_events),
            "n_ticks": float(self._n_ticks),
            "n_empty_ticks": float(self._n_empty_ticks),
            "n_timed_ticks": float(self._n_timed_ticks),
            "total_samples": float(self._total_samples),
            "last_tick_s": self._last_tick_s,
            "last_probe_s": self._last_probe_s,
            "mean_tick_s": self._total_tick_s / self._n_timed_ticks
            if self._n_timed_ticks
            else float("nan"),
            "samples_per_s": self._timed_samples / self._total_tick_s
            if self._total_tick_s > 0
            else float("nan"),
            "n_deadline_misses": float(self._n_deadline_misses),
            "deadline_miss_rate": self.deadline_miss_rate,
            "n_shed": float(self._n_shed),
            "n_slo_events": float(self._n_slo_events),
            **sk.summary(),
        })

    def session_stats(self, session_id: Hashable) -> Dict[str, float]:
        """Per-session counters: ticks, samples, service-time samples/sec,
        seconds spent waiting in the admission queue — plus the convergence
        monitor (smoothed stat, consecutive below-count) when a policy is
        attached and the deadline record (lifetime misses, window-resident
        misses) once the session has seen a budgeted tick."""
        st = self._stats[session_id]
        out = {
            "ticks": float(st.ticks),
            "samples": float(st.samples),
            "samples_per_s": st.samples_per_s(),
            "queue_wait_s": st.queue_wait_s(),
        }
        mon = self._monitors.get(session_id)
        if mon is not None:
            out["conv_stat"] = mon.stat
            out["conv_below"] = float(mon.below)
        dmon = self._deadline_mon.get(session_id)
        if dmon is not None:
            out["deadline_misses"] = float(dmon.misses)
            out["deadline_misses_recent"] = float(len(dmon.recent))
        if self._moments is not None:
            out["mu_ctrl"] = float(self._moments.scale(session_id))
            est = self._moments.estimate(session_id)
            if est is not None:
                out["kurtosis_fast"], out["kurtosis_slow"] = est
        return out

    @property
    def slo_events(self) -> List[SLOEvent]:
        """Load-control actions so far (shed/gate; read-only view — drain
        with ``pop_slo_events``).  Per-tick misses are counters, not events."""
        return list(self._slo_events)

    def pop_slo_events(self) -> List[SLOEvent]:
        out, self._slo_events = self._slo_events, []
        return out

    def _sched_ctx(self) -> SchedulerContext:
        return SchedulerContext(
            tick=self._n_ticks,
            active=dict(self._meta),
            deadline_miss_rate=self.deadline_miss_rate,
        )

    def admit(
        self,
        session_id: Hashable,
        source=None,
        state: Optional[SMBGDState] = None,
        tenant: Optional[str] = None,
        priority: float = 0.0,
        deadline: Optional[float] = None,
    ) -> Optional[int]:
        """Admit ``session_id``: into a free slot (returns the slot index), or
        — when the bank is full and ``max_queue`` allows — into the
        scheduler's waiting room (returns ``None``; the session activates
        when a slot frees and the scheduler picks it).  Raises ``ValueError``
        for duplicate ids and ``RuntimeError`` when bank AND queue are full
        (backpressure: the caller must shed load or retry later).

        ``source`` binds a ``SignalSource`` for ``run_tick`` ingestion (and
        the drift watchdog).  ``state`` warm-starts the session from an
        existing ``SMBGDState`` instead of a fresh init (the re-admission
        path).  ``tenant``/``priority``/``deadline`` are scheduling metadata
        (``SessionMeta``) consumed by the configured ``AdmissionScheduler``.

        When every slot is held but some by HOT (converged, drift-watched)
        sessions, the least-drifted hot session is preempted to make room —
        keeping separators warm never starves new work."""
        if session_id in self._slot_of or session_id in self.scheduler:
            raise ValueError(f"session {session_id!r} already admitted")
        if session_id in self._parked:
            raise ValueError(
                f"session {session_id!r} is parked under drift watch; "
                f"evict it first to force a fresh admission"
            )
        if session_id in self._quarantined:
            raise ValueError(
                f"session {session_id!r} is quarantined under health watch; "
                f"evict it first to force a fresh admission"
            )
        meta = SessionMeta(
            tenant=tenant, priority=float(priority), deadline=deadline,
            order=self._seq,
        )
        self._seq += 1
        # queue-wait clock starts NOW — _activate stamps the other end
        self._admit_time[session_id] = time.perf_counter()
        if source is not None:
            self._sources[session_id] = source
        if state is not None:
            self._warm[session_id] = state
        if not self._free and self._hot:
            ctx = self._sched_ctx()
            # preempt a warm separator only for work that can actually take
            # the slot — a quota-gated admission must not cost anyone warmth
            if self.scheduler.can_activate(meta, ctx) or self.scheduler.has_eligible(ctx):
                self._preempt_hot()
        try:
            if (
                self._free
                and not len(self.scheduler)
                and not self._slo_gated()
                and self.scheduler.can_activate(meta, self._sched_ctx())
            ):
                self._meta[session_id] = meta
                return self._activate(session_id)
            if not self._free and self.scheduler.full:
                raise RuntimeError(
                    f"bank full ({self.bank.n_streams} slots, "
                    f"{len(self.scheduler)}/{self.max_queue} queued); evict "
                    f"before admitting"
                )
            # free slots may exist while sessions wait (tenant at quota /
            # non-empty queue / SLO admission gate): enqueue and let the
            # scheduler pick when the gate reopens
            self.scheduler.push(session_id, meta)
        except (RuntimeError, ValueError):
            self._sources.pop(session_id, None)
            self._warm.pop(session_id, None)
            self._admit_time.pop(session_id, None)
            raise
        self._backfill()
        return self._slot_of.get(session_id)

    def _activate(self, session_id: Hashable) -> int:
        """QUEUED/new → ACTIVE: claim a free slot and initialize it (the
        session's device state is born here, so the γ step-0 gate applies at
        its first *served* tick).  Warm-start admissions instead write their
        carried ``SMBGDState`` into the slot (step counter and all)."""
        slot = self._free.pop()
        warm = self._warm.pop(session_id, None)
        if warm is not None:
            self.state = self.bank.set_slot(self.state, slot, warm)
        else:
            self.key, k = jax.random.split(self.key)
            self.state = self.bank.init_slot(self.state, slot, k)
        self._slot_of[session_id] = slot
        self._meta.setdefault(session_id, SessionMeta(order=self._seq))
        self._reset_mu(slot)
        if self._moments is not None:
            # a slot's new occupant (fresh OR warm re-admission) starts with
            # no kurtosis reference — the EMAs re-seed on its first tick
            self._moments.reset(session_id)
        now = time.perf_counter()
        self._stats[session_id] = SessionStats(
            admitted_at=self._admit_time.pop(session_id, now),
            activated_at=now,
        )
        self._monitors[session_id] = ConvergenceMonitor()
        if self._shadow is not None:
            # seed the slot's shadow from the state it was just born with —
            # a first-offense rollback must restore THIS session's state,
            # never the slot's previous occupant's
            self._shadow = self.bank.copy_slot(self._shadow, self.state, slot)
        if self.health_policy is not None:
            # quarantine releases re-enter with their ladder memory intact
            # (setdefault keeps the monitor _release_quarantine pre-seeded)
            self._health_mon.setdefault(session_id, HealthMonitor())
        if self.on_admit is not None:
            self.on_admit(session_id, slot)
        return slot

    def _slo_gated(self) -> bool:
        """Is the SLO admission gate closed?  True while
        ``SLOPolicy(gate_admissions=True)`` and the windowed deadline-miss
        rate is over ``max_miss_rate`` — free slots stay free (and direct
        admissions queue) until the window recovers, so shedding/gating can
        actually reduce load instead of instantly re-filling it."""
        return (
            self.slo.gate_admissions
            and self.slo.deadline_budget_s is not None
            and self.deadline_miss_rate > self.slo.max_miss_rate
        )

    def _backfill(self) -> None:
        """Fill free slots from the scheduler until it runs out of eligible
        sessions (``pop`` returning ``None`` = everyone gated, e.g. tenants
        at quota — the slot stays free and we retry at the next release or
        ``run_tick``).  The SLO admission gate holds backfills entirely
        while the service is over its deadline-miss ceiling (one ``"gate"``
        event per closed-gate attempt with waiting work)."""
        if self._slo_gated():
            if self._free and len(self.scheduler):
                self._record_slo(
                    SLOEvent(
                        session_id=None,
                        tick=self._n_ticks,
                        tick_s=self._last_tick_s,
                        budget_s=float(self.slo.deadline_budget_s),
                        action="gate",
                        miss_rate=self.deadline_miss_rate,
                    )
                )
            return
        while self._free and len(self.scheduler):
            popped = self.scheduler.pop(self._sched_ctx())
            if popped is None:
                return
            sid, meta = popped
            self._meta[sid] = meta
            self._activate(sid)

    def _preempt_hot(self) -> None:
        """Evict the least-drifted HOT session to free a slot for waiting
        work (reason ``"preempted"`` — its record lands in ``finished``)."""
        conv = np.asarray(self.state.conv)
        victim = min(
            self._hot, key=lambda sid: float(conv[self._slot_of[sid]])
        )
        self._release(victim, reason="preempted")

    def evict(self, session_id: Hashable) -> Optional[SMBGDState]:
        """ACTIVE → EVICTED: release the slot and return the session's final
        single-stream state (B is its learned separation matrix), backfilling
        the freed slot from the scheduler.  A QUEUED session is simply
        dequeued (returns ``None`` — it never had device state); a PARKED
        session is taken off drift watch (its frozen state is returned and
        its record moves to ``finished``).  An unknown id raises ``KeyError``
        without touching the free list."""
        if session_id in self._slot_of:
            return self._release(session_id, reason="evicted").state
        if self.scheduler.remove(session_id):  # cancellation of a queued session
            self._mixing.pop(session_id, None)
            self._sources.pop(session_id, None)
            self._warm.pop(session_id, None)
            self._admit_time.pop(session_id, None)
            return None
        if session_id in self._parked:
            ps = self._parked.pop(session_id)
            self._finished[session_id] = ps.record
            return ps.record.state
        if session_id in self._quarantined:
            qs = self._quarantined.pop(session_id)
            self._health_mon.pop(session_id, None)
            self._finished[session_id] = qs.record
            return qs.record.state
        raise KeyError(
            f"session {session_id!r} is neither active nor queued (nor "
            f"parked nor quarantined)"
        )

    def _release(
        self,
        session_id: Hashable,
        reason: str,
        health: Optional[HealthMonitor] = None,
    ) -> EvictionRecord:
        """ACTIVE → EVICTED edge shared by manual ``evict``, the policy's
        auto-eviction, hot-session preemption, source exhaustion, the
        readmit-mode park and the health ladder's divergence eviction: slice
        the final state out of the bank, free the slot, record the eviction,
        and backfill from the scheduler — all before the next tick touches
        the bank."""
        slot = self._slot_of.pop(session_id)
        record = EvictionRecord(
            state=self.bank.slot_state(self.state, slot),
            stats=self._stats.pop(session_id),
            monitor=self._monitors.pop(session_id, None),
            reason=reason,
            tick=self._n_ticks,
            health=health,
        )
        self._mixing.pop(session_id, None)
        meta = self._meta.pop(session_id, None)
        self._hot.pop(session_id, None)
        self._boost_left.pop(session_id, None)
        self._cut_left.pop(session_id, None)
        self._health_mon.pop(session_id, None)
        self._deadline_mon.pop(session_id, None)
        self._admit_time.pop(session_id, None)
        self._reset_mu(slot)
        if self._moments is not None:
            self._moments.forget(session_id)
        self._free.append(slot)
        self._n_evicted += 1
        if reason == "converged":
            self._n_auto_evicted += 1
        source = self._sources.pop(session_id, None)
        if (
            reason == "converged"
            and source is not None
            and self.drift_policy is not None
            and self.drift_policy.mode == "readmit"
        ):
            # PARK instead of finishing: the frozen separator + its source
            # stay under out-of-band drift watch (see _probe_parked)
            self._parked[session_id] = ParkedSession(
                record=record,
                source=source,
                monitor=DriftMonitor(),
                meta=meta if meta is not None else SessionMeta(),
            )
        else:
            self._finished[session_id] = record
        if self.on_evict is not None:
            self.on_evict(session_id, record)
        # same-tick backfill: the freed slot was appended last, so the
        # scheduler's pick lands exactly in the slot that just opened
        self._backfill()
        return record

    def step(self, batches: Dict[Hashable, jnp.ndarray]) -> Dict[Hashable, np.ndarray]:
        """Advance every session that sent data this tick.

        ``batches`` maps session_id → ``(P, m)`` mini-batch.  Sessions without
        data (and free slots) are masked inactive — state untouched.  Returns
        session_id → separated ``(P, n)`` outputs from one fused bank step,
        as host ``np.ndarray`` views of one fresh ``(S, P, n)`` array.

        On a fused bank the staging buffer is allocated block-aligned
        (``(S, P_pad, m_pad)``) so the jitted step consumes it with no
        re-padding copy; the step's ``Y`` is read to the host once and cut
        there (``SeparatorBank.slot_outputs``).  That read waits for the
        step, so every tick that returns outputs syncs, whatever
        ``SLOPolicy.sync_every`` says.
        """
        if not batches:
            return {}
        with _span("serve.step"):
            P = self.bank.opt.batch_size
            with _span("serve.stage"):
                X, active = self._stage_batches(batches)
                # time-to-ready tick clock: JAX dispatches
                # asynchronously, so stopping at dispatch measured nothing on a
                # real accelerator.  The timer blocks on the bank's conv leaf —
                # a tiny (S,) vector whose readiness implies the whole bank
                # program retired — every tick (or 1-in-k under
                # SLOPolicy.sync_every); block_ticks=True keeps its stronger
                # full-result sync and is timed as-is.
                timer = self._timer
                timer.start()
                with _span("serve.h2d", bytes=X.nbytes + active.nbytes):
                    X_dev, active_dev = jnp.asarray(X), jnp.asarray(active)
                hp = (self._current_hp(),) if self._hp_step else ()
            # the megakernel's grid over the streams (none on unfused banks)
            block_s = self.bank.resolved_block_s
            grid = (
                {"block_s": block_s, "stream_blocks": self.bank.n_streams // block_s}
                if block_s
                else {}
            )
            with _span("serve.launch", **grid):
                self.state, Y = self._step(self.state, X_dev, active_dev, *hp)
            # the batch's device copy goes once the step holds it: kept to
            # the end of the tick, it would add its size to peak memory
            del X_dev, active_dev
            with _span("serve.ready"):
                if self.block_ticks:
                    jax.block_until_ready((self.state, Y))
                    dt, timed = timer.stop(already_synced=True)
                else:
                    dt, timed = timer.stop(sync_leaf=self.state.conv)
            self._n_ticks += 1
            self._total_samples += P * len(batches)
            for sid in batches:
                st = self._stats[sid]
                st.ticks += 1
                st.samples += P
            # cut outputs BEFORE any auto-eviction mutates the slot map:
            # evicted sessions still receive this tick's separated output.
            # One host read of the whole Y, no dispatch and no program, so
            # neither the served count nor the width compiles anything
            with _span(
                "serve.outputs", sessions=len(batches),
                width=self.bank.n_streams, bytes=Y.nbytes,
            ):
                ys = self.bank.slot_outputs(
                    Y, [self._slot_of[sid] for sid in batches]
                )
                out = dict(zip(batches, ys))
            served = list(batches.keys())
            if self._moments is not None:
                # one (S, 2) host read per tick: fold this tick's raw moments
                # into each served session's kurtosis EMAs and refresh its μ
                # multiplier (consumed by _current_hp next tick — traced
                # operand, no retrace)
                with _span("serve.moments"):
                    mom = np.asarray(self.state.moments)
                    for sid in served:
                        slot = self._slot_of[sid]
                        self._ctrl_scale[slot] = self._moments.observe(
                            sid, float(mom[slot, 0]), float(mom[slot, 1])
                        )
            if self._defer_slo:
                # called from run_tick: the tick's latency record is finished
                # AFTER the probe phase, so probe time is billed to this tick
                self._pending_tick = (served, timed, P * len(batches))
            else:
                self._finish_tick(dt, served, timed, P * len(batches))
            if self.health_policy is not None:
                # containment first: offenders are rolled back / quarantined /
                # diverged and drop out of this tick's convergence sweep (their
                # conv statistic was never committed anyway)
                with _span("serve.health"):
                    served = self._apply_health(served)
            if self.policy is not None:
                with _span("serve.policy"):
                    self._apply_policy(served)
            return out

    def _stage_batches(
        self, batches: Dict[Hashable, jnp.ndarray]
    ) -> Tuple[np.ndarray, np.ndarray]:
        """Check every batch and copy it into the staging buffer; returns
        the buffer and the ``(S,)`` active mask."""
        unknown = set(batches) - set(self._slot_of)
        if unknown:
            # never silently drop data: queued/parked sessions hold no slot
            # (their batch would corrupt nothing but vanish), unknown ids are
            # caller bugs — name each class so the fix is obvious
            queued = sorted(str(s) for s in unknown if s in self.scheduler)
            parked = sorted(str(s) for s in unknown if s in self._parked)
            quar = sorted(str(s) for s in unknown if s in self._quarantined)
            msg = f"sessions not active: {sorted(map(str, unknown))}"
            if queued:
                msg += (
                    f"; queued with no slot yet (wait for activation or raise "
                    f"capacity): {queued}"
                )
            if parked:
                msg += f"; parked under drift watch (evict to detach): {parked}"
            if quar:
                msg += (
                    f"; quarantined under health watch (awaiting probation): "
                    f"{quar}"
                )
            raise KeyError(msg)
        S = self.bank.n_streams
        P = self.bank.opt.batch_size
        m = self.bank.easi.n_features
        # reused staging buffer (block-aligned on fused banks): stale data in
        # slots not written this tick only feeds masked-out streams, and the
        # padding region is never written, so it stays zero from __init__
        X = self._stage
        active = np.zeros((S,), dtype=bool)
        for sid, xb in batches.items():
            xb = np.asarray(xb, dtype=np.float32)
            if xb.shape != (P, m):  # don't let numpy broadcast a wrong batch
                raise ValueError(
                    f"session {sid!r}: batch shape {xb.shape} != required "
                    f"(P={P}, m={m})"
                )
            slot = self._slot_of[sid]
            X[slot, :P, :m] = xb
            active[slot] = True
        return X, active

    def _finish_tick(
        self, dt: float, served: List[Hashable], timed: bool, samples: int
    ) -> None:
        """Close out one data tick's latency record.  Sampled-out ticks
        (``timed=False`` — SLOPolicy.sync_every > 1) stopped the clock at
        dispatch: they carry no latency information and are dropped entirely
        rather than recorded as fiction."""
        if not timed:
            return
        self._last_tick_s = dt
        self._total_tick_s += dt
        self._n_timed_ticks += 1
        self._timed_samples += samples
        self._record_latency(dt, served)

    def _record_slo(self, event: SLOEvent) -> None:
        self._slo_events.append(event)
        self._n_slo_events += 1

    def _record_latency(self, dt: float, served: List[Hashable]) -> None:
        """Fold one timed latency into the sketch and — under a budget —
        the deadline machinery: the service miss window, every served
        session's ``DeadlineMonitor``, and (opted in) the shed decision.
        The shed victim is the still-active session with the most
        window-resident misses (ties → lower priority, younger admission):
        the session most consistently present when the budget blows is the
        best guess at the expensive one."""
        self._sketch.add(dt)
        pol = self.slo
        budget = pol.deadline_budget_s
        if budget is None:
            return
        missed = dt > budget
        if missed:
            self._n_deadline_misses += 1
        self._recent_misses.append(1 if missed else 0)
        victim, victim_rank = None, None
        for sid in served:
            mon = self._deadline_mon.setdefault(sid, DeadlineMonitor())
            count = mon.record(self._n_ticks, missed, pol)
            if sid not in self._slot_of:
                continue  # evicted/parked by this tick's sweeps
            meta = self._meta.get(sid) or SessionMeta()
            rank = (-count, meta.priority, -meta.order)
            if victim_rank is None or rank < victim_rank:
                victim, victim_rank = sid, rank
        if not missed:
            return
        rate = self.deadline_miss_rate
        if (
            pol.shed
            and rate > pol.max_miss_rate
            and victim is not None
            and self.n_active > 1
            and self._n_ticks - self._last_shed_tick >= pol.shed_cooldown
        ):
            self._last_shed_tick = self._n_ticks
            self._n_shed += 1
            self._release(victim, reason="shed")
            self._record_slo(
                SLOEvent(
                    session_id=victim,
                    tick=self._n_ticks,
                    tick_s=dt,
                    budget_s=float(budget),
                    action="shed",
                    miss_rate=rate,
                )
            )

    def _apply_policy(self, served) -> None:
        """End-of-tick convergence + drift sweep: update each served session's
        monitor from the bank's in-step statistic, auto-evict (or park / keep
        hot) the converged ones, fire the drift watchdog for hot sessions,
        and backfill freed slots from the scheduler (same tick).

        One (S,)-float device read per tick — the statistic itself was folded
        inside the bank step (in-register on the fused path)."""
        pol = self.policy
        dpol = self.drift_policy
        conv = np.asarray(self.state.conv)  # (S,) f32
        evict_now: List[Hashable] = []
        for sid in served:
            slot = self._slot_of[sid]
            x = float(conv[slot])
            if sid in self._hot:
                # converged-hot: the DRIFT watchdog owns this session now
                if self._hot[sid].update(x, dpol):
                    self._fire_boost(sid, slot)
                continue
            if sid in self._boost_left:
                # re-adapting under μ boost: count the boost down (expiry
                # releases only the BOOST ladder — a live μ-cut or controller
                # scale on the same slot is untouched)
                self._boost_left[sid] -= 1
                if self._boost_left[sid] <= 0:
                    del self._boost_left[sid]
                    self._boost_scale[slot] = 1.0
            mon = self._monitors[sid]
            mon.update(x, pol)
            if mon.ticks < pol.min_ticks or mon.below < pol.patience:
                continue
            if pol.amari_threshold is not None:
                A = self._mixing.get(sid)
                if A is None and sid in self._sources:
                    # drifting synthetic sources report their live mixing
                    A = sources_lib.true_mixing_of(self._sources[sid])
                if A is not None:
                    B = self.bank.slot_state(self.state, slot).B
                    pi = float(
                        metrics_lib.amari_index(
                            metrics_lib.global_system(B, jnp.asarray(A))
                        )
                    )
                    if pi > pol.amari_threshold:
                        continue  # blind stat dipped early — not separated yet
            if (
                dpol is not None
                and dpol.mode == "boost"
                and sid in self._sources
                and not self.scheduler.has_eligible(self._sched_ctx())
            ):
                # keep HOT: hold the slot, keep serving, watch for drift
                # (capacity pressure wins over warmth — but only a waiting
                # session that could actually take the slot counts)
                self._hot[sid] = DriftMonitor()
                if sid in self._boost_left:
                    # re-converged before the boost ran out: the boost did
                    # its job — μ returns to base for the hot watch
                    del self._boost_left[sid]
                    self._boost_scale[slot] = 1.0
                continue
            evict_now.append(sid)
        for sid in evict_now:
            self._release(sid, reason="converged")

    # -- drift watchdog ----------------------------------------------------
    def _record_drift(self, event: DriftEvent) -> None:
        self._drift_events.append(event)
        self._n_drift_events += 1
        if self.on_drift is not None:
            self.on_drift(event.session_id, event)

    def _fire_boost(self, session_id: Hashable, slot: int) -> None:
        """HOT → ACTIVE: the watchdog saw the conv statistic rise — boost the
        session's per-stream μ and make it re-earn convergence."""
        mon = self._hot.pop(session_id)
        dpol = self.drift_policy
        self._monitors[session_id] = ConvergenceMonitor()
        if dpol.boost != 1.0:
            self._boost_scale[slot] = dpol.boost
            self._boost_left[session_id] = dpol.boost_ticks
        self._record_drift(
            DriftEvent(
                session_id=session_id,
                tick=self._n_ticks,
                stat=mon.stat,
                action="boost",
                slot=slot,
            )
        )

    def _reset_mu(self, slot: int) -> None:
        """Clear every μ ladder's multiplier for ``slot`` (slot turnover:
        activation, release, quarantine)."""
        self._boost_scale[slot] = 1.0
        self._cut_scale[slot] = 1.0
        self._ctrl_scale[slot] = 1.0
        self._cut_on[slot] = False

    def _effective_mu_scale(self) -> np.ndarray:
        """The pinned composition of the three μ writers, per slot: a live
        HealthPolicy cut WINS outright (containment beats adaptation — never
        boost a separator that just rolled back), otherwise the DriftPolicy
        boost and the moment controller MULTIPLY."""
        return np.where(
            self._cut_on, self._cut_scale, self._boost_scale * self._ctrl_scale
        ).astype(np.float32)

    def _current_hp(self) -> BankHyperparams:
        """Per-stream hyperparameter rows for THIS tick: the bank's base
        (μ, β, γ) with the composed μ multipliers folded in
        (``_effective_mu_scale`` — cut wins, boost × controller multiply).
        Traced operands — varying them tick to tick costs no retrace."""
        hp = self._base_hp
        if self._boost_left or self._cut_left or self._moments is not None:
            return BankHyperparams(
                mu=hp.mu * jnp.asarray(self._effective_mu_scale()),
                beta=hp.beta,
                gamma=hp.gamma,
            )
        return hp

    # -- fault containment (HealthPolicy) ----------------------------------
    def _record_health(self, event: HealthEvent) -> None:
        self._health_events.append(event)
        self._n_health_events += 1
        if self.on_health is not None:
            self.on_health(event.session_id, event)

    def _apply_health(self, served: List[Hashable]) -> List[Hashable]:
        """End-of-tick containment sweep: read the (S,) health words the
        kernel folded into this tick, walk the escalation ladder for every
        offender (rollback + μ cut → quarantine → evict ``"diverged"``), and
        refresh the copy-on-healthy shadow every ``shadow_every`` ticks.
        Returns the served sessions still active and healthy — the set the
        convergence sweep may judge this tick.

        The kernel already refused the offenders' commits (pre-tick state in
        the slot), so the rollback's job is rewinding the *trajectory*: the
        pre-tick state may itself be mid-divergence, and the shadow is the
        last state that survived ``shadow_every`` ticks of health checks."""
        hpol = self.health_policy
        words = np.asarray(self.state.health)  # (S,) int32, this tick's verdict
        healthy: List[Hashable] = []
        for sid in served:
            slot = self._slot_of.get(sid)
            if slot is None:
                continue
            word = int(words[slot])
            mon = self._health_mon.setdefault(sid, HealthMonitor())
            if word == 0:
                mon.healthy_streak += 1
                if sid in self._cut_left:
                    self._cut_left[sid] -= 1
                    if self._cut_left[sid] <= 0:
                        del self._cut_left[sid]
                        # the cut expiring hands μ BACK to boost × controller
                        # (their multipliers kept ticking underneath)
                        self._cut_scale[slot] = 1.0
                        self._cut_on[slot] = False
                healthy.append(sid)
                continue
            escalate = mon.record_offense(self._n_ticks, word, hpol)
            # roll the slot back to its last-known-good shadow regardless of
            # what happens next: the quarantine/diverged record must carry
            # the recoverable state, not the one that was drifting apart
            self.state = self.bank.restore_slot(self.state, self._shadow, slot)
            if self._moments is not None:
                # the rolled-back separator invalidates the kurtosis
                # reference: drop the EMAs, re-seed from the next clean tick
                self._moments.reset(sid)
                self._ctrl_scale[slot] = 1.0
            if not escalate:
                self._n_rollbacks += 1
                self._cut_scale[slot] = hpol.mu_cut
                self._cut_on[slot] = True
                self._cut_left[sid] = hpol.cut_ticks
                self._record_health(
                    HealthEvent(sid, self._n_ticks, word, "rollback", slot)
                )
            elif mon.quarantines >= hpol.max_quarantines:
                self._health_mon.pop(sid, None)
                self._release(sid, reason="diverged", health=mon)
                self._n_diverged += 1
                self._record_health(
                    HealthEvent(sid, self._n_ticks, word, "diverge", slot)
                )
            else:
                self._quarantine(sid, word)
        if self._n_ticks % hpol.shadow_every == 0:
            # copy-on-healthy: only slots that PASSED this tick's checks may
            # refresh their shadow (offenders were just rolled back — copying
            # them would be a no-op, but masking keeps the invariant obvious)
            mask = np.zeros((self.bank.n_streams,), dtype=bool)
            for sid in healthy:
                mask[self._slot_of[sid]] = True
            self._shadow = self.bank.update_shadow(
                self._shadow, self.state, jnp.asarray(mask)
            )
        return healthy

    def _quarantine(self, session_id: Hashable, word: int) -> None:
        """ACTIVE → QUARANTINED: the session used up its rollback budget —
        free the slot (the record carries the just-rolled-back last-known-good
        state) and park it under out-of-band health probes until probation
        clears or the ladder tops out."""
        slot = self._slot_of.pop(session_id)
        mon = self._health_mon.pop(session_id, None) or HealthMonitor()
        mon.quarantines += 1
        mon.healthy_streak = 0
        record = EvictionRecord(
            state=self.bank.slot_state(self.state, slot),
            stats=self._stats.pop(session_id),
            monitor=self._monitors.pop(session_id, None),
            reason="quarantined",
            tick=self._n_ticks,
        )
        self._mixing.pop(session_id, None)
        meta = self._meta.pop(session_id, None)
        self._hot.pop(session_id, None)
        self._boost_left.pop(session_id, None)
        self._cut_left.pop(session_id, None)
        self._deadline_mon.pop(session_id, None)
        self._reset_mu(slot)
        if self._moments is not None:
            self._moments.forget(session_id)
        self._free.append(slot)
        self._quarantined[session_id] = QuarantinedSession(
            record=record,
            source=self._sources.pop(session_id, None),
            monitor=mon,
            meta=meta if meta is not None else SessionMeta(),
        )
        self._record_health(
            HealthEvent(session_id, self._n_ticks, word, "quarantine", slot)
        )
        self._backfill()

    def _probe_quarantined(self) -> None:
        """Every ``probe_every`` run_ticks, probe every sourced quarantined
        session out of band: stack the last-known-good states into transient
        pow-2 probe banks (the same machinery as the drift watchdog's parked
        probes) and read the VIRTUAL health word a step on fresh data would
        produce.  A healthy probe advances the probation streak; ``probation``
        consecutive healthy probes re-admit the session warm (through the
        scheduler).  An unhealthy probe resets the streak and counts as an
        offense on the same ladder — a session whose ladder tops out
        (``quarantines > max_quarantines``) evicts with reason
        ``"diverged"``."""
        hpol = self.health_policy
        if not self._quarantined or hpol is None:
            return
        self._quar_ticks += 1
        if self._quar_ticks % hpol.probe_every:
            return
        due = list(self._quarantined)
        P = self.bank.opt.batch_size
        m = self.bank.easi.n_features
        pulled: List[Tuple[Hashable, QuarantinedSession, np.ndarray]] = []
        for sid in due:
            qs = self._quarantined[sid]
            blk = self._pull_probe_block(
                sid, qs, pool=self._quarantined, probe_every=hpol.probe_every
            )
            if blk is not None:
                pulled.append((sid, qs, blk))
        batch = 64  # quarantine pools are small; one pow-2 launch per 64
        for lo in range(0, len(pulled), batch):
            chunk = pulled[lo : lo + batch]
            width = self._probe_width(len(chunk))
            bank, probe_fn = self._probe_bank(width)
            states = [qs.record.state for _, qs, _ in chunk]
            states += [states[-1]] * (width - len(chunk))
            state = SeparatorBank.stack_states(states)
            if bank.fused:
                state = bank.pad_state(state)
                lay = bank.layout
                P_stage, m_stage = lay.P_pad, lay.m_pad
            else:
                P_stage, m_stage = P, m
            X = np.zeros((width, P_stage, m_stage), dtype=np.float32)
            for j, (_, _, blk) in enumerate(chunk):
                X[j, :P, :m] = blk.T
            active = np.zeros((width,), dtype=np.int32)
            active[: len(chunk)] = 1
            _conv, health, _mom = probe_fn(
                state, jnp.asarray(X), jnp.asarray(active)
            )
            health = np.asarray(health)
            self._n_probes += len(chunk)
            self._n_probe_launches += 1
            for j, (sid, qs, _) in enumerate(chunk):
                word = int(health[j])
                if word == 0:
                    qs.monitor.healthy_streak += 1
                    if qs.monitor.healthy_streak >= hpol.probation:
                        self._release_quarantine(sid, qs)
                else:
                    qs.monitor.healthy_streak = 0
                    qs.monitor.last_word = word
                    # a failed probe is an offense on the same ladder: when
                    # the rollback budget is exhausted AGAIN while already
                    # quarantined, the quarantine counter climbs — a session
                    # that never produces a healthy probe tops out without
                    # ever being released
                    if qs.monitor.record_offense(self._n_ticks, word, hpol):
                        qs.monitor.quarantines += 1
                    if qs.monitor.quarantines > hpol.max_quarantines:
                        del self._quarantined[sid]
                        record = dataclasses.replace(
                            qs.record,
                            reason="diverged",
                            tick=self._n_ticks,
                            health=qs.monitor,
                        )
                        self._finished[sid] = record
                        self._n_evicted += 1
                        self._n_diverged += 1
                        self._record_health(
                            HealthEvent(sid, self._n_ticks, word, "diverge")
                        )
                        if self.on_evict is not None:
                            self.on_evict(sid, record)

    def _release_quarantine(
        self, session_id: Hashable, qs: QuarantinedSession
    ) -> None:
        """QUARANTINED → ACTIVE after probation: back through the scheduler's
        admission gate, warm-started from the last-known-good state, with the
        escalation ladder's memory intact (a repeat offender escalates past
        its earlier rungs).  Like ``_readmit``, the release only proceeds
        when it can activate immediately — otherwise the session stays
        quarantined and the next probe retries."""
        del self._quarantined[session_id]
        self._health_mon[session_id] = qs.monitor
        try:
            slot = self.admit(
                session_id,
                source=qs.source,
                state=qs.record.state,
                tenant=qs.meta.tenant,
                priority=qs.meta.priority,
                deadline=qs.meta.deadline,
            )
        except RuntimeError:  # bank AND queue full: stay quarantined
            self._health_mon.pop(session_id, None)
            self._quarantined[session_id] = qs
            return
        if slot is None:  # would queue: back out, stay quarantined
            self.evict(session_id)  # dequeues; detaches source/warm bindings
            self._health_mon.pop(session_id, None)
            self._quarantined[session_id] = qs
            return
        self._record_health(
            HealthEvent(
                session_id, self._n_ticks, qs.monitor.last_word, "release", slot
            )
        )

    def _virtual_conv(self, state: SMBGDState, X: jnp.ndarray) -> float:
        """The conv statistic a bank step WOULD commit from ``state`` on
        ``X (P, m)`` — same ``‖ΔB‖_F/‖B‖_F`` formula, computed out of band
        without touching the bank (the parked-session drift probe)."""
        if self._probe_fn is None:
            ecfg, ocfg = self.bank.easi, self.bank.opt

            def probe(st, x):
                st2, _ = smbgd_lib.smbgd_batched_step(st, x, ecfg, ocfg)
                return metrics_lib.update_magnitude(st2.B, st.B)

            self._probe_fn = jax.jit(probe)
        return float(self._probe_fn(state, X))

    def _probe_parked(self) -> None:
        """Every ``probe_every`` run_ticks, probe every parked session: pull
        one block per parked source, compute the virtual conv statistics (the
        update a bank step WOULD commit from each frozen state), fold them
        into the drift monitors, and re-admit (warm-started, through the
        scheduler) the sessions whose mixing has drifted.  A parked source
        that drains mid-probe evicts the session (reason ``"exhausted"``).

        The due batch — all parked sessions, in park order — runs through the
        BATCHED engine by default: frozen states are stacked into a transient
        probe bank and each ``probe_batch``-wide chunk costs ONE no-commit
        bank launch (``stream.SeparatorBank.probe``; the megakernel's
        freeze-only fast path on fused banks), so watchdog latency scales as
        O(parked / probe_batch) dispatches.  ``DriftPolicy(probe_batch=0)``
        selects the legacy sequential loop — one jitted dispatch per session
        — kept as the oracle the batched engine is differentially tested
        against.  Probe decisions are applied in park order in both engines,
        so they re-admit identically.

        Probes treat the source as LIVE: a parked session is not consuming
        its feed, so the samples that arrived between probes are skipped
        (``seek`` past them, for sources exposing a cursor) — the probe sees
        the present, and parked time advances at service time.

        With ``DriftPolicy.probe_phases > 1`` the parked population is
        STAGGERED: each session hashes (stably, by id) into one of
        ``probe_phases`` buckets and only the rotating due bucket is probed
        per probe tick, so a large parked pool spreads its probe cost over
        ``probe_phases`` ticks instead of stalling one.  Every session keeps
        a fixed probe period of ``probe_every * probe_phases`` run_ticks
        (the seek-past skip accounts for it); ``probe_phases=1`` is exactly
        the legacy everyone-at-once sweep."""
        dpol = self.drift_policy
        if not self._parked or dpol is None or dpol.mode != "readmit":
            return
        self._probe_ticks += 1
        if self._probe_ticks % dpol.probe_every:
            return
        due = list(self._parked)  # the due batch, in park order
        if dpol.probe_phases > 1:
            # rotating bucket: probe cycle k serves phase k mod probe_phases
            phase = (self._probe_ticks // dpol.probe_every) % dpol.probe_phases
            due = [
                sid
                for sid in due
                if self._probe_phase(sid, dpol.probe_phases) == phase
            ]
        if not due:
            return
        if dpol.probe_batch == 0:
            self._probe_sequential(due)
        else:
            self._probe_batched(due)

    @staticmethod
    def _probe_phase(sid: Hashable, phases: int) -> int:
        """Stable stagger bucket of a parked session: the same
        JSON-serialized crc32 the parked-leaf fingerprint uses, mod the
        bucket count — deterministic across processes and restores (Python's
        ``hash`` is salted per process and would reshuffle buckets on every
        restart)."""
        import zlib

        return zlib.crc32(json.dumps(sid, default=str).encode()) % phases

    def _pull_probe_block(
        self,
        sid: Hashable,
        ps,
        pool: Optional[Dict[Hashable, Any]] = None,
        probe_every: Optional[int] = None,
    ):
        """Seek ``sid``'s parked (or quarantined) source to service time and
        pull one probe block ``(m, P)``.  Returns ``None`` when the session
        cannot be probed this tick: no source bound yet (fresh restore
        awaiting ``bind_source``), the source faulted (degraded probe — the
        wrapper's retries were already spent), or the source drained — which
        EVICTS the session from ``pool`` with reason ``"exhausted"`` (a
        drained feed is a finished session; no exception ever escapes
        ``run_tick``)."""
        if ps.source is None:
            return None
        pool = self._parked if pool is None else pool
        if probe_every is None:
            # a staggered session's effective period is probe_every ×
            # probe_phases run_ticks — the seek must skip the whole gap or
            # staggered probes would lag live time by (phases−1) windows
            dpol = self.drift_policy
            probe_every = dpol.probe_every * max(dpol.probe_phases, 1)
        P = self.bank.opt.batch_size
        skip = (probe_every - 1) * P
        if skip and hasattr(ps.source, "seek") and hasattr(ps.source, "position"):
            target = ps.source.position + skip
            limit = getattr(ps.source, "n_samples", None)
            if limit is not None and getattr(ps.source, "loop", False):
                target %= max(limit, 1)  # looping feed: modular live time
            elif limit is not None:
                # finite feed near its end: clamp to the last full block
                # so the probe still measures the PRESENT, not a window
                # from (probe_every-1) ticks ago — but never move the
                # cursor backward (a fully drained feed must exhaust,
                # not re-probe its final block forever)
                target = max(
                    min(target, max(limit - P, 0)), ps.source.position
                )
            try:
                ps.source.seek(target)
            except ValueError:
                pass  # source without absolute seek semantics: best effort
        try:
            blk = np.asarray(ps.source.next_block(P), dtype=np.float32)
        except sources_lib.SourceExhausted:
            del pool[sid]
            record = dataclasses.replace(
                ps.record, reason="exhausted", tick=self._n_ticks
            )
            self._finished[sid] = record
            self._n_evicted += 1
            if self.on_evict is not None:
                self.on_evict(sid, record)
            return None
        except Exception as e:  # noqa: BLE001 — probe-side fault isolation
            self._n_degraded_ticks += 1
            self._last_fault[sid] = f"{type(e).__name__}: {e}"
            return None
        if hasattr(ps.source, "pop_retries"):
            self._n_source_retries += int(ps.source.pop_retries())
        if blk.shape != (self.bank.easi.n_features, P):
            self._n_degraded_ticks += 1
            self._last_fault[sid] = f"probe block shape {blk.shape}"
            return None
        return blk

    def _probe_sequential(self, due: List[Hashable]) -> None:
        """The PR-4 probe engine: one jitted virtual-conv dispatch per parked
        session (``DriftPolicy(probe_batch=0)``) — the differential-test
        oracle of ``_probe_batched``."""
        dpol = self.drift_policy
        for sid in due:
            ps = self._parked[sid]
            blk = self._pull_probe_block(sid, ps)
            if blk is None:
                continue
            x = self._virtual_conv(ps.record.state, jnp.asarray(blk.T))
            self._n_probes += 1
            self._n_probe_launches += 1
            if ps.monitor.update(x, dpol):
                self._readmit(sid, ps)

    def _probe_batched(self, due: List[Hashable]) -> None:
        """The batched probe engine: assemble the due batch (one pulled block
        per parked source), stack the frozen ``(B, Ĥ, step)`` states of each
        ``probe_batch``-wide chunk into a transient probe bank, and compute
        the whole chunk's virtual conv statistics with ONE no-commit launch.
        Ragged chunks are padded to the bank's power-of-two width and masked
        inactive, so at most log2(probe_batch) distinct programs ever
        compile.  Frozen states are immutable while a session stays parked,
        so each chunk's stacked probe-bank state is CACHED (keyed by the
        sessions' park stamps) — a steady parked population pays the
        Python-side stacking once, not every probe tick.  Monitor updates /
        re-admissions are applied in park order, so both engines reach the
        same decisions and end state (the differential property tests pin
        this); the one observable ordering difference is that exhaustion
        evictions surface during the up-front pull phase here, where the
        sequential loop interleaves them per session."""
        dpol = self.drift_policy
        P = self.bank.opt.batch_size
        m = self.bank.easi.n_features
        # fused probe banks consume block-aligned X: staging at padded shape
        # hits pad_batch's zero-copy fast path inside the jitted probe (the
        # same trick the serving tick's staging buffer plays)
        if self.bank.fused:
            lay = self.bank.layout
            P_stage, m_stage = lay.P_pad, lay.m_pad
        else:
            P_stage, m_stage = P, m
        pulled: List[Tuple[Hashable, ParkedSession, np.ndarray]] = []
        for sid in due:
            ps = self._parked[sid]
            blk = self._pull_probe_block(sid, ps)
            if blk is not None:
                pulled.append((sid, ps, blk))
        stacks: Dict[Tuple, BankState] = {}  # chunks live this tick only
        for lo in range(0, len(pulled), dpol.probe_batch):
            chunk = pulled[lo : lo + dpol.probe_batch]
            width = self._probe_width(len(chunk))
            bank, probe_fn = self._probe_bank(width)
            for _, ps, _ in chunk:
                if ps.park_seq < 0:  # white-box/legacy parks: stamp lazily
                    ps.park_seq = self._park_seq
                    self._park_seq += 1
            stamp = tuple(ps.park_seq for _, ps, _ in chunk)
            state = self._probe_stacks.get(stamp)
            if state is None:
                # pad ragged chunks by repeating the last frozen state
                # (masked out below — any well-formed state works; repeating
                # avoids manufacturing degenerate all-zero operands)
                states = [ps.record.state for _, ps, _ in chunk]
                states += [states[-1]] * (width - len(chunk))
                state = SeparatorBank.stack_states(states)
                if bank.fused:
                    state = bank.pad_state(state)
            stacks[stamp] = state
            X = np.zeros((width, P_stage, m_stage), dtype=np.float32)
            for j, (_, _, blk) in enumerate(chunk):
                X[j, :P, :m] = blk.T
            active = np.zeros((width,), dtype=np.int32)
            active[: len(chunk)] = 1
            conv, _health, _mom = probe_fn(
                state, jnp.asarray(X), jnp.asarray(active)
            )
            conv = np.asarray(conv)
            self._n_probes += len(chunk)
            self._n_probe_launches += 1
            for j, (sid, ps, _) in enumerate(chunk):
                if ps.monitor.update(float(conv[j]), dpol):
                    self._readmit(sid, ps)
        self._probe_stacks = stacks  # drop stacks of reshuffled/gone chunks

    @staticmethod
    def _probe_width(k: int) -> int:
        """Probe-bank width for a chunk of ``k`` sessions: the next power of
        two — ragged due batches retrace at most log2(probe_batch) widths."""
        w = 1
        while w < k:
            w *= 2
        return w

    def _probe_bank(self, width: int) -> Tuple[SeparatorBank, Any]:
        """The (cached) transient probe bank of ``width`` slots: same step
        geometry AND memory-system knobs as the serving bank (fused / pallas
        / block_p / dtype_policy) with the bank's base
        hyperparameters — exactly what ``_virtual_conv`` models per session —
        and its jitted no-commit probe step.  ``autotune=False``: the probe
        width is a transient pow-2, not a shape anyone tuned for, so the
        serving bank's resolved geometry is pinned rather than re-looked-up."""
        got = self._probe_banks.get(width)
        if got is None:
            bank = SeparatorBank(
                self.bank.easi,
                self.bank.opt,
                n_streams=width,
                algorithm="smbgd_batched",
                use_pallas=self.bank.use_pallas,
                fused=self.bank.fused,
                block_p=(
                    self.bank.layout.block_p
                    if self.bank.fused
                    else self.bank.block_p
                ),
                dtype_policy=self.bank.dtype_policy,
                moments=bool(self.bank.moments),
                autotune=False,
            )
            got = (bank, bank.make_probe())
            self._probe_banks[width] = got
        return got

    def _readmit(self, session_id: Hashable, ps: ParkedSession) -> None:
        """PARKED → ACTIVE on watchdog fire: back through the scheduler's
        admission gate, warm-started from the frozen separator.  The
        re-admission only proceeds when it can ACTIVATE immediately (a free
        slot, or a preemptable hot session); if it would merely queue —
        backpressure, tenant quota — the session stays parked and the next
        probe retries.  A queued re-admission would hold its warm-start
        state as an un-snapshotable pending array; parked-until-activatable
        keeps checkpoints exact."""
        del self._parked[session_id]
        try:
            slot = self.admit(
                session_id,
                source=ps.source,
                state=ps.record.state,
                tenant=ps.meta.tenant,
                priority=ps.meta.priority,
                deadline=ps.meta.deadline,
            )
        except RuntimeError:  # bank AND queue full: stay parked, retry later
            self._parked[session_id] = ps
            return
        if slot is None:  # would queue (gated/contended): back out, stay parked
            self.evict(session_id)  # dequeues; detaches the source/warm bindings
            self._parked[session_id] = ps
            return
        self._record_drift(
            DriftEvent(
                session_id=session_id,
                tick=self._n_ticks,
                stat=ps.monitor.stat,
                action="readmit",
                slot=slot,
            )
        )

    # -- elastic capacity --------------------------------------------------
    @staticmethod
    def _step_key(bank: SeparatorBank) -> Tuple:
        """Jitted-step cache key: a resize back to a previously served
        (width, geometry) reuses its compiled program instead of retracing."""
        return (bank.n_streams, bank.block_p, bank.block_s)

    def _get_step(self, bank: SeparatorBank):
        got = self._step_cache.get(self._step_key(bank))
        if got is None:
            got = bank.make_step(with_hyperparams=self._hp_step)
            self._step_cache[self._step_key(bank)] = got
        return got

    def prewarm(self, widths) -> None:
        """Compile (and jit-cache) the serving step at each width in
        ``widths`` ahead of time, so the first tick after a resize pays no
        compile.  The warm-up CALLS each jitted step on blank operands with
        the serving tick's exact dtypes (f32 X, bool active mask, the bank's
        base hyperparameter rows when the μ machinery is armed) — lowering
        alone would not populate the jit cache.  It also exercises the
        slot-write and resize paths at every width (and ``resize_state``
        across each consecutive pair of widths, both directions): those are
        eager jnp ops whose first execution at a new shape pays a one-off
        XLA compile that would otherwise land on the serving tick that
        resizes.  Throwaway states only: the serving state, RNG key and free
        list are untouched."""
        widths = sorted(set(widths))
        banks, states = {}, {}
        for w in widths:
            bank = (
                self.bank if w == self.bank.n_streams
                else self.bank.with_streams(w)
            )
            fn = self._get_step(bank)
            state = bank.init(jax.random.PRNGKey(0))
            if bank.fused:
                lay = bank.layout
                X = np.zeros((w, lay.P_pad, lay.m_pad), dtype=np.float32)
            else:
                X = np.zeros(
                    (w, bank.opt.batch_size, bank.easi.n_features),
                    dtype=np.float32,
                )
            active = np.zeros((w,), dtype=bool)
            args = (state, jnp.asarray(X), jnp.asarray(active))
            if self._hp_step:
                args = args + (bank._bank_hyperparams(),)
            out_state, _ = fn(*args)
            jax.block_until_ready(out_state.conv)
            banks[w], states[w] = bank, out_state
        for w in widths:
            bank, state = banks[w], states[w]
            # activation (set_slot), fresh-init (init_slot) and compaction
            # (move_slot) writes at this width
            sub = bank.slot_state(state, 0)
            jax.block_until_ready(bank.set_slot(state, 0, sub).B)
            jax.block_until_ready(
                bank.init_slot(state, 0, jax.random.PRNGKey(0)).B
            )
            if w > 1:
                jax.block_until_ready(bank.move_slot(state, 0, w - 1).B)
        # all ordered width pairs: the autoscaler's shrink can skip ladder
        # rungs (8 -> 2 straight), and each (from, to) pair has its own
        # concat/slice shapes
        for src in widths:
            for dst in widths:
                if src != dst:
                    jax.block_until_ready(
                        banks[dst].resize_state(states[src]).B
                    )

    def compact(self) -> int:
        """Migrate every live slot to the low end of the bank (preserving
        slot order) so the high end is contiguously free — what lets a
        half-empty wide bank actually release width.  Each move carries the
        slot's FULL row (``SeparatorBank.move_slot``: B, Ĥ, step, conv,
        health, moments — plus the shadow snapshot and the per-slot μ
        multipliers), so a compacted session's trajectory is bit-identical
        to never having moved; sid-keyed bookkeeping (monitors, stats,
        deadline windows, kurtosis EMAs) never even notices.  Returns the
        number of sessions moved (0 = already compact, not counted as a
        compaction)."""
        order = sorted(self._slot_of.items(), key=lambda kv: kv[1])
        moved = 0
        for target, (sid, slot) in enumerate(order):
            if slot == target:
                continue
            # slots ascend and each target < its source, so no move ever
            # reads a row an earlier move already overwrote
            self.state = self.bank.move_slot(self.state, target, slot)
            if self._shadow is not None:
                self._shadow = self.bank.move_slot(self._shadow, target, slot)
            for arr in (
                self._boost_scale,
                self._cut_scale,
                self._ctrl_scale,
                self._cut_on,
            ):
                arr[target] = arr[slot]
            self._reset_mu(slot)
            self._slot_of[sid] = target
            moved += 1
        if moved:
            taken = set(self._slot_of.values())
            self._free = [
                s
                for s in range(self.bank.n_streams - 1, -1, -1)
                if s not in taken
            ]
            self._n_compactions += 1
            self._resize_history.append(
                {
                    "tick": self._n_ticks,
                    "action": "compact",
                    "from": self.bank.n_streams,
                    "to": self.bank.n_streams,
                    "reason": f"moved={moved}",
                }
            )
        return moved

    def grow(self, new_S: int, reason: str = "manual") -> None:
        """Widen the bank to ``new_S`` slots in place: surviving sessions
        keep their slots (state grows by leaf-wise prefix copy; no RNG is
        consumed for the blank slots), the free list gains the new high
        slots, and the waiting room backfills into them immediately."""
        if new_S < self.bank.n_streams:
            raise ValueError(
                f"grow target {new_S} < current width "
                f"{self.bank.n_streams}; use shrink"
            )
        self._resize(new_S, "grow", reason)

    def shrink(self, new_S: int, reason: str = "manual") -> None:
        """Narrow the bank to ``new_S`` slots, compacting live sessions to
        the low end first when any of them occupies a slot the truncation
        would drop.  Raises an actionable ``ValueError`` (naming the live
        sids and both widths) when the live sessions simply do not fit."""
        if new_S > self.bank.n_streams:
            raise ValueError(
                f"shrink target {new_S} > current width "
                f"{self.bank.n_streams}; use grow"
            )
        self._resize(new_S, "shrink", reason)

    def _resize(self, new_S: int, action: str, reason: str) -> None:
        """The shared grow/shrink edge: swap in ``bank.with_streams(new_S)``
        (autotune geometry re-resolves at the new width key; explicit knobs
        win — see ``SeparatorBank.with_streams``), prefix-copy every
        width-dependent array (state, shadow, μ ladders, staging buffer),
        rebuild the free list around the surviving slot map, and re-point
        the jitted step at the cached program for the new geometry."""
        old_S = self.bank.n_streams
        if new_S == old_S:
            return
        if new_S < 1:
            raise ValueError("bank width must be >= 1")
        if new_S < old_S:
            if self.n_active > new_S:
                raise ValueError(
                    f"cannot shrink bank {old_S} -> {new_S}: "
                    f"{self.n_active} live sessions exceed the new capacity "
                    f"({sorted(map(str, self._slot_of))})"
                )
            if any(slot >= new_S for slot in self._slot_of.values()):
                self.compact()
        new_bank = self.bank.with_streams(new_S)
        self.state = new_bank.resize_state(self.state)
        if self._shadow is not None:
            self._shadow = new_bank.resize_state(self._shadow)
        if new_S > old_S:
            pad = new_S - old_S
            self._boost_scale = np.concatenate(
                [self._boost_scale, np.ones((pad,), np.float32)]
            )
            self._cut_scale = np.concatenate(
                [self._cut_scale, np.ones((pad,), np.float32)]
            )
            self._ctrl_scale = np.concatenate(
                [self._ctrl_scale, np.ones((pad,), np.float32)]
            )
            self._cut_on = np.concatenate(
                [self._cut_on, np.zeros((pad,), bool)]
            )
        else:
            self._boost_scale = self._boost_scale[:new_S].copy()
            self._cut_scale = self._cut_scale[:new_S].copy()
            self._ctrl_scale = self._ctrl_scale[:new_S].copy()
            self._cut_on = self._cut_on[:new_S].copy()
        if new_bank.fused:
            lay = new_bank.layout
            stage_shape = (new_S, lay.P_pad, lay.m_pad)
        else:
            stage_shape = (
                new_S, new_bank.opt.batch_size, new_bank.easi.n_features
            )
        self._stage = np.zeros(stage_shape, dtype=np.float32)
        self._base_hp = (
            new_bank._bank_hyperparams() if self._hp_step else None
        )
        self._step = self._get_step(new_bank)
        # probe banks pin the SERVING bank's resolved geometry — drop them
        # only when the re-resolution actually changed it (stacked-state
        # caches key on park stamps, not geometry, but a probe bank rebuild
        # would re-pad them, so they go together)
        old_block_p = (
            self.bank.layout.block_p if self.bank.fused else self.bank.block_p
        )
        new_block_p = (
            new_bank.layout.block_p if new_bank.fused else new_bank.block_p
        )
        if old_block_p != new_block_p:
            self._probe_banks = {}
            self._probe_stacks = {}
        self.bank = new_bank
        taken = set(self._slot_of.values())
        self._free = [
            s for s in range(new_S - 1, -1, -1) if s not in taken
        ]
        if action == "grow":
            self._n_grows += 1
        else:
            self._n_shrinks += 1
        self._resize_history.append(
            {
                "tick": self._n_ticks,
                "action": action,
                "from": old_S,
                "to": new_S,
                "reason": reason,
            }
        )
        self._last_resize_tick = self._elastic_ticks
        if action == "grow":
            # new slots serve waiting work the same tick they appear
            self._backfill()

    def _autoscale_tick(self) -> None:
        """One autoscaler evaluation per ``run_tick`` (after the probe
        phase, before the tick's latency record closes — resize cost is
        billed to the tick that resized)."""
        pol = self.autoscale
        if pol is None:
            return
        self._elastic_ticks += 1
        since = (
            None
            if self._last_resize_tick is None
            else self._elastic_ticks - self._last_resize_tick
        )
        decision: Optional[ResizeDecision] = pol.decide(
            self.bank.n_streams,
            self.n_active,
            self.n_queued,
            self.deadline_miss_rate,
            since,
        )
        if decision is None:
            return
        if decision.action == "grow":
            self.grow(decision.target, reason=decision.reason)
        else:
            if pol.compact_before_shrink:
                self.compact()
            self.shrink(decision.target, reason=decision.reason)

    # -- scheduler-driven ingestion ---------------------------------------
    def run_tick(self) -> Dict[Hashable, np.ndarray]:
        """One pull tick: backfill free slots from the scheduler, pull a
        channel-major ``(m, P)`` block from every active session's bound
        ``SignalSource``, advance them all with ONE fused bank step, evict
        sessions whose source drained (reason ``"exhausted"``), and probe
        parked and quarantined sessions out of band.  Returns session_id →
        separated ``(P, n)`` host outputs, as ``step`` does (sessions without
        a source are skipped — push their batches through ``step`` instead;
        both modes mix freely).

        Per-session fault isolation: a source raising anything other than
        ``SourceExhausted`` (transient I/O error, stall past a
        ``ResilientSource`` timeout, short read) degrades THAT session's tick
        — it is simply left out of the batch, so the bank's active mask
        freezes its slot — and never fails the launch for everyone else.
        Degraded session-ticks count in ``metrics['n_degraded_ticks']``; the
        last per-session failure string is kept in ``last_faults``.

        Latency accounting (PR-8): the tick's recorded latency is the FULL
        ``run_tick`` duration — pull + bank step (time-to-ready) + drain
        evictions + out-of-band probes — so probe work is billed to the tick
        that ran it and a ``deadline_budget_s`` judges what a real-time
        caller actually waited.  A run_tick whose batches all degraded or
        drained (or that only probed) no longer vanishes from telemetry: it
        counts in ``metrics['n_empty_ticks']`` and its duration still lands
        in the latency sketch and the deadline check (``n_ticks`` remains
        data ticks only — lifecycle stamps keep their meaning)."""
        with _span("serve.run_tick", tick=self._n_ticks):
            t0 = time.perf_counter()
            with _span("serve.backfill"):
                self._backfill()  # deadline/quota gates may have reopened
            with _span("serve.pull"):
                batches, drained = self._pull_blocks()
            if batches:
                self._defer_slo = True
                try:
                    out = self.step(batches)
                finally:
                    self._defer_slo = False
            else:
                out = {}
            with _span("serve.release"):
                for sid in drained:
                    if sid in self._slot_of:
                        self._release(sid, reason="exhausted")
            had_oob = bool(self._parked or self._quarantined)
            with _span("serve.probe"):
                pt0 = time.perf_counter()
                self._probe_parked()
                self._probe_quarantined()
                pt1 = time.perf_counter()
            if had_oob:
                self._last_probe_s = pt1 - pt0  # out-of-band probe phase, timed
            # autoscale AFTER serve+probe (decisions see this tick's telemetry)
            # and BEFORE the latency record closes: resize cost is billed to
            # the tick that resized, so the SLO sketch and the bench's
            # resize-tick overhead metric both see it
            with _span("serve.autoscale"):
                self._autoscale_tick()
            dt = time.perf_counter() - t0
            if self._pending_tick is not None:
                served, timed, samples = self._pending_tick
                self._pending_tick = None
                self._finish_tick(dt, served, timed, samples)
            else:
                # empty tick: every source degraded/drained, or probe-only work
                # — distinctly counted, and its wall-clock still faces the
                # budget (probes end host-synced, so dt is honest without a
                # sync leaf)
                self._n_empty_ticks += 1
                self._record_latency(dt, [])
            return out

    def _pull_blocks(self) -> Tuple[Dict[Hashable, np.ndarray], List[Hashable]]:
        """One ``(m, P)`` block from every active session's bound source,
        returned as its ``(P, m)`` batch; also the sessions whose source
        drained.  A source that raises anything else degrades its own
        session's tick only (see ``run_tick``)."""
        P = self.bank.opt.batch_size
        m = self.bank.easi.n_features
        batches: Dict[Hashable, np.ndarray] = {}
        drained: List[Hashable] = []
        for sid in list(self._slot_of):
            src = self._sources.get(sid)
            if src is None:
                continue
            try:
                blk = np.asarray(src.next_block(P), dtype=np.float32)
                if blk.shape != (m, P):
                    raise ValueError(
                        f"block shape {blk.shape} != (m={m}, n_samples={P})"
                    )
            except sources_lib.SourceExhausted:
                drained.append(sid)
                continue
            except Exception as e:  # noqa: BLE001 — per-session isolation
                self._n_degraded_ticks += 1
                self._last_fault[sid] = f"{type(e).__name__}: {e}"
                continue
            if hasattr(src, "pop_retries"):
                self._n_source_retries += int(src.pop_retries())
            batches[sid] = blk.T
        return batches, drained

    @property
    def last_faults(self) -> Dict[Hashable, str]:
        """Most recent per-session source-failure strings (degraded ticks —
        the observability twin of ``metrics['n_degraded_ticks']``)."""
        return dict(self._last_fault)

    # -- persistence -------------------------------------------------------
    # The bank state is a plain pytree, so the array side round-trips through
    # any Checkpointer.  The session→slot map, admission queue and monitor
    # counters are host bookkeeping (arbitrary hashable ids — not arrays):
    # callers persist them via ``sessions``/``lifecycle`` and hand them back
    # to ``restore`` to resume live sessions and queued admissions.

    @property
    def sessions(self) -> Dict[Hashable, int]:
        """Snapshot of the live session→slot map (save alongside the arrays)."""
        return dict(self._slot_of)

    @property
    def lifecycle(self) -> Dict[str, Any]:
        """JSON-friendly snapshot of the full host-side lifecycle state:
        session→slot map, the scheduler's waiting room (ids + scheduling
        metadata), per-session convergence monitors, active-session metadata,
        the drift watchdog (hot-session monitors, remaining boost ticks,
        per-slot μ multipliers, bound-source cursor positions), and the
        parked population under out-of-band probe — each parked session's
        drift-monitor EMA, scheduling metadata, eviction provenance and
        source cursor, in park order, plus the probe cadence counter
        (``probe_ticks``), so a restored watchdog resumes mid-cycle with the
        exact due-batch membership and phase it left off at.  Save alongside
        the arrays; hand back to ``restore`` to resume sessions, queue,
        convergence progress AND drift watch in place.

        Deliberately excluded (arrays / live objects, not JSON): mixing
        matrices registered via ``set_mixing`` (re-register after restore),
        the ``SignalSource`` objects themselves (re-attach via
        ``bind_source``, which seeks them to the recorded positions — parked
        sessions included; an unbound parked session stays parked and simply
        skips probes), the parked sessions' frozen separator arrays (those
        ride ``save``/``restore`` as stacked ``parked_*`` checkpoint leaves),
        and pending warm-start states of QUEUED sessions (a caller's
        ``admit(state=...)`` under backpressure activates FRESH after a
        restore; the watchdog itself never queues a warm re-admission —
        see ``_readmit``)."""
        return {
            "sessions": dict(self._slot_of),
            "queue": self.scheduler.snapshot(),
            "monitors": {
                sid: dataclasses.asdict(mon)
                for sid, mon in self._monitors.items()
            },
            "meta": {sid: meta.asdict() for sid, meta in self._meta.items()},
            "hot": {
                sid: dataclasses.asdict(mon) for sid, mon in self._hot.items()
            },
            "boost": dict(self._boost_left),
            # legacy composite (pre-split readers) + the per-ladder arrays
            "mu_scale": [float(v) for v in self._effective_mu_scale()],
            "mu_boost_scale": [float(v) for v in self._boost_scale],
            "mu_cut_scale": [float(v) for v in self._cut_scale],
            "mu_ctrl_scale": [float(v) for v in self._ctrl_scale],
            "mu_cut_on": [bool(v) for v in self._cut_on],
            "moments": (
                self._moments.state_dict() if self._moments is not None else {}
            ),
            "sources": {
                sid: int(src.position)
                for sid, src in self._sources.items()
                if hasattr(src, "position")
            },
            "probe_ticks": self._probe_ticks,
            "health": {
                sid: dataclasses.asdict(mon)
                for sid, mon in self._health_mon.items()
            },
            "cut": dict(self._cut_left),
            "quarantine_ticks": self._quar_ticks,
            "resize_history": [dict(e) for e in self._resize_history],
            "shadow": self._shadow is not None,
            "quarantined": [
                [
                    sid,
                    {
                        "monitor": dataclasses.asdict(qs.monitor),
                        "meta": qs.meta.asdict(),
                        "reason": qs.record.reason,
                        "tick": qs.record.tick,
                        "position": (
                            int(qs.source.position)
                            if qs.source is not None
                            and hasattr(qs.source, "position")
                            else None
                        ),
                    },
                ]
                for sid, qs in self._quarantined.items()
            ],
            "parked": [
                [
                    sid,
                    {
                        "monitor": dataclasses.asdict(ps.monitor),
                        "meta": ps.meta.asdict(),
                        "reason": ps.record.reason,
                        "tick": ps.record.tick,
                        "position": (
                            int(ps.source.position)
                            if ps.source is not None
                            and hasattr(ps.source, "position")
                            else None
                        ),
                    },
                ]
                for sid, ps in self._parked.items()
            ],
        }

    @staticmethod
    def _parked_fingerprint(sids) -> jnp.ndarray:
        """Order-sensitive (K,) uint32 fingerprint of parked session ids.

        Saved alongside the stacked ``parked_*`` leaves and recomputed from
        the ``lifecycle`` snapshot at restore: the arrays and the snapshot
        are separate artifacts zipped back together BY INDEX, so a snapshot
        captured at a different moment than ``save`` (same parked count,
        different membership/order) must fail loudly instead of silently
        attaching frozen separators to the wrong sessions."""
        import zlib

        return jnp.asarray(
            [
                zlib.crc32(json.dumps(sid, default=str).encode())
                for sid in sids
            ],
            dtype=jnp.uint32,
        )

    def save(self, checkpointer, step: int) -> None:
        # rng_key rides along so post-restore admissions continue the key
        # sequence instead of replaying pre-save inits; parked sessions'
        # frozen separators ride as stacked leaves (in the ``lifecycle``
        # snapshot's park order — restore zips the two back together, with
        # the sid fingerprint guarding the index pairing)
        tree = dict(self.state._asdict(), rng_key=self.key)
        if self._parked:
            frozen = [ps.record.state for ps in self._parked.values()]
            tree["parked_B"] = jnp.stack([jnp.asarray(s.B) for s in frozen])
            tree["parked_H_hat"] = jnp.stack(
                [jnp.asarray(s.H_hat) for s in frozen]
            )
            tree["parked_step"] = jnp.stack(
                [jnp.asarray(s.step) for s in frozen]
            )
            tree["parked_ids"] = self._parked_fingerprint(self._parked)
        # the last-known-good shadow rides as its own leaves: a restored
        # service must be able to roll back to the SAME snapshot the
        # checkpointed one would have, not to the post-restore state
        if self._shadow is not None:
            tree["shadow_B"] = self._shadow.B
            tree["shadow_H_hat"] = self._shadow.H_hat
            tree["shadow_step"] = self._shadow.step
            tree["shadow_conv"] = self._shadow.conv
        # quarantined sessions' last-known-good states ride like parked ones
        # (zipped back by index against lifecycle['quarantined'], fingerprint
        # guarded)
        if self._quarantined:
            lkg = [qs.record.state for qs in self._quarantined.values()]
            tree["quar_B"] = jnp.stack([jnp.asarray(s.B) for s in lkg])
            tree["quar_H_hat"] = jnp.stack([jnp.asarray(s.H_hat) for s in lkg])
            tree["quar_step"] = jnp.stack([jnp.asarray(s.step) for s in lkg])
            tree["quar_ids"] = self._parked_fingerprint(self._quarantined)
        checkpointer.save(step, tree)

    def restore(
        self,
        checkpointer,
        step: Optional[int] = None,
        sessions: Optional[Dict[Hashable, int]] = None,
        lifecycle: Optional[Dict[str, Any]] = None,
    ) -> int:
        """Restore bank arrays and (optionally) re-attach host lifecycle state.

        Without ``sessions``/``lifecycle`` every slot is considered free:
        restored separator matrices are still in the arrays but will be
        overwritten as slots are re-admitted.  Pass the ``sessions`` map (or
        the richer ``lifecycle`` snapshot, which also carries the admission
        queue, the per-session convergence monitors AND the parked probe
        population — frozen separators from the checkpoint's stacked
        ``parked_*`` leaves, drift-monitor EMAs, probe cadence and due-batch
        order from the snapshot) captured at save time to resume in place.
        Restored parked sessions hold no source until ``bind_source``
        re-attaches one (seeking it to the recorded cursor); until then they
        stay parked and skip probes.

        Ground-truth mixing matrices are NOT part of the snapshot (they are
        arrays, not host bookkeeping, and the snapshot stays JSON-able):
        callers using ``ConvergencePolicy.amari_threshold`` must re-register
        them via ``set_mixing`` after restore, or the Amari confirmation is
        skipped and the blind statistic decides alone.
        """
        lifecycle = lifecycle or {}
        if sessions is None:
            sessions = lifecycle.get("sessions") or {}
        queue_entries = list(lifecycle.get("queue") or [])
        # entries are [sid, meta] pairs (new) or plain sids (PR-3 snapshots)
        queue_ids = [
            e[0]
            if isinstance(e, (list, tuple)) and len(e) == 2 and isinstance(e[1], dict)
            else e
            for e in queue_entries
        ]
        monitors = lifecycle.get("monitors") or {}
        meta_snap = lifecycle.get("meta") or {}
        hot_snap = lifecycle.get("hot") or {}
        boost_snap = lifecycle.get("boost") or {}
        mu_scale = lifecycle.get("mu_scale")
        parked_snap = list(lifecycle.get("parked") or [])
        parked_ids = [sid for sid, _info in parked_snap]
        health_snap = lifecycle.get("health") or {}
        cut_snap = lifecycle.get("cut") or {}
        boost_scale_snap = lifecycle.get("mu_boost_scale")
        cut_scale_snap = lifecycle.get("mu_cut_scale")
        ctrl_scale_snap = lifecycle.get("mu_ctrl_scale")
        cut_on_snap = lifecycle.get("mu_cut_on")
        moments_snap = lifecycle.get("moments") or {}
        quar_snap = list(lifecycle.get("quarantined") or [])
        quar_ids = [sid for sid, _info in quar_snap]
        want_shadow = bool(lifecycle.get("shadow"))
        # elastic restore: the checkpoint's true width comes from the
        # manifest peek (no array data loaded) — a service resized since
        # save builds its restore target at the SAVED width and re-places
        # the sessions into the current free list afterwards, instead of
        # failing the Checkpointer's per-leaf shape check
        saved_S = self.bank.n_streams
        peek = getattr(checkpointer, "leaf_shapes", None)
        if peek is not None:
            shape = peek(step=step).get("B")
            if shape:
                saved_S = int(shape[0])
        if saved_S != self.bank.n_streams and len(sessions) > self.bank.n_streams:
            raise ValueError(
                f"cannot restore checkpoint of width {saved_S} into a bank "
                f"of width {self.bank.n_streams}: {len(sessions)} live "
                f"sessions exceed the new capacity "
                f"({sorted(map(str, sessions))}) — grow the bank or evict "
                f"before restoring"
            )
        bad = {
            s: slot
            for s, slot in sessions.items()
            if not 0 <= slot < saved_S
        }
        if bad:
            raise ValueError(f"session slots out of range: {bad}")
        if len(set(sessions.values())) != len(sessions):
            raise ValueError(f"duplicate slots in session map: {sessions}")
        overlap = set(queue_ids) & set(sessions)
        if overlap or len(set(queue_ids)) != len(queue_ids):
            raise ValueError(f"queue/session overlap or duplicates: {queue_ids}")
        parked_overlap = set(parked_ids) & (set(sessions) | set(queue_ids))
        if parked_overlap or len(set(parked_ids)) != len(parked_ids):
            raise ValueError(
                f"parked/session/queue overlap or duplicates: {parked_ids}"
            )
        if parked_snap and (
            self.drift_policy is None or self.drift_policy.mode != "readmit"
        ):
            raise ValueError(
                "lifecycle snapshot carries parked sessions but this service "
                "has no readmit-mode drift_policy to probe them"
            )
        quar_overlap = set(quar_ids) & (
            set(sessions) | set(queue_ids) | set(parked_ids)
        )
        if quar_overlap or len(set(quar_ids)) != len(quar_ids):
            raise ValueError(
                f"quarantined/session/queue/parked overlap or duplicates: "
                f"{quar_ids}"
            )
        if (quar_snap or health_snap or cut_snap) and self.health_policy is None:
            raise ValueError(
                "lifecycle snapshot carries health-containment state "
                "(quarantined/health/cut) but this service has no "
                "health_policy to run the escalation ladder"
            )
        for name, arr in (
            ("mu_scale", mu_scale),
            ("mu_boost_scale", boost_scale_snap),
            ("mu_cut_scale", cut_scale_snap),
            ("mu_ctrl_scale", ctrl_scale_snap),
            ("mu_cut_on", cut_on_snap),
        ):
            if arr is not None and len(arr) != saved_S:
                raise ValueError(
                    f"{name} length {len(arr)} != n_streams "
                    f"{saved_S}"
                )
        if moments_snap and self._moments is None:
            raise ValueError(
                "lifecycle snapshot carries moment-controller state but this "
                "service has no moment_policy to apply it"
            )
        # drift-watch state needs the drift machinery to run: re-arming hot
        # monitors without a policy would crash the next served tick, and μ
        # multipliers without the hyperparam step would be silently inert
        if (hot_snap or boost_snap) and self.drift_policy is None:
            raise ValueError(
                "lifecycle snapshot carries drift-watch state (hot/boost) "
                "but this service has no drift_policy"
            )
        if not self._hp_step and any(
            any(float(v) != 1.0 for v in arr)
            for arr in (mu_scale, boost_scale_snap, cut_scale_snap, ctrl_scale_snap)
            if arr is not None
        ):
            raise ValueError(
                "lifecycle snapshot carries μ multipliers but this service "
                "cannot apply them (no boost-mode drift_policy)"
            )
        # validate BEFORE mutating: a rejected map must leave the live
        # service untouched
        if saved_S == self.bank.n_streams:
            target = dict(self.state._asdict(), rng_key=self.key)
        else:
            # restore target at the checkpoint's width; the current state's
            # trailing dims are width-independent, so they size the leaves
            target = {
                name: (
                    None
                    if leaf is None
                    else jnp.zeros((saved_S,) + leaf.shape[1:], leaf.dtype)
                )
                for name, leaf in self.state._asdict().items()
            }
            target["rng_key"] = self.key
        if parked_snap:
            n = self.bank.easi.n_components
            m = self.bank.easi.n_features
            dt = self.bank.easi.dtype
            K = len(parked_snap)
            target["parked_B"] = jnp.zeros((K, n, m), dt)
            target["parked_H_hat"] = jnp.zeros((K, n, n), dt)
            target["parked_step"] = jnp.zeros((K,), jnp.int32)
            target["parked_ids"] = jnp.zeros((K,), jnp.uint32)
        if want_shadow:
            # shadow leaves are width-dependent too — sized off the (possibly
            # saved-width) state target so they match the checkpoint
            target["shadow_B"] = jnp.zeros_like(target["B"])
            target["shadow_H_hat"] = jnp.zeros_like(target["H_hat"])
            target["shadow_step"] = jnp.zeros_like(target["step"])
            target["shadow_conv"] = jnp.zeros_like(target["conv"])
        if quar_snap:
            n = self.bank.easi.n_components
            m = self.bank.easi.n_features
            dt = self.bank.easi.dtype
            K = len(quar_snap)
            target["quar_B"] = jnp.zeros((K, n, m), dt)
            target["quar_H_hat"] = jnp.zeros((K, n, n), dt)
            target["quar_step"] = jnp.zeros((K,), jnp.int32)
            target["quar_ids"] = jnp.zeros((K,), jnp.uint32)
        tree, got = checkpointer.restore(target, step=step)
        if quar_snap:
            want = np.asarray(self._parked_fingerprint(quar_ids))
            saved = np.asarray(tree.pop("quar_ids"))
            if not np.array_equal(saved, want):
                raise ValueError(
                    "lifecycle['quarantined'] does not match the checkpoint's "
                    "quar_* leaves (membership/order changed between save and "
                    "snapshot?) — last-known-good states would attach to the "
                    "wrong sessions"
                )
        if parked_snap:
            # the arrays and the snapshot are zipped by index: the saved sid
            # fingerprint must match the snapshot's park order exactly
            want = np.asarray(self._parked_fingerprint(parked_ids))
            saved = np.asarray(tree.pop("parked_ids"))
            if not np.array_equal(saved, want):
                raise ValueError(
                    "lifecycle['parked'] does not match the checkpoint's "
                    "parked_* leaves (membership/order changed between save "
                    "and snapshot?) — frozen separators would attach to the "
                    "wrong sessions"
                )
        self.key = tree.pop("rng_key")
        parked_B = tree.pop("parked_B", None)
        parked_H = tree.pop("parked_H_hat", None)
        parked_step = tree.pop("parked_step", None)
        shadow_B = tree.pop("shadow_B", None)
        shadow_H = tree.pop("shadow_H_hat", None)
        shadow_step = tree.pop("shadow_step", None)
        shadow_conv = tree.pop("shadow_conv", None)
        quar_B = tree.pop("quar_B", None)
        quar_H = tree.pop("quar_H_hat", None)
        quar_step = tree.pop("quar_step", None)
        self.state = BankState(**tree)
        if shadow_B is not None:
            self._shadow = BankState(
                B=shadow_B,
                H_hat=shadow_H,
                step=shadow_step,
                conv=shadow_conv,
                health=jnp.zeros_like(self.state.health),
                moments=jnp.zeros((shadow_B.shape[0], 2), jnp.float32),
            )
        elif self.health_policy is not None:
            # checkpoint predates the shadow (or was saved without one):
            # re-seed the last-known-good snapshot from the restored state —
            # a state that was committed and saved is by definition healthy
            self._shadow = self.state
        else:
            self._shadow = None
        if saved_S != self.bank.n_streams:
            # re-placement: gather the restored sessions' rows (in slot
            # order), re-place them contiguously from slot 0, and pad or
            # truncate to the CURRENT width — every surviving row is carried
            # verbatim, so the restored trajectories stay bit-identical
            order = sorted(sessions.items(), key=lambda kv: kv[1])
            idx = jnp.asarray(
                [slot for _sid, slot in order], dtype=jnp.int32
            )

            def _gather(st: BankState) -> BankState:
                return BankState(
                    B=st.B[idx],
                    H_hat=st.H_hat[idx],
                    step=st.step[idx],
                    conv=None if st.conv is None else st.conv[idx],
                    health=None if st.health is None else st.health[idx],
                    moments=(
                        None if st.moments is None else st.moments[idx]
                    ),
                )

            self.state = self.bank.resize_state(_gather(self.state))
            if self._shadow is not None:
                self._shadow = self.bank.resize_state(_gather(self._shadow))

            def _remap(arr, fill):
                if arr is None:
                    return None
                out = [fill] * self.bank.n_streams
                for new_slot, (_sid, old_slot) in enumerate(order):
                    out[new_slot] = arr[old_slot]
                return out

            mu_scale = _remap(mu_scale, 1.0)
            boost_scale_snap = _remap(boost_scale_snap, 1.0)
            cut_scale_snap = _remap(cut_scale_snap, 1.0)
            ctrl_scale_snap = _remap(ctrl_scale_snap, 1.0)
            cut_on_snap = _remap(cut_on_snap, False)
            sessions = {sid: i for i, (sid, _slot) in enumerate(order)}
        self._slot_of = dict(sessions)
        self.scheduler.load(queue_entries)
        # convergence progress resumes exactly; sessions without a saved
        # monitor restart their decision state (but not their separator)
        self._monitors = {
            sid: ConvergenceMonitor(**monitors[sid])
            if sid in monitors
            else ConvergenceMonitor()
            for sid in sessions
        }
        self._meta = {
            sid: SessionMeta(**meta_snap[sid])
            if sid in meta_snap
            else SessionMeta()
            for sid in sessions
        }
        # drift watch resumes exactly: hot monitors, boost countdowns, μ rows
        self._hot = {
            sid: DriftMonitor(**mon)
            for sid, mon in hot_snap.items()
            if sid in sessions
        }
        self._boost_left = {
            sid: int(v) for sid, v in boost_snap.items() if sid in sessions
        }
        S = self.bank.n_streams
        if (
            boost_scale_snap is not None
            or cut_scale_snap is not None
            or ctrl_scale_snap is not None
        ):
            # per-ladder snapshot (PR-9+): restore each writer's multiplier
            self._boost_scale = (
                np.asarray(boost_scale_snap, np.float32)
                if boost_scale_snap is not None
                else np.ones((S,), np.float32)
            )
            self._cut_scale = (
                np.asarray(cut_scale_snap, np.float32)
                if cut_scale_snap is not None
                else np.ones((S,), np.float32)
            )
            self._ctrl_scale = (
                np.asarray(ctrl_scale_snap, np.float32)
                if ctrl_scale_snap is not None
                else np.ones((S,), np.float32)
            )
            self._cut_on = (
                np.asarray(cut_on_snap, bool)
                if cut_on_snap is not None
                else np.zeros((S,), bool)
            )
        else:
            # legacy single-array snapshot: attribute each slot's composite
            # multiplier to the ladder that owns the session there (μ-cut
            # sessions are exactly the cut_left keys; everything else was a
            # boost — the controller never persisted pre-split)
            self._boost_scale = np.ones((S,), np.float32)
            self._cut_scale = np.ones((S,), np.float32)
            self._ctrl_scale = np.ones((S,), np.float32)
            self._cut_on = np.zeros((S,), bool)
            if mu_scale is not None:
                cut_slots = {
                    sessions[sid] for sid in cut_snap if sid in sessions
                }
                for slot, v in enumerate(mu_scale):
                    v = float(v)
                    if v == 1.0:
                        continue
                    if slot in cut_slots:
                        self._cut_scale[slot] = v
                        self._cut_on[slot] = True
                    else:
                        self._boost_scale[slot] = v
        if self._moments is not None:
            # stringified keys resolve against the restored roster (active
            # sessions only — parked/quarantined re-seed at re-admission)
            self._moments.load_state_dict(
                moments_snap, key_map={str(sid): sid for sid in sessions}
            )
        self._sources = {}
        self._warm = {}
        self._drift_events = []
        self._n_drift_events = 0
        self._n_probes = 0
        self._n_probe_launches = 0
        self._probe_stacks = {}
        # the probe cadence resumes mid-cycle: a restored watchdog fires its
        # next probe exactly when the checkpointed one would have
        self._probe_ticks = int(lifecycle.get("probe_ticks") or 0)
        # bind_source(seek=True) replays these cursors into re-bound sources
        self._restored_positions = dict(lifecycle.get("sources") or {})
        # parked sessions resume in park order (= due-batch order): frozen
        # separators from the stacked checkpoint leaves, monitors/meta from
        # the snapshot, sources re-bound (and re-sought) via bind_source
        now = time.perf_counter()
        self._parked = {}
        for i, (sid, info) in enumerate(parked_snap):
            frozen = SMBGDState(
                B=parked_B[i], H_hat=parked_H[i], step=parked_step[i]
            )
            self._parked[sid] = ParkedSession(
                record=EvictionRecord(
                    state=frozen,
                    stats=SessionStats(admitted_at=now),
                    monitor=None,
                    reason=info.get("reason", "converged"),
                    tick=int(info.get("tick", 0)),
                ),
                source=None,
                monitor=DriftMonitor(**(info.get("monitor") or {})),
                meta=SessionMeta(**(info.get("meta") or {})),
            )
            pos = info.get("position")
            if pos is not None:
                self._restored_positions[sid] = int(pos)
        # quarantined sessions resume with their escalation memory intact:
        # last-known-good states from the stacked leaves, monitors/meta from
        # the snapshot, sources re-bound via bind_source (unbound quarantined
        # sessions skip probes, exactly like unbound parked ones)
        self._quarantined = {}
        for i, (sid, info) in enumerate(quar_snap):
            lkg = SMBGDState(B=quar_B[i], H_hat=quar_H[i], step=quar_step[i])
            self._quarantined[sid] = QuarantinedSession(
                record=EvictionRecord(
                    state=lkg,
                    stats=SessionStats(admitted_at=now),
                    monitor=None,
                    reason=info.get("reason", "quarantined"),
                    tick=int(info.get("tick", 0)),
                ),
                source=None,
                monitor=HealthMonitor(**(info.get("monitor") or {})),
                meta=SessionMeta(**(info.get("meta") or {})),
            )
            pos = info.get("position")
            if pos is not None:
                self._restored_positions[sid] = int(pos)
        # active sessions' ladder memory + μ-cut countdowns resume exactly
        self._health_mon = {
            sid: HealthMonitor(**health_snap[sid])
            for sid in sessions
            if sid in health_snap
        }
        self._cut_left = {
            sid: int(v) for sid, v in cut_snap.items() if sid in sessions
        }
        self._quar_ticks = int(lifecycle.get("quarantine_ticks") or 0)
        self._health_events = []
        self._n_health_events = 0
        self._n_rollbacks = 0
        self._n_diverged = 0
        self._n_degraded_ticks = 0
        self._n_source_retries = 0
        self._last_fault = {}
        queue_meta_orders = [
            e[1].get("order", 0)
            for e in queue_entries
            if isinstance(e, (list, tuple)) and len(e) == 2 and isinstance(e[1], dict)
        ]
        self._seq = 1 + max(
            [m.order for m in self._meta.values()]
            + [ps.meta.order for ps in self._parked.values()]
            + [qs.meta.order for qs in self._quarantined.values()]
            + queue_meta_orders,
            default=-1,
        )
        self._mixing = {}
        self._finished = {}
        # serving counters restart at restore time — per-session AND aggregate
        # (metrics must describe the restored epoch, not blend the old run)
        now = time.perf_counter()
        self._stats = {
            sid: SessionStats(admitted_at=now, activated_at=now)
            for sid in sessions
        }
        self._admit_time = {}
        self._n_ticks = 0
        self._total_samples = 0
        self._total_tick_s = 0.0
        self._last_tick_s = float("nan")
        self._n_evicted = 0
        self._n_auto_evicted = 0
        # SLO telemetry restarts with the epoch (sketch, deadline monitors,
        # miss window, empty-tick counters — same rule as the counters above)
        self._reset_slo()
        # resize provenance rides the lifecycle snapshot; the elastic
        # counters restart with the epoch like every other serving counter
        self._resize_history = [
            dict(e) for e in (lifecycle.get("resize_history") or [])
        ]
        self._n_grows = 0
        self._n_shrinks = 0
        self._n_compactions = 0
        self._elastic_ticks = 0
        self._last_resize_tick = None
        taken = set(sessions.values())
        self._free = [s for s in range(self.bank.n_streams - 1, -1, -1) if s not in taken]
        return got
