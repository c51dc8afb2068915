"""Serving: the drift-aware separation pipeline.

Public API of the separation side (the paper's "deployment in hardware"
mandate, grown into an end-to-end adaptive service):

  * ``SeparationService``   — continuous-batching front door for a
    ``stream.SeparatorBank``: admission, scheduling, convergence lifecycle,
    drift watchdog, fault containment, ``run_tick()`` pull ingestion.
  * ``ConvergencePolicy`` / ``ConvergenceMonitor`` — when is a session done.
  * ``DriftPolicy`` / ``DriftMonitor`` / ``DriftEvent`` — when has a done
    session drifted, and what to do about it (μ boost / warm re-admission).
  * ``HealthPolicy`` / ``HealthMonitor`` / ``HealthEvent`` — when has a
    session gone BAD (in-kernel health word: non-finite state / blow-up),
    and the escalation ladder: rollback-to-shadow + μ cut → quarantine →
    evict ``"diverged"``.
  * ``MomentPolicy`` / ``MomentController`` — moment-scaled adaptive μ over
    the bank's in-kernel kurtosis telemetry (``SeparatorBank(moments=True)``):
    fast/slow EMA kurtosis per session, μ × clamp(deviation^gain), annealing
    as re-convergence pulls the estimate home.  Composition with the other μ
    writers is pinned: a health μ-cut wins while live; drift boost and the
    controller multiply.
  * ``AdmissionScheduler`` (FIFO) / ``PriorityScheduler`` /
    ``DeadlineScheduler`` + ``SessionMeta`` — who waits, who activates.
  * ``AutoscalePolicy`` / ``ResizeDecision`` — telemetry-driven elastic
    capacity: the service grows/shrinks/compacts its bank from queue depth
    and deadline-miss pressure (hysteresis bands + cooldown; see
    ``serve.elastic`` and ``SeparationService.grow``/``shrink``/``compact``).
  * ``SLOPolicy`` / ``DeadlineMonitor`` / ``SLOEvent`` / ``LatencySketch`` /
    ``TickTimer`` + ``slo.replay`` — real-time budgets over TIME-TO-READY
    tick latency (p50/p99/p999, deadline misses, shed/gate load control) and
    deterministic replay of recorded loads (``data.sources.RecordingSource``
    → ``save_recording``/``load_recording``).
  * ``EvictionRecord`` / ``ParkedSession`` / ``QuarantinedSession`` — what
    leaves a slot carries.

Signal feeds (``data.sources``): bind a ``SignalSource`` at ``admit`` time
and drive the whole pipeline with ``run_tick()``.  Flaky feeds wrap in
``data.resilience.ResilientSource`` (bounded retry/backoff/stall-timeout);
``data.resilience.FaultInjector`` is the chaos-test harness.
"""
from repro.serve.drift import DriftEvent, DriftMonitor, DriftPolicy
from repro.serve.elastic import AutoscalePolicy, ResizeDecision
from repro.serve.engine import (
    ConvergenceMonitor,
    ConvergencePolicy,
    EvictionRecord,
    ParkedSession,
    QuarantinedSession,
    SeparationService,
    SessionStats,
)
from repro.serve.health import HealthEvent, HealthMonitor, HealthPolicy
from repro.serve.moments import MomentController, MomentPolicy
from repro.serve.scheduling import (
    AdmissionScheduler,
    DeadlineScheduler,
    PriorityScheduler,
    SchedulerContext,
    SessionMeta,
)
from repro.serve.slo import (
    DeadlineMonitor,
    LatencySketch,
    SLOEvent,
    SLOPolicy,
    TickTimer,
    replay,
)

__all__ = [
    "AdmissionScheduler",
    "AutoscalePolicy",
    "ConvergenceMonitor",
    "ConvergencePolicy",
    "DeadlineMonitor",
    "DeadlineScheduler",
    "DriftEvent",
    "DriftMonitor",
    "DriftPolicy",
    "EvictionRecord",
    "HealthEvent",
    "HealthMonitor",
    "HealthPolicy",
    "LatencySketch",
    "MomentController",
    "MomentPolicy",
    "ParkedSession",
    "PriorityScheduler",
    "QuarantinedSession",
    "ResizeDecision",
    "SLOEvent",
    "SLOPolicy",
    "SchedulerContext",
    "SeparationService",
    "SessionMeta",
    "SessionStats",
    "TickTimer",
    "replay",
]
