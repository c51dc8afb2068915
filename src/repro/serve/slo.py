"""Real-time SLO layer: time-to-ready tick timing, streaming latency
quantiles, deadline budgets, and deterministic load replay.

The paper's headline numbers are throughput and clock headroom — deployment
cares whether every tick lands inside a real-time budget (the CORTEX-style
harness of ROADMAP item 2: BCI kernels benchmarked under deadlines with
latency / jitter / deadline-miss telemetry and a recorded-stream replayer).
This module is that harness's measurement core; ``serve.SeparationService``
wires it into every tick:

  * ``TickTimer``     — the time-to-ready clock.  JAX dispatches
    asynchronously: ``perf_counter()`` around a jitted call measures enqueue
    latency, not compute.  The timer stops the clock only after a
    ``block_until_ready`` on a designated telemetry leaf (the service uses
    ``BankState.conv`` — a tiny ``(S,)`` float vector whose readiness implies
    the whole bank program retired), so tick latencies are real on any
    backend regardless of ``block_ticks``.  ``sync_every=k`` samples the sync
    1-in-k: only synced ticks are *timed* (fed to the sketch, deadline-
    checked, counted in ``mean_tick_s``); the k−1 unsynced ticks between them
    keep no latency record at all — sampled mode trades telemetry density
    for fewer timer syncs, never fabricates numbers.  A served tick still
    waits for its step: ``SeparationService.step`` reads the step's outputs
    to the host.
  * ``LatencySketch`` — streaming p50/p99/p999 over tick latencies, two
    horizons at once: an exact sliding window (last ``window`` timed ticks,
    ``np.quantile`` on demand) and a bounded-memory lifetime histogram with
    log-spaced bins (HDR-style: relative error ≤ one bin width, ~2.6% at the
    default 90 bins/decade — tails keep their resolution however long the
    service runs).
  * ``SLOPolicy``     — the budget + escalation config: a per-tick
    ``deadline_budget_s`` (timed ticks over budget increment
    ``n_deadline_misses``), per-session miss tracking (``DeadlineMonitor``,
    the ``HealthMonitor``-style sliding window), and two load-control levers
    over the windowed miss rate: ``shed`` preempts the worst-missing session
    (reason ``"shed"``), ``gate_admissions`` holds backfills/direct
    admissions while the service is over its miss-rate ceiling.
  * ``SLOEvent``      — the observability record for shed/gate actions
    (``SeparationService.slo_events``; per-tick misses are counters + sketch
    entries, not events — a sustained overload must not grow a list).
  * ``replay``        — drives a service through a ``data.sources``
    ``Recording`` (admit each session at its recorded tick with its recorded
    scheduling metadata, ``run_tick`` until every recorded feed drains):
    the load test that turns a captured production stream into a
    reproducible SLO measurement (``benchmarks/stream_throughput.py --slo``).
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Dict, Hashable, List, Optional, Tuple

import numpy as np


class LatencySketch:
    """Streaming quantiles over a latency series, windowed + lifetime.

    ``add`` is O(1): append to a bounded deque (the exact sliding window) and
    increment one bin of a log-spaced lifetime histogram covering
    ``[lo, hi)`` seconds with ``bins_per_decade`` bins per decade.  Lifetime
    quantiles return the geometric midpoint of the selected bin, so their
    relative error is bounded by the bin width (``10**(1/bins_per_decade) −
    1``, ~2.6% at the default 90) — memory stays a few KB forever, unlike
    keeping every sample.  Windowed quantiles are exact ``np.quantile`` over
    the retained samples.  Samples outside ``[lo, hi)`` clamp to the edge
    bins (their windowed quantiles stay exact)."""

    def __init__(
        self,
        window: int = 256,
        lo: float = 1e-6,
        hi: float = 1e3,
        bins_per_decade: int = 90,
    ):
        if window < 1:
            raise ValueError("window must be >= 1")
        if not (0.0 < lo < hi):
            raise ValueError(f"need 0 < lo < hi, got lo={lo} hi={hi}")
        if bins_per_decade < 1:
            raise ValueError("bins_per_decade must be >= 1")
        self.window = int(window)
        self.lo = float(lo)
        self.hi = float(hi)
        self.bins_per_decade = int(bins_per_decade)
        n_decades = math.log10(hi / lo)
        self._n_bins = max(1, int(math.ceil(n_decades * bins_per_decade)))
        self._counts = np.zeros((self._n_bins,), dtype=np.int64)
        self._recent: collections.deque = collections.deque(maxlen=window)
        self._n = 0

    def _bin_of(self, x: float) -> int:
        if x <= self.lo:
            return 0
        idx = int(math.log10(x / self.lo) * self.bins_per_decade)
        return min(max(idx, 0), self._n_bins - 1)

    def add(self, x: float) -> None:
        x = float(x)
        if math.isnan(x):
            return  # a clock anomaly must not poison the tail quantiles
        self._recent.append(x)
        self._counts[self._bin_of(x)] += 1
        self._n += 1

    @property
    def count(self) -> int:
        """Lifetime samples folded in."""
        return self._n

    @property
    def window_count(self) -> int:
        return len(self._recent)

    def quantile(self, q: float) -> float:
        """Lifetime quantile (log-binned; relative error ≤ one bin width)."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"q must be in [0, 1], got {q}")
        if self._n == 0:
            return float("nan")
        # rank of the q-th sample (nearest-rank), found by cumulative count
        rank = min(max(int(math.ceil(q * self._n)), 1), self._n)
        cum = np.cumsum(self._counts)
        b = int(np.searchsorted(cum, rank))
        edge_lo = self.lo * 10.0 ** (b / self.bins_per_decade)
        edge_hi = self.lo * 10.0 ** ((b + 1) / self.bins_per_decade)
        return math.sqrt(edge_lo * edge_hi)

    def window_quantile(self, q: float) -> float:
        """Exact quantile over the last ``window`` samples."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"q must be in [0, 1], got {q}")
        if not self._recent:
            return float("nan")
        return float(np.quantile(np.asarray(self._recent), q))

    def summary(self) -> Dict[str, float]:
        """The metrics-surface view: windowed p50/p99/p999 (exact) plus their
        lifetime twins (``*_life``, log-binned)."""
        return {
            "p50_tick_s": self.window_quantile(0.50),
            "p99_tick_s": self.window_quantile(0.99),
            "p999_tick_s": self.window_quantile(0.999),
            "p50_tick_s_life": self.quantile(0.50),
            "p99_tick_s_life": self.quantile(0.99),
            "p999_tick_s_life": self.quantile(0.999),
        }

    def reset(self) -> None:
        self._counts[:] = 0
        self._recent.clear()
        self._n = 0


class TickTimer:
    """Time-to-ready tick clock with 1-in-k sampled sync.

    ``start()`` stamps the dispatch; ``stop(sync_leaf=...)`` blocks on the
    designated telemetry leaf when this tick is *due* for a sync (every tick
    at the default ``sync_every=1``; every k-th tick otherwise) and returns
    ``(dt, timed)``.  ``timed=False`` means the clock stopped at dispatch —
    the caller must NOT record ``dt`` as a latency (sampled-out ticks carry
    no latency information, by design).  A caller that already synchronized
    (``block_ticks=True``) passes ``already_synced=True``: the tick is timed
    without a second block, and the sampling cadence still advances."""

    def __init__(self, sync_every: int = 1):
        if sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        self.sync_every = int(sync_every)
        self._n = 0  # ticks observed (drives the 1-in-k cadence)
        self._t0: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()

    def stop(self, sync_leaf=None, already_synced: bool = False) -> Tuple[float, bool]:
        if self._t0 is None:
            raise RuntimeError("stop() without start()")
        due = already_synced or (self._n % self.sync_every == 0)
        self._n += 1
        timed = already_synced
        if due and not already_synced and sync_leaf is not None:
            import jax  # deferred: the sketch/policy side stays jax-free

            jax.block_until_ready(sync_leaf)
            timed = True
        dt = time.perf_counter() - self._t0
        self._t0 = None
        return dt, timed


@dataclasses.dataclass(frozen=True)
class SLOPolicy:
    """Latency-SLO configuration for ``SeparationService``.

    Telemetry (the sketch + time-to-ready sync) is always on; a policy is
    attached by default.  ``deadline_budget_s`` arms the deadline machinery:
    every *timed* tick over budget increments ``n_deadline_misses``, stamps
    each served session's ``DeadlineMonitor``, and feeds the windowed miss
    rate (last ``miss_window`` timed ticks).  Load control is opt-in:

      * ``shed=True`` — when a miss lands while the windowed miss rate
        exceeds ``max_miss_rate``, preempt the active session with the most
        window-resident misses (reason ``"shed"``; ties broken toward lower
        priority then younger admission), at most once per ``shed_cooldown``
        ticks.  Shed sessions land in ``finished`` with their state — the
        caller decides whether to re-admit when load subsides.
      * ``gate_admissions=True`` — while the rate is over the ceiling, free
        slots are NOT backfilled and direct admissions queue instead of
        activating: capacity drains until the window recovers.

    Both levers need a budget (they act on misses); arming them without one
    raises.  ``sync_every`` samples the time-to-ready sync 1-in-k (see
    ``TickTimer``); with k > 1 the deadline check inherits the sampling —
    only timed ticks can miss."""

    deadline_budget_s: Optional[float] = None
    sync_every: int = 1
    window: int = 256  # latency-sketch sliding window (timed ticks)
    miss_window: int = 64  # miss-rate window (timed ticks)
    max_miss_rate: float = 0.5  # shed/gate ceiling on the windowed rate
    shed: bool = False
    gate_admissions: bool = False
    shed_cooldown: int = 32  # min ticks between sheds (let the window react)

    def __post_init__(self) -> None:
        if self.deadline_budget_s is not None and self.deadline_budget_s <= 0:
            raise ValueError("deadline_budget_s must be > 0")
        if self.sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        if self.window < 1:
            raise ValueError("window must be >= 1")
        if self.miss_window < 1:
            raise ValueError("miss_window must be >= 1")
        if not (0.0 < self.max_miss_rate <= 1.0):
            raise ValueError("max_miss_rate must be in (0, 1]")
        if self.shed_cooldown < 1:
            raise ValueError("shed_cooldown must be >= 1")
        if (self.shed or self.gate_admissions) and self.deadline_budget_s is None:
            raise ValueError(
                "shed/gate_admissions act on deadline misses: set "
                "deadline_budget_s to arm them"
            )


@dataclasses.dataclass
class DeadlineMonitor:
    """Per-session streaming deadline record (host-side,
    ``dataclasses.asdict``-serializable — the ``HealthMonitor`` idiom).

    ``recent`` holds the service-tick stamps of misses still inside the
    policy's ``miss_window``; ``served``/``misses`` are lifetime counters.
    The windowed count returned by ``record`` is what the shed victim
    selection ranks on — the session present during the most recent misses
    is the one whose work is (probabilistically) blowing the budget."""

    served: int = 0
    misses: int = 0
    recent: List[int] = dataclasses.field(default_factory=list)

    def record(self, tick: int, missed: bool, policy: SLOPolicy) -> int:
        """Fold one timed tick in; returns the window-resident miss count."""
        self.served += 1
        self.recent = [
            t for t in self.recent if tick - t < policy.miss_window
        ]
        if missed:
            self.misses += 1
            self.recent.append(int(tick))
        return len(self.recent)


@dataclasses.dataclass
class SLOEvent:
    """One load-control action: who (``None`` = service-wide), when, the
    latency/budget that triggered it, what we did (``"shed"`` — a session
    preempted; ``"gate"`` — backfill held while over the ceiling), and the
    windowed miss rate at the time."""

    session_id: Optional[Hashable]
    tick: int
    tick_s: float
    budget_s: float
    action: str
    miss_rate: float = 0.0


def replay(
    svc,
    recording,
    extra_ticks: int = 0,
    max_ticks: int = 100_000,
) -> List[Dict]:
    """Drive ``svc`` through a recorded load, deterministically.

    ``recording`` is a ``data.sources.Recording`` (``load_recording``): each
    session is admitted at its recorded admit tick with its recorded
    scheduling metadata, bound to its ``RecordedSource``, and served via
    ``run_tick`` until every recorded feed drains (drained feeds evict with
    reason ``"exhausted"``, exactly like the live run) — plus ``extra_ticks``
    trailing ticks for probe/queue settling.  Returns the per-tick output
    dicts, so a replay is comparable block-for-block against the live run it
    was captured from.  Recordings without admit events admit every session
    before the first tick."""
    events = [
        dict(e) for e in (recording.events or []) if e.get("action") == "admit"
    ]
    if not events:
        events = [{"sid": sid, "tick": 0} for sid in recording.sources]
    pending = sorted(
        events, key=lambda e: (int(e.get("tick", 0)), e.get("order", 0))
    )
    missing = [e["sid"] for e in pending if e["sid"] not in recording.sources]
    if missing:
        raise ValueError(f"admit events for unrecorded sessions: {missing}")
    outputs: List[Dict] = []
    settle = 0
    for tick in range(max_ticks):
        while pending and int(pending[0].get("tick", 0)) <= tick:
            e = pending.pop(0)
            svc.admit(
                e["sid"],
                source=recording.sources[e["sid"]],
                tenant=e.get("tenant"),
                priority=float(e.get("priority", 0.0)),
                deadline=e.get("deadline"),
            )
        outputs.append(svc.run_tick())
        done = (
            not pending
            and svc.n_active == 0
            and svc.n_queued == 0
            and not svc.parked
            and not svc.quarantined
        )
        if done:
            settle += 1
            if settle > extra_ticks:
                break
        else:
            settle = 0
    return outputs
