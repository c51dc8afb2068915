"""From a profiler trace to the phases of the program's tick loop.

The program marks the phases of ``SeparationService.run_tick`` and ``step``
with ``jax.profiler.TraceAnnotation`` spans named ``serve.*`` (the tuple
``repro.serve.engine.SPANS``), on the thread that calls them: they nest
inside the harness's ``tick`` span, on the device operations' clock.  Only
spans that start inside the traced window (the first ``tick``'s start to the
last ``tick``'s end) count, and every total is divided by the harness's
tick count:

* each span's self time: its duration less the union of its direct
  ``serve.*`` children;
* the JAX dispatches (``PjitFunction(...)``, ``DevicePut...``) nested in a
  ``serve.run_tick`` and in no other dispatch, also by name and by the
  innermost ``serve.*`` span around them;
* idle by phase: the device-idle time inside each span's self intervals,
  averaged over the devices;
* the longest idle gaps of the first device, each named by the innermost
  ``serve.*`` span open at its middle (else the harness's span).

A name is read up to its first ``#``, where a trace may fold in metadata.
A trace with no ``serve.*`` span in the window (a program without them)
reduces to None, and so do its metrics.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from benchlib import trace as trace_lib
from benchlib.trace import Event, Interval, Trace

PREFIX = "serve."
ROOT = "serve.run_tick"
DISPATCH = re.compile(r"^(PjitFunction\(|DevicePut)")
HARNESS = (trace_lib.TICK, trace_lib.PULL, trace_lib.FETCH, trace_lib.ADMIT)


def base_name(name: str) -> str:
    return name.split("#", 1)[0]


@dataclasses.dataclass
class Phases:
    """The traced window's ``serve.*`` spans, reduced."""

    ticks: int  # the harness's ticks in the window
    self_s: Dict[str, float]  # span name -> total self seconds
    total_s: Dict[str, float]  # span name -> total seconds
    counts: Dict[str, int]  # span name -> spans
    dispatch_counts: Dict[Tuple[str, str], int]  # (phase, dispatch) -> count
    idle_s: Dict[str, float]  # span name -> device-idle seconds in its self time
    tick_s: float  # the harness's ticks less their fetch, total seconds
    gaps: List[Tuple[str, float]]  # longest first: (phase, seconds)

    @property
    def dispatches(self) -> int:
        return sum(self.dispatch_counts.values())

    def ms_per_tick(self, *names: str) -> float:
        return sum(self.self_s.get(n, 0.0) for n in names) / self.ticks * 1e3


class _Busy:
    """The union of one device's busy intervals, for fast overlap sums."""

    def __init__(self, intervals: Sequence[Interval]):
        self.iv = trace_lib.union(intervals)
        self.starts = [s for s, _ in self.iv]

    def covered(self, lo: float, hi: float) -> float:
        i = max(bisect.bisect_right(self.starts, lo) - 1, 0)
        out = 0.0
        while i < len(self.iv) and self.iv[i][0] < hi:
            s, e = self.iv[i]
            out += max(0.0, min(e, hi) - max(s, lo))
            i += 1
        return out


def _minus(lo: float, hi: float, holes: Sequence[Interval]) -> List[Interval]:
    """``[lo, hi)`` less the union of ``holes``."""
    out, cursor = [], lo
    for s, e in trace_lib.union(holes):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def _nest(events: Sequence[Event]):
    """Walk one thread's events in start order; yield each with the events
    open around it, outermost first (a thread's spans nest properly)."""
    stack: List[Event] = []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and not (stack[-1].start <= e.start and e.end <= stack[-1].end):
            stack.pop()
        yield e, stack
        stack.append(e)


def reduce(trace: Trace, gaps: int = 10) -> Optional[Phases]:
    line = trace_lib._harness_line(trace)
    harness = trace.host[line]
    ticks = [e for e in harness if e.name == trace_lib.TICK]
    lo = min(t.start for t in ticks)
    hi = max(t.end for t in ticks)
    busy = [
        _Busy([(o.start, o.end) for o in ops if o.end > lo and o.start < hi])
        for _, ops in sorted(trace.devices.items())
    ]
    self_s: Dict[str, float] = defaultdict(float)
    total_s: Dict[str, float] = defaultdict(float)
    counts: Dict[str, int] = defaultdict(int)
    idle_s: Dict[str, float] = defaultdict(float)
    dispatch_counts: Dict[Tuple[str, str], int] = defaultdict(int)
    spans: List[Tuple[float, float, str]] = []
    for events in trace.host.values():
        ours = []
        for e in events:
            name = base_name(e.name)
            if name.startswith(PREFIX) or DISPATCH.match(name):
                ours.append(Event(name, e.start, e.end))
        children: Dict[int, List[Interval]] = defaultdict(list)
        mine: List[Event] = []
        for e, open_ in _nest(ours):
            if not lo <= e.start < hi:
                continue
            if e.name.startswith(PREFIX):
                mine.append(e)
                parent = next(
                    (p for p in reversed(open_) if p.name.startswith(PREFIX)), None
                )
                if parent is not None:
                    children[id(parent)].append((e.start, e.end))
            elif (any(p.name == ROOT for p in open_)
                  and not any(DISPATCH.match(p.name) for p in open_)):
                phase = [p.name for p in open_ if p.name.startswith(PREFIX)][-1]
                dispatch_counts[phase, e.name] += 1
        for e in mine:
            own = _minus(e.start, e.end, children.get(id(e), []))
            length = sum(b - a for a, b in own)
            self_s[e.name] += length * 1e-9
            total_s[e.name] += (e.end - e.start) * 1e-9
            counts[e.name] += 1
            if busy:
                on = sum(d.covered(a, b) for d in busy for a, b in own) / len(busy)
                idle_s[e.name] += (length - on) * 1e-9
            spans.append((e.start, e.end, e.name))
    if not spans:
        return None
    tick_ns = sum(t.end - t.start for t in ticks) - sum(
        e.end - e.start for e in harness
        if e.name == trace_lib.FETCH and lo <= e.start < hi
    )
    return Phases(
        ticks=len(ticks),
        self_s=dict(self_s),
        total_s=dict(total_s),
        counts=dict(counts),
        dispatch_counts=dict(dispatch_counts),
        idle_s=dict(idle_s),
        tick_s=tick_ns * 1e-9,
        gaps=_named_gaps(busy[0].iv if busy else [], lo, hi, spans, harness, gaps),
    )


def _named_gaps(busy0, lo, hi, spans, harness, k) -> List[Tuple[str, float]]:
    holes = sorted(_minus(lo, hi, busy0), key=lambda h: h[0] - h[1])[:k]
    outer = [(e.start, e.end, e.name) for e in harness if e.name in HARNESS]

    def innermost(t: float, among) -> Optional[str]:
        best = None
        for s, e, name in among:
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else None

    named = []
    for s, e in holes:
        t = (s + e) / 2
        named.append((innermost(t, spans) or innermost(t, outer) or "(no span)",
                      (e - s) * 1e-9))
    return named


# The per-layer metrics the phases feed, each the self milliseconds per
# tick of its spans, summed.  ``bench/phases.py`` prints them all; the
# readers in ``bench/metrics/`` take theirs from ``TracedRun.phase_metrics``.
METRICS = {
    "pull_ms_per_tick": ("serve.pull",),
    "stage_ms_per_tick": ("serve.stage", "serve.launch"),
    "ready_ms_per_tick": ("serve.ready",),
    "outputs_ms_per_tick": ("serve.outputs",),
    "sweeps_ms_per_tick": ("serve.moments", "serve.health", "serve.policy"),
    "lifecycle_ms_per_tick": (
        "serve.backfill", "serve.release", "serve.probe", "serve.autoscale",
    ),
}


def metrics(phases: Optional[Phases]) -> Optional[Dict[str, float]]:
    """``METRICS`` and ``dispatches_per_tick`` of reduced phases; None
    where the trace had no ``serve.*`` span."""
    if phases is None:
        return None
    out = {name: phases.ms_per_tick(*names) for name, names in METRICS.items()}
    out["dispatches_per_tick"] = phases.dispatches / phases.ticks
    return out
