"""From a profiler trace to the per-layer numbers.

The harness marks each served tick, each source pull, each output fetch
and each batch of admissions between ticks with a
``jax.profiler.TraceAnnotation`` (``tick``, ``pull``, ``fetch``, ``admit``), so
its spans sit on the same clock as the device's operations.  The traced
window runs from the first tick's start to the last tick's end.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane), averaged over
  the devices;
* idle share: 1 − busy / window;
* operations per tick, and the time of a named kernel per tick;
* host time per tick: a tick's span less the device-busy time inside it;
* the breakdown: the device operations that took most time, and the
  longest idle gaps, each named by the innermost host event that was open
  in the middle of the gap on the harness's thread.
"""
from __future__ import annotations

import dataclasses
import glob
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
TICK, PULL, FETCH, ADMIT = "tick", "pull", "fetch", "admit"

Interval = Tuple[float, float]  # (start_ns, end_ns)


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float  # ns


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]  # plane name -> device operations
    host: Dict[str, List[Event]]  # host line name -> events


def op_name(name: str) -> str:
    """A TPU trace names each operation by its whole HLO instruction
    (``%smbgd_step_bank.1 = (...) custom-call(...)``); keep the name."""
    if name.startswith("%") and " = " in name:
        return name[1:name.index(" = ")]
    return name


def find_xplane(log_dir) -> Path:
    found = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(found[-1])


def load(path) -> Trace:
    """Read an ``.xplane.pb`` with JAX's own reader."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: Dict[str, List[Event]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [
                        Event(op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events
                    ]
            devices[plane.name] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host[line.name] = [
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                ]
    return Trace(devices=devices, host=host)


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge overlapping intervals, sorted by start."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(intervals: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


@dataclasses.dataclass
class Reduced:
    """The traced window, reduced: what the per-layer readers read."""

    ticks: int
    window_s: float
    busy_s: float  # averaged over devices
    ops_per_tick: float
    host_ms_per_tick: float
    op_seconds: Dict[str, float]  # device op name -> total seconds (all devices)
    op_counts: Dict[str, int]
    idle_gaps: List[Tuple[str, float]]  # longest first
    n_devices: int

    def kernel_seconds(self, pattern: str) -> float:
        """Total device seconds of the operations whose name matches."""
        rx = re.compile(pattern)
        return sum(s for name, s in self.op_seconds.items() if rx.search(name))

    def top_ops(self, k: int = 10) -> List[Tuple[str, float]]:
        return sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:k]


def _harness_line(trace: Trace) -> str:
    for name, events in trace.host.items():
        if any(e.name == TICK for e in events):
            return name
    raise ValueError("no tick spans in the trace")


def _innermost(events: Sequence[Event], t: float) -> str:
    best = None
    for e in events:
        if e.start <= t < e.end and (best is None or e.end - e.start < best.end - best.start):
            best = e
    return best.name if best is not None else "(no host event)"


def reduce(trace: Trace, gaps: int = 10) -> Reduced:
    line = _harness_line(trace)
    host = trace.host[line]
    ticks = sorted((e for e in host if e.name == TICK), key=lambda e: e.start)
    lo, hi = ticks[0].start, ticks[-1].end
    window = hi - lo
    busy_by_dev = {}
    op_seconds: Dict[str, float] = defaultdict(float)
    op_counts: Dict[str, int] = defaultdict(int)
    n_ops = 0
    for plane, ops in trace.devices.items():
        inside = [op for op in ops if op.end > lo and op.start < hi]
        for op in inside:
            op_seconds[op.name] += (min(op.end, hi) - max(op.start, lo)) * 1e-9
            op_counts[op.name] += 1
        n_ops += len(inside)
        busy_by_dev[plane] = union(_clip([(o.start, o.end) for o in inside], lo, hi))
    n_dev = max(len(busy_by_dev), 1)
    busy = sum(_length(b) for b in busy_by_dev.values()) / n_dev
    host_ns = []
    for t in ticks:
        inside = sum(
            _length(_clip(b, t.start, t.end)) for b in busy_by_dev.values()
        ) / n_dev
        host_ns.append((t.end - t.start) - inside)
    # idle gaps of the first device, named by what the harness thread was in
    first = sorted(busy_by_dev)[0] if busy_by_dev else None
    busy0 = busy_by_dev.get(first, [])
    holes, cursor = [], lo
    for s, e in busy0:
        if s > cursor:
            holes.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        holes.append((cursor, hi))
    holes.sort(key=lambda h: h[0] - h[1])
    named = [
        (_innermost(host, (s + e) / 2), (e - s) * 1e-9) for s, e in holes[:gaps]
    ]
    return Reduced(
        ticks=len(ticks),
        window_s=window * 1e-9,
        busy_s=busy * 1e-9,
        ops_per_tick=n_ops / n_dev / len(ticks),
        host_ms_per_tick=sum(host_ns) / len(host_ns) * 1e-6,
        op_seconds=dict(op_seconds),
        op_counts=dict(op_counts),
        idle_gaps=named,
        n_devices=len(busy_by_dev),
    )
