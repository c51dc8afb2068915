"""The reduction from a profiler trace to the per-layer numbers: on hand-made
traces whose answers are known, and on a small trace recorded on a TPU v5e
(``data/paper_small.xplane.pb``: the paper cell at 16 sessions traced for
0.5 s, cut to the device's op line and the harness's thread)."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import registry, trace  # noqa: E402
from benchlib.cell import TracedRun  # noqa: E402
from benchlib.trace import Event, Trace  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data" / "paper_small.xplane.pb"
E = Event


def test_union_merges_overlaps_and_keeps_gaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def _two_ticks():
    # two ticks of 100 ns; device busy 10+10 in tick 1 (overlapping ops
    # count once), 30 in tick 2; a pull span covers the longest gap
    host = {"main": [
        E("tick", 0, 100), E("pull", 10, 60), E("fetch", 90, 100),
        E("tick", 100, 200), E("fetch", 180, 200),
    ]}
    dev = {"/device:TPU:0": [
        E("smbgd_step_bank.1", 60, 70), E("fusion.2", 65, 80),
        E("smbgd_step_bank.1", 150, 180),
    ]}
    return Trace(devices=dev, host=host)


def test_reduce_hand_made_trace():
    red = trace.reduce(_two_ticks())
    assert red.ticks == 2
    assert red.window_s == pytest.approx(200e-9)
    assert red.busy_s == pytest.approx(50e-9)
    assert red.ops_per_tick == pytest.approx(1.5)
    assert red.host_ms_per_tick == pytest.approx(((100 - 20) + (100 - 30)) / 2 * 1e-6)
    assert red.kernel_seconds(r"^smbgd_step_bank(\.\d+)?$") == pytest.approx(40e-9)
    assert red.top_ops(1) == [("smbgd_step_bank.1", pytest.approx(40e-9))]
    # gaps: [0,60) in the pull, [80,150) in tick 2, [180,200) in a fetch
    assert [name for name, _ in red.idle_gaps] == ["tick", "pull", "fetch"]
    assert red.idle_gaps[0][1] == pytest.approx(70e-9)


def test_readers_on_hand_made_trace():
    red = trace.reduce(_two_ticks())
    cfg = registry.config(ROOT, "paper_m4n2")
    run = TracedRun(red, cfg, "TPU v5 lite", sessions_per_tick=256)
    read = lambda name: registry.reader(ROOT, name)(run)
    assert read("device_idle_share") == pytest.approx(75.0)
    assert read("kernel_ms_per_tick") == pytest.approx(20e-6)
    share = read("smbgd_step_bank_roofline")
    assert share == pytest.approx(100 * 256 * 336 / 819e9 / 20e-9)
    empty = TracedRun(trace.reduce(Trace(devices={}, host=_two_ticks().host)),
                      cfg, "TPU v5 lite", 256)
    # no kernel in the trace: the readers report nothing, never 0
    assert registry.reader(ROOT, "kernel_ms_per_tick")(empty) is None
    assert registry.reader(ROOT, "smbgd_step_bank_roofline")(empty) is None


def test_reduce_recorded_v5e_trace():
    red = trace.reduce(trace.load(RECORDED))
    assert red.n_devices == 1
    assert red.ticks >= 2
    assert 0 < red.busy_s < red.window_s
    assert red.ops_per_tick > 1
    assert red.kernel_seconds(r"^smbgd_step_bank(\.\d+)?$") > 0
    assert 0 < red.host_ms_per_tick < red.window_s * 1e3
    assert len(red.top_ops(10)) == 10
    assert red.idle_gaps and all(s > 0 for _, s in red.idle_gaps)
    cfg = registry.config(ROOT, "paper_m4n2")
    share = registry.reader(ROOT, "smbgd_step_bank_roofline")(
        TracedRun(red, cfg, "TPU v5 lite", sessions_per_tick=16))
    assert 0 < share < 100
