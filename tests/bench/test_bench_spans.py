"""The reduction of the program's ``serve.*`` spans to the tick loop's
phases (``benchlib.spans``) and its metrics: on a hand-made
trace whose answers are known, on traces without the spans (the program
before it had them), and beside ``trace.reduce``, which the spans must not
move."""
import dataclasses
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import cell, registry, spans, trace  # noqa: E402
from benchlib.trace import Event, Trace  # noqa: E402
from test_bench_trace import RECORDED, _two_ticks  # noqa: E402

E = Event
PHASE_READERS = ("outputs_ms_per_tick", "dispatches_per_tick")


def _tick(t0, meta=""):
    """One tick of 100 ns at ``t0``: ``serve.run_tick`` [0, 90) with the
    phases below, the harness's fetch [90, 100).  Self times: run_tick 2,
    step 2, backfill 5, pull 15, stage 10, launch 2, ready 28, outputs 15,
    health 3, release 2, probe 3, autoscale 3.  Five dispatches: a nested
    pjit counts once, and the one during the fetch is outside run_tick."""
    ev = lambda name, s, e: E(name, t0 + s, t0 + e)
    return [
        ev("tick", 0, 100),
        ev("serve.run_tick" + meta, 0, 90),
        ev("serve.backfill", 0, 5),
        ev("serve.pull", 5, 20),
        ev("pull", 6, 10),
        ev("serve.step", 20, 80),
        ev("serve.stage", 20, 30),
        ev("PjitFunction(convert_element_type)", 21, 23),
        ev("PjitFunction(convert_element_type)", 21.5, 22.5),
        ev("DevicePut", 24, 26),
        ev("serve.launch", 30, 32),
        ev("PjitFunction(bank_step)", 30, 31),
        ev("serve.ready", 32, 60),
        ev("serve.outputs", 60, 75),
        ev("PjitFunction(less)", 61, 62),
        ev("PjitFunction(squeeze)", 62, 63),
        ev("serve.health", 75, 78),
        ev("serve.release", 80, 82),
        ev("serve.probe", 82, 85),
        ev("serve.autoscale", 85, 88),
        ev("fetch", 90, 100),
        ev("PjitFunction(copy)", 92, 93),
    ]


def _traced():
    host = _tick(0, meta="#tick=0#") + _tick(100, meta="#tick=1#") + [
        # before the first tick and after the last: outside the window
        E("serve.run_tick", -50, -10), E("serve.pull", -40, -20),
        E("PjitFunction(add)", -30, -25),
        E("serve.step", 250, 260), E("serve.stage", 250, 255),
    ]
    # the device works through each ready and briefly in each outputs
    dev = {"/device:TPU:0": [
        E("smbgd_step_bank.1", 32, 50), E("copy.1", 70, 72),
        E("smbgd_step_bank.1", 132, 150), E("copy.1", 170, 172),
    ]}
    return Trace(devices=dev, host={"main": host})


def test_self_time_is_the_span_less_its_children():
    ph = spans.reduce(_traced())
    assert ph.ticks == 2
    want = {
        "serve.run_tick": 2, "serve.step": 2, "serve.backfill": 5,
        "serve.pull": 15, "serve.stage": 10, "serve.launch": 2,
        "serve.ready": 28, "serve.outputs": 15, "serve.health": 3,
        "serve.release": 2, "serve.probe": 3, "serve.autoscale": 3,
    }
    assert ph.self_s == pytest.approx({k: 2 * v * 1e-9 for k, v in want.items()})
    assert ph.total_s["serve.run_tick"] == pytest.approx(180e-9)
    assert ph.total_s["serve.step"] == pytest.approx(120e-9)
    assert ph.tick_s == pytest.approx(180e-9)  # the ticks less their fetch
    assert ph.ms_per_tick("serve.stage", "serve.launch") == pytest.approx(12e-6)


def test_spans_outside_the_window_are_ignored():
    ph = spans.reduce(_traced())
    assert ph.counts == {name: 2 for name in ph.self_s}
    assert ph.dispatches == 10


def test_metadata_after_a_hash_is_stripped():
    ph = spans.reduce(_traced())
    assert "serve.run_tick" in ph.counts
    assert not [name for name in ph.counts if "#" in name]
    assert spans.base_name("serve.run_tick#tick=7#") == "serve.run_tick"


def test_nested_dispatches_count_once():
    tr = _traced()
    ph = spans.reduce(tr)
    assert ph.dispatches / ph.ticks == 5
    assert ph.dispatch_counts == {
        ("serve.stage", "PjitFunction(convert_element_type)"): 2,
        ("serve.stage", "DevicePut"): 2,
        ("serve.launch", "PjitFunction(bank_step)"): 2,
        ("serve.outputs", "PjitFunction(less)"): 2,
        ("serve.outputs", "PjitFunction(squeeze)"): 2,
    }
    # without the outer pjit, the inner one counts in its place
    host = [e for e in tr.host["main"]
            if not (e.name == "PjitFunction(convert_element_type)" and e.end - e.start == 2)]
    assert spans.reduce(Trace(tr.devices, {"main": host})).dispatches == 10


def test_idle_by_phase_and_gaps():
    ph = spans.reduce(_traced())
    assert ph.idle_s["serve.ready"] == pytest.approx(2 * 10e-9)
    assert ph.idle_s["serve.outputs"] == pytest.approx(2 * 13e-9)
    assert ph.idle_s["serve.pull"] == pytest.approx(2 * 15e-9)
    assert [name for name, _ in ph.gaps] == [
        "serve.backfill", "serve.pull", "serve.autoscale", "serve.outputs",
        "serve.outputs",
    ]
    assert [s for _, s in ph.gaps] == pytest.approx([60e-9, 32e-9, 28e-9, 20e-9, 20e-9])


def test_readers_on_hand_made_trace():
    read = spans.metrics(spans.reduce(_traced()))
    assert read == pytest.approx({
        "pull_ms_per_tick": 15e-6, "stage_ms_per_tick": 12e-6,
        "ready_ms_per_tick": 28e-6, "outputs_ms_per_tick": 15e-6,
        "sweeps_ms_per_tick": 3e-6, "lifecycle_ms_per_tick": 13e-6,
        "dispatches_per_tick": 5.0,
    })


@pytest.mark.parametrize("source", ["hand-made", "recorded"])
def test_readers_report_nothing_without_program_spans(source):
    tr = _two_ticks() if source == "hand-made" else trace.load(RECORDED)
    trace.reduce(tr)  # the harness's own reduction still reads it
    assert spans.reduce(tr) is None
    assert spans.metrics(None) is None
    run = cell.TracedRun(trace.reduce(tr), {}, "TPU v5 lite", 2, trace=tr)
    for name in PHASE_READERS:
        assert registry.reader(ROOT, name)(run) is None


@pytest.mark.parametrize("with_trace", [True, False])
def test_phase_readers_of_the_benchmark(with_trace):
    """The phase metrics that ``BENCHMARK.json`` lists, read by their
    readers from the loaded trace a traced run hands them; nothing where
    the run holds no trace."""
    tr = _traced()
    run = cell.TracedRun(trace.reduce(tr), {}, "TPU v5 lite", 2,
                         trace=tr if with_trace else None)
    listed = {m["name"] for m in registry.benchmark(ROOT)["per_layer"]}
    assert set(PHASE_READERS) <= listed
    got = {name: registry.reader(ROOT, name)(run) for name in PHASE_READERS}
    if with_trace:
        assert got == pytest.approx({"outputs_ms_per_tick": 15e-6,
                                     "dispatches_per_tick": 5.0})
    else:
        assert got == {name: None for name in PHASE_READERS}


def test_program_spans_leave_the_harness_reduction_unchanged():
    plain = _two_ticks()
    spanned = _two_ticks()
    spanned.host["main"] += [
        E("serve.run_tick", 0, 90), E("serve.pull", 10, 60),
        E("serve.step", 60, 88), E("serve.ready", 62, 80),
        E("serve.run_tick", 100, 180), E("serve.step", 140, 179),
    ]
    a, b = trace.reduce(plain), trace.reduce(spanned)
    numbers = lambda red: {
        k: v for k, v in dataclasses.asdict(red).items() if k != "idle_gaps"
    }
    assert numbers(a) == numbers(b)
    assert [s for _, s in a.idle_gaps] == [s for _, s in b.idle_gaps]
    assert b.ticks == 2


def test_phase_summary_of_hand_made_trace():
    import phases

    got = phases.summary(spans.reduce(_traced()))
    assert list(got["phases"])[0] == "serve.ready"  # most self time first
    assert got["phases"]["serve.ready"] == pytest.approx(
        {"self_ms": 28e-6, "total_ms": 28e-6, "idle_ms": 10e-6, "count": 2}
    )
    assert got["run_tick_cover"] == pytest.approx(1.0)
    assert got["run_tick_self"] == pytest.approx(2 / 90)
    assert got["metrics"]["dispatches_per_tick"] == 5
    assert got["metrics"]["ready_ms_per_tick"] == pytest.approx(28e-6)
    assert got["dispatches"][0][2] == 1  # per tick
    assert got["gaps"][0] == ("serve.backfill", pytest.approx(60e-9))
