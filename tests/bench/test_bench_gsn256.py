"""The 256-channel EEG deployment (``eeg_gsn256``): its files load by name,
its traffic builds at a CPU size, the served path at ``m = n = 256`` agrees
with the plain reference step by step, and the ``serve.h2d`` span's reader
reads the span and nothing where the span is missing."""
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import cell, reference, registry, spans, trace  # noqa: E402
from benchlib.trace import Event  # noqa: E402
from benchlib.traffic import RingSource, make_traffic  # noqa: E402
from test_bench_spans import _traced  # noqa: E402
from test_bench_trace import _two_ticks  # noqa: E402

CONFIG = "eeg_gsn256"
CELL = "eeg_gsn256.steady"


def _traffic(slots, seed, ring_blocks=None):
    config = registry.config(ROOT, CONFIG)
    mix = registry.traffic(ROOT, registry.workload(ROOT, CELL)["traffic"])
    if ring_blocks is not None:
        mix = dict(mix, ring_blocks=ring_blocks)
    signals = registry.signal_model(ROOT, config["signals"]["model"])
    return config, make_traffic(config, mix, seed, signals, slots=slots)


def test_config_limits_and_signals_load_by_name():
    config = registry.config(ROOT, CONFIG)
    eeg = registry.config(ROOT, "eeg_bci4_2a")
    assert set(config) == set(eeg)  # written like the 22-channel file
    assert (config["m"], config["n"], config["P"]) == (256, 256, 8)
    assert config["published"]["channels"] == 256
    assert set(config["reduced"]) == {"sessions"}
    assert config["signals"] == eeg["signals"]
    limits = registry.limits(ROOT, CONFIG)
    assert limits["config"] == CONFIG
    assert set(limits["limits"]) == set(registry.limits(ROOT, "eeg_bci4_2a")["limits"])
    # the control reads 1 on every share: each limit must fail it
    assert all(v < 1 for k, v in limits["limits"].items() if "_ctl_" in k)
    wl = registry.workload(ROOT, CELL)
    assert (wl["config"], wl["traffic"], wl["chips"]) == (CONFIG, "steady", 1)
    assert callable(registry.signal_model(ROOT, config["signals"]["model"]))


def test_traffic_builds_at_a_slots_override():
    config, t = _traffic(slots=4, seed=2**31 + 77)
    ring = registry.traffic(ROOT, "steady")["ring_blocks"]
    assert t.blocks.shape == (4, ring, 256, 8)
    assert t.B0.shape == (4, 256, 256) and t.mixing.shape == (4, 256, 256)
    assert np.isfinite(t.blocks).all()
    np.testing.assert_allclose(np.linalg.norm(t.mixing, axis=-1), 1.0, rtol=1e-5)
    # b0_scale keeps the starting separator's distance from I near the
    # 22-channel configuration's (about 4.4 at its scale of 0.5)
    dist = np.linalg.norm(t.B0 - np.eye(256)[None], ord=2, axis=(1, 2))
    assert np.all((3.5 < dist) & (dist < 5.5)), dist


# Tolerances of the served path against the reference at "highest", each
# relative to the session's largest reference value.  On the CPU over six
# ticks of 16 sessions (three seeds) the served path read at most 7.4e-7
# (outputs), 2.6e-7 (B) and 5.6e-7 (Ĥ); the three-pass bf16 control read at
# least 1.24e-5, 7.4e-6 and 3.3e-5.  Each tolerance sits at least four
# times above the first and 3.7 times below the second.
Y_TOL, B_TOL, H_TOL = 3e-6, 2e-6, 6e-6
TICKS, SESSIONS = 6, 16


def _rel(got, ref, axes):
    return np.abs(got - ref).max(axis=axes) / np.abs(ref).max(axis=axes)


def _agrees(Y, B, H, ref) -> bool:
    """Every session's every step within the tolerances: outputs ``Y (K, S,
    P, n)``, final ``B`` and ``Ĥ``."""
    return bool(
        (_rel(Y, ref["Y"], (2, 3)) <= Y_TOL).all()
        and (_rel(B, ref["B"], (1, 2)) <= B_TOL).all()
        and (_rel(H, ref["H"], (1, 2)) <= H_TOL).all()
    )


@pytest.fixture(scope="module")
def served_256():
    """``run_tick`` over the served bank (``cell.build_bank``: the fused
    megakernel, moments, the blow-up bound) at m = n = 256 with 16 sessions,
    admitted from the seed's ``B0``; the interpreter's VMEM budget resolves
    ``block_s`` to 8, so the grid runs two stream blocks."""
    from repro.core.smbgd import SMBGDState
    from repro.serve import SeparationService

    config, t = _traffic(SESSIONS, seed=2147483700, ring_blocks=TICKS)
    bank = cell.build_bank(config, SESSIONS)
    svc = SeparationService(bank, seed=0)
    n = config["n"]
    for i in range(SESSIONS):
        state = SMBGDState(B=t.B0[i], H_hat=np.zeros((n, n), np.float32),
                           step=np.int32(0))
        svc.admit(i, source=RingSource(t, i), state=state)
    Y = []
    for _ in range(TICKS):
        out = svc.run_tick()
        Y.append(np.stack([np.asarray(out[i]) for i in range(SESSIONS)]))
    end = bank.unpad_state(svc.state)
    ids, steps = np.arange(SESSIONS), np.full((SESSIONS,), TICKS)
    refs = {p: reference.replay(config, t, ids, steps, precision=p)
            for p in ("highest", "high")}
    return bank, np.stack(Y), np.asarray(end.B), np.asarray(end.H_hat), refs


def test_served_path_at_256_lanes_matches_the_reference(served_256):
    bank, Y, B, H, refs = served_256
    assert bank.layout.n_pad == bank.layout.m_pad == 256
    assert bank.resolved_block_s == 8  # two stream blocks of eight
    ref = refs["highest"]
    assert not ref["flagged"].any()
    assert _agrees(Y, B, H, ref)


def test_control_in_the_served_paths_place_fails(served_256):
    _, _, _, _, refs = served_256
    ctl, ref = refs["high"], refs["highest"]
    assert not _agrees(ctl["Y"], ctl["B"], ctl["H"], ref)
    # and it fails in every session, on its outputs and on both states
    assert (_rel(ctl["Y"], ref["Y"], (0, 2, 3)) > Y_TOL).all()
    assert (_rel(ctl["B"], ref["B"], (1, 2)) > B_TOL).all()
    assert (_rel(ctl["H"], ref["H"], (1, 2)) > H_TOL).all()


def _with_h2d():
    """The hand-made trace of ``test_bench_spans`` with a ``serve.h2d`` of
    4 ns inside each tick's ``serve.stage`` [20, 30)."""
    tr = _traced()
    tr.host["main"] += [Event("serve.h2d", 24, 28), Event("serve.h2d", 124, 128)]
    return tr


def test_h2d_reader_reads_the_span_inside_stage():
    tr = _with_h2d()
    run = cell.TracedRun(trace.reduce(tr), {}, "TPU v5 lite", 2, trace=tr)
    assert registry.reader(ROOT, "h2d_ms_per_tick")(run) == pytest.approx(4e-6)
    # the copy leaves the stage's own time
    assert spans.metrics(spans.reduce(tr))["stage_ms_per_tick"] == pytest.approx(8e-6)


@pytest.mark.parametrize("case", ["no trace", "no program spans", "no h2d span"])
def test_h2d_reader_reports_nothing_without_the_span(case):
    tr = {"no trace": _with_h2d, "no program spans": _two_ticks,
          "no h2d span": _traced}[case]()
    run = cell.TracedRun(trace.reduce(tr), {}, "TPU v5 lite", 2,
                         trace=None if case == "no trace" else tr)
    assert registry.reader(ROOT, "h2d_ms_per_tick")(run) is None


def test_h2d_metric_is_listed_for_the_new_cell_only():
    got = {m["name"]: m for m in registry.benchmark(ROOT)["per_layer"]}
    assert got["h2d_ms_per_tick"]["workloads"] == [CELL]
    assert got["h2d_ms_per_tick"]["layer"] == "tick loop"
    listed = [m for m in got.values() if CELL in m.get("workloads", [CELL])]
    assert set(got) == {m["name"] for m in listed}
