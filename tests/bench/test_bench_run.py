"""The harness rehearsed on the CPU: it refuses to measure without its
chip, and the comparison that decides ``correct`` passes a sound served
run and fails the control and each fault the cells can have."""
import io
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import cell, registry  # noqa: E402

CELLS = [w["name"] for w in registry.benchmark(ROOT)["workloads"]]
RUN = [sys.executable, "bench/run.py", "--seed", "5", "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return env


def test_run_refuses_a_machine_without_the_chip():
    got = subprocess.run(
        RUN + ["--workload", CELLS[0]], cwd=ROOT, env=_env(),
        capture_output=True, text=True, timeout=300,
    )
    assert got.returncode != 0
    assert "TPU" in got.stderr
    assert got.stdout.strip() == ""


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = _env()
    env.pop("PYTHONPATH", None)
    got = subprocess.run(
        RUN + ["--workload", CELLS[0]], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert got.returncode != 0
    assert got.stdout.strip() == ""


def _run(workload, seed=11, sessions=8, seconds=0.5, probe_widths=None,
         monkeypatch=None):
    if probe_widths is not None:
        monkeypatch.setattr(cell, "probe_widths", lambda slots: probe_widths)
    err = io.StringIO()
    got = cell.run(
        ROOT, workload, seed, seconds, False, time.perf_counter(),
        require_chip=False, sessions=sessions, out=io.StringIO(), err=err,
    )
    return got, err.getvalue()


@pytest.mark.parametrize("workload", CELLS)
def test_sound_served_run_is_correct(workload, monkeypatch):
    """A tiny served run through the real program in interpret mode: every
    number within its limit, printed beside it, last in the result."""
    got, err = _run(workload, monkeypatch=monkeypatch, probe_widths=[1, 2])
    assert got["correct"], got["checks"]
    assert list(got)[-1] == "checks"
    assert got["failed"] == 0 and got["attempted"] > 0
    assert set(got["metrics"]) == {
        m["name"] for m in registry.metrics_of(ROOT, workload, "end_to_end")
    }
    lines = err.strip().splitlines()[-len(got["checks"]):]
    assert all(line.startswith("check ") and " limit " in line for line in lines)
    json.dumps(got)  # the result line is plain JSON


def test_the_full_mix_warms_every_probe_width_before_the_window():
    """The steady mix's own warm-up, every probe width of the bank: no
    program is lowered inside the window, a check held to 0."""
    got, err = _run(CELLS[0], sessions=4, seconds=0.3)
    assert got["correct"]
    assert got["checks"]["compiles_in_window"] == {"value": 0.0, "limit": 0}
    assert "check compiles_in_window: 0.0 limit 0" in err


def test_a_compile_inside_the_window_is_not_correct(monkeypatch):
    """A program lowered while the window runs fails the run."""
    import jax

    from repro.serve import SeparationService

    real = SeparationService.run_tick
    calls = []

    def run_tick(self):
        calls.append(1)
        jax.jit(lambda x, k=len(calls): x + k)(1.0)  # a new program each tick
        return real(self)

    monkeypatch.setattr(SeparationService, "run_tick", run_tick)
    got, _ = _run(CELLS[0], monkeypatch=monkeypatch, probe_widths=[1])
    assert got["checks"]["compiles_in_window"]["value"] > 0
    assert not got["correct"]


def test_warm_up_fails_loudly_without_the_probe_bank(monkeypatch):
    """The harness reaches the service's probe banks to warm them; if the
    program drops that path the run stops, rather than compile in the
    window unseen."""
    from repro.serve import SeparationService

    monkeypatch.delattr(SeparationService, "_probe_bank")
    with pytest.raises(RuntimeError, match="_probe_bank"):
        _run(CELLS[0], sessions=2, seconds=0.1)


# a seed on which the reference sees two of 16 fresh paper sessions blow up
BLOWUP = {"sessions": 16, "seed": 27}


def test_sound_run_with_blowups_is_correct(monkeypatch):
    """The health path flags the sessions the reference flags: the run is
    correct, and the blown-up sessions are left out of the values."""
    got, err = _run(CELLS[0], monkeypatch=monkeypatch, probe_widths=[1, 2],
                    seconds=1.0, **BLOWUP)
    assert got["correct"], got["checks"]
    assert got["checks"]["health_missed"]["value"] == 0
    assert got["compared"]["sessions"] < got["compared"]["of"]


def _broken_step(monkeypatch, fault):
    import jax.numpy as jnp

    from repro.stream import SeparatorBank

    real = SeparatorBank.make_step

    def make_step(self, donate=None, with_hyperparams=False):
        fn = real(self, donate=False, with_hyperparams=with_hyperparams)

        def step(state, X, active, *rest):
            if fault == "state_unchanged":
                return state, fn(state, X, active, *rest)[1]
            if fault == "half_the_sessions":
                return fn(state, X, active.at[::2].set(False), *rest)
            if fault == "health_word_dropped":
                new, Y = fn(state, X, active, *rest)
                return new._replace(health=jnp.zeros_like(new.health)), Y
            raise ValueError(fault)

        return step

    monkeypatch.setattr(SeparatorBank, "make_step", make_step)


def _altered_answer(monkeypatch):
    from repro.serve import SeparationService

    real = SeparationService.run_tick
    calls = []

    def run_tick(self):
        out = real(self)
        calls.append(1)
        if len(calls) == 3 and 0 in out:
            out[0] = -out[0]
        return out

    monkeypatch.setattr(SeparationService, "run_tick", run_tick)


@pytest.mark.parametrize(
    "fault",
    ["state_unchanged", "half_the_sessions", "answer_altered", "health_word_dropped"],
)
def test_each_fault_of_the_served_path_is_not_correct(fault, monkeypatch):
    """The timed path broken underneath, the rest of the run as it is:
    a step that returns its state unchanged, half of each tick's sessions
    left out of the step, one output altered where it is produced, the
    health word dropped where the step produces it (on a seed where
    sessions blow up).  (The cells span one chip, so there is no exchange
    between chips to drop.)"""
    if fault == "answer_altered":
        _altered_answer(monkeypatch)
    else:
        _broken_step(monkeypatch, fault)
    extra = dict(BLOWUP, seconds=1.0) if fault == "health_word_dropped" else {}
    got, _ = _run(CELLS[0], monkeypatch=monkeypatch, probe_widths=[1, 2], **extra)
    assert not got["correct"], got["checks"]
    if fault == "health_word_dropped":
        assert got["checks"]["health_missed"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_control_one_precision_step_below_is_not_correct(workload):
    """The reference in the program's place at three bf16 passes (one step
    below the configurations' f32 at HIGHEST) fails the comparison, at the
    cells' own session count and as many ticks as a run serves."""
    from control import control_numbers

    numbers, ok = control_numbers(ROOT, workload, seed=21, ticks=40)
    assert not ok, numbers


def test_witness_sets_the_served_path_beside_both_references(capsys):
    """The second witness at a CPU size: on the CPU the chip's reference is
    the CPU's, and the served path lies far nearer it than the control."""
    from witness import main

    assert main(["--workload", CELLS[0], "--seeds", "3", "--ticks", "6",
                 "--sessions", "4"]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    got = {r["pair"]: r for r in rows if r.get("max_update") == float("inf")}
    assert got["ref_dev-ref_cpu"]["y"] == 0.0
    assert got["kernel-ref_cpu"]["y"] * 3 < got["ctl_cpu-ref_cpu"]["y"]
    assert got["kernel-vmap"]["compared"] > 0


def test_thousands_of_steps_of_a_sound_bank_are_correct():
    """The steady paper cell rehearsed at 8 sessions for 2,048 steps each,
    the bank driven by its own jitted step as a fast served path would
    drive it: the comparison calls it correct at every mark, and the
    control not."""
    from calibrate import readings
    from control import control_readings

    marks = (64, 2048)
    rows, _ = readings(ROOT, CELLS[0], seed=2147483659, marks=marks, sessions=8,
                       out=io.StringIO())
    for row in rows:
        assert row["correct"], row
        assert row["compared"] > 0
    for k, numbers, ok in control_readings(ROOT, CELLS[0], 2147483659, marks, sessions=8):
        assert not ok, numbers
        assert numbers["y_ctl_med"] == 1.0
