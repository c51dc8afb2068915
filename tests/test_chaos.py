"""Fault-injection chaos drills: inject → detect → contain → recover.

The end-to-end containment claim of the fault-tolerance layer, exercised on
BOTH execution paths (vmap oracle and fused megakernel):

  * an injected input fault (NaN/Inf burst, amplitude spike) is detected by
    the in-kernel health word within two ticks of the poisoned block being
    served,
  * the offender is rolled back to its last-known-good shadow and walks the
    escalation ladder (μ cut → quarantine → evict ``"diverged"``),
  * healthy co-tenant sessions are BIT-IDENTICAL to a fault-free run — the
    blast radius of a faulted stream is exactly that stream,
  * transient source failures (raise, stall, short read) degrade one
    session-tick instead of failing the shared launch, and
    ``ResilientSource`` retries make them invisible,
  * health state, shadows and quarantine membership survive a checkpoint
    round-trip.
"""
import tempfile
import time

import jax
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer
from repro.core import EASIConfig, SMBGDConfig
from repro.data.pipeline import MixedSignals
from repro.data.resilience import (
    FAULT_MODES,
    FaultInjector,
    ResilientSource,
    SourceStalled,
)
from repro.data.sources import ReplaySource, SourceExhausted, SyntheticSource
from repro.serve import ConvergencePolicy, HealthPolicy, SeparationService
from repro.stream import SeparatorBank

pytestmark = pytest.mark.chaos

P = 16
HPOL = HealthPolicy(
    max_rollbacks=1, window=30, mu_cut=0.25, cut_ticks=5,
    max_quarantines=1, probation=2, probe_every=2, shadow_every=4,
)
# convergence disabled: these drills isolate the health ladder
NEVER = ConvergencePolicy(threshold=1e-12, patience=10**6, min_ticks=10**6)


def _svc(fused, S=3, **kw):
    ecfg = EASIConfig(n_components=2, n_features=4, mu=3e-3)
    ocfg = SMBGDConfig(batch_size=P, mu=3e-3, beta=0.9, gamma=0.5)
    bank = SeparatorBank(ecfg, ocfg, n_streams=S, fused=fused, health_checks=True)
    return SeparationService(
        bank, seed=0, policy=NEVER, health_policy=HPOL, max_queue=8, **kw
    )


def _src(seed=0, faults=None):
    pipe = MixedSignals(m=4, n=2, batch=P, seed=seed)
    return FaultInjector(SyntheticSource(pipe), faults or {})


def _slot_B(svc, sid):
    return np.asarray(svc.bank.slot_state(svc.state, svc.sessions[sid]).B)


class TestFaultInjectorHarness:
    def test_fault_free_wrapper_is_bit_identical(self):
        a, b = _src(seed=3), FaultInjector(
            SyntheticSource(MixedSignals(m=4, n=2, batch=P, seed=3)), {}
        )
        for _ in range(5):
            np.testing.assert_array_equal(a.next_block(P), b.next_block(P))
        assert a.injected == {}

    def test_nan_inf_spike_truncate(self):
        src = _src(faults={0: "nan", 1: "inf", 2: ("spike", 1e3), 3: "truncate"})
        blk = src.next_block(P)
        assert np.isnan(blk[:, : P // 4]).all() and not np.isnan(blk[:, P // 2 :]).any()
        blk = src.next_block(P)
        assert np.isinf(blk[:, : P // 4]).all()
        clean = SyntheticSource(MixedSignals(m=4, n=2, batch=P, seed=0))
        for _ in range(2):
            clean.next_block(P)
        np.testing.assert_allclose(src.next_block(P), clean.next_block(P) * 1e3)
        assert src.next_block(P).shape == (4, P // 2)
        assert src.injected == {0: "nan", 1: "inf", 2: "spike", 3: "truncate"}

    def test_raise_is_transient(self):
        """The raise fires once WITHOUT consuming the block: the retry pulls
        the same block, clean."""
        src = _src(seed=5, faults={1: "raise"})
        clean = SyntheticSource(MixedSignals(m=4, n=2, batch=P, seed=5))
        np.testing.assert_array_equal(src.next_block(P), clean.next_block(P))
        with pytest.raises(RuntimeError, match="injected"):
            src.next_block(P)
        np.testing.assert_array_equal(src.next_block(P), clean.next_block(P))

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError, match="fault mode"):
            FaultInjector(None, {0: "gamma-ray"})
        assert set(FAULT_MODES) == {
            "nan", "inf", "spike", "truncate", "raise", "stall"
        }


class TestResilientSource:
    def test_retries_then_succeeds_and_counts(self):
        src = ResilientSource(_src(seed=1, faults={0: "raise"}), max_retries=2)
        blk = src.next_block(P)
        assert blk.shape == (4, P)
        assert src.pop_retries() == 1 and src.pop_retries() == 0

    def test_budget_exhausted_reraises(self):
        class AlwaysBroken:
            def next_block(self, n):
                raise OSError("dead sensor")

        src = ResilientSource(AlwaysBroken(), max_retries=2)
        with pytest.raises(OSError, match="dead sensor"):
            src.next_block(P)
        assert src.pop_retries() == 2  # both retries burned

    def test_exhausted_passes_through_unretried(self):
        src = ResilientSource(
            ReplaySource(np.zeros((P, 4), np.float32)), max_retries=3
        )
        src.next_block(P)
        with pytest.raises(SourceExhausted):
            src.next_block(P)
        assert src.pop_retries() == 0

    def test_stall_timeout_raises_source_stalled(self):
        src = ResilientSource(
            _src(seed=2, faults={0: ("stall", 0.5), 1: ("stall", 0.5)}),
            max_retries=1,
            timeout_s=0.05,
        )
        t0 = time.monotonic()
        with pytest.raises(SourceStalled):
            src.next_block(P)
        assert time.monotonic() - t0 < 2.0  # abandoned, not awaited

    def test_delegates_cursor_protocol(self):
        inner = SyntheticSource(MixedSignals(m=4, n=2, batch=P, seed=0))
        src = ResilientSource(FaultInjector(inner, {}))
        assert src.n_channels == 4 and src.block_size == P
        src.next_block(P)
        assert src.position == P
        src.seek(0)
        assert inner.position == 0


@pytest.mark.parametrize("fused", [False, True])
class TestChaosEndToEnd:
    def test_detection_containment_and_healthy_isolation(self, fused):
        """The flagship drill: NaN burst on one session — detected within 2
        ticks, rolled back, μ cut; the healthy co-tenant's trajectory is
        bit-identical to a run where the faulty session never existed."""
        FAULT_BLOCK = 3
        chaos = _svc(fused)
        chaos.admit("healthy", source=_src(seed=1))
        chaos.admit("faulty", source=_src(seed=2, faults={FAULT_BLOCK: "nan"}))
        clean = _svc(fused)
        clean.admit("healthy", source=_src(seed=1))
        T = 10
        for _ in range(T):
            chaos.run_tick()
            clean.run_tick()
        events = [e for e in chaos.health_events if e.session_id == "faulty"]
        assert events and events[0].action == "rollback"
        # the poisoned block is served on tick FAULT_BLOCK+1; detection is ≤2
        # ticks later (in fact: the same tick, in-kernel)
        assert events[0].tick - (FAULT_BLOCK + 1) <= 2
        assert chaos.metrics["n_rollbacks"] >= 1
        # blast radius — the healthy session never felt it
        np.testing.assert_array_equal(
            _slot_B(chaos, "healthy"), _slot_B(clean, "healthy")
        )
        # containment — the faulty slot's committed state stayed finite
        assert np.isfinite(_slot_B(chaos, "faulty")).all()

    def test_escalation_to_quarantine_and_diverged(self, fused):
        """A repeat offender quarantines; one that never produces a healthy
        probe tops the ladder out and evicts with reason ``"diverged"`` —
        carrying the escalation history in the eviction record."""
        svc = _svc(fused, S=2)
        svc.admit("doomed", source=_src(seed=4, faults={i: "nan" for i in range(99)}))
        svc.admit("ok", source=_src(seed=5))
        for _ in range(40):
            svc.run_tick()
            if svc.status("doomed") == "finished":
                break
        acts = [e.action for e in svc.health_events if e.session_id == "doomed"]
        assert acts[:2] == ["rollback", "quarantine"]
        assert svc.status("doomed") == "finished"
        rec = svc.finished["doomed"]
        assert rec.reason == "diverged"
        assert rec.health is not None and rec.health.quarantines >= 1
        assert svc.metrics["n_diverged"] == 1
        assert svc.status("ok") == "active"  # co-tenant untouched

    def test_quarantine_probation_release(self, fused):
        """Two offenses quarantine; clean out-of-band probes release the
        session warm after ``probation`` healthy probes."""
        svc = _svc(fused, S=2)
        svc.admit("flappy", source=_src(seed=3, faults={2: "nan", 4: "nan"}))
        released_at = None
        for t in range(30):
            svc.run_tick()
            acts = [e.action for e in svc.health_events if e.session_id == "flappy"]
            if "release" in acts:
                released_at = t
                break
        assert released_at is not None
        assert acts == ["rollback", "quarantine", "release"]
        assert svc.status("flappy") in ("active", "queued")
        assert svc.metrics["n_quarantined"] == 0

    def test_state_corruption_hook_detected_next_tick(self, fused):
        """The bank-side corruption hook: poisoning a slot's separator state
        directly (bit-flip drill, no input fault) is caught by the next
        tick's health word and rolled back to the shadow."""
        svc = _svc(fused, S=2)
        svc.admit("victim", source=_src(seed=6))
        for _ in range(4):
            svc.run_tick()
        assert svc.metrics["n_rollbacks"] == 0
        svc.state = svc.bank.corrupt_slot(
            svc.state, svc.sessions["victim"], mode="nan"
        )
        svc.run_tick()
        events = [e for e in svc.health_events if e.session_id == "victim"]
        assert events and events[0].action == "rollback"
        assert np.isfinite(_slot_B(svc, "victim")).all()

    def test_truncated_block_degrades_one_session_tick(self, fused):
        """A short read (wrong downstream shape) is a per-session fault: the
        launch proceeds, the session skips the tick, the error is recorded."""
        svc = _svc(fused, S=2)
        svc.admit("short", source=_src(seed=7, faults={1: "truncate"}))
        svc.admit("ok", source=_src(seed=8))
        outs = [svc.run_tick() for _ in range(3)]
        assert all("ok" in out for out in outs)
        assert "short" not in outs[1] and "short" in outs[2]
        assert svc.metrics["n_degraded_ticks"] == 1
        assert "block shape" in svc.last_faults["short"]

    def test_resilient_wrapper_makes_transient_raise_invisible(self, fused):
        """FaultInjector(raise) + ResilientSource: the retry pulls the same
        block clean — the trajectory is bit-identical to a fault-free run and
        only the retry counter shows anything happened."""
        chaos = _svc(fused, S=1)
        chaos.admit(
            "u",
            source=ResilientSource(_src(seed=9, faults={2: "raise", 5: "raise"})),
        )
        clean = _svc(fused, S=1)
        clean.admit("u", source=_src(seed=9))
        for _ in range(8):
            chaos.run_tick()
            clean.run_tick()
        np.testing.assert_array_equal(_slot_B(chaos, "u"), _slot_B(clean, "u"))
        assert chaos.metrics["n_source_retries"] == 2
        assert chaos.metrics["n_degraded_ticks"] == 0

    def test_containment_state_roundtrips_checkpoint(self, fused, tmp_path):
        """Shadows, health monitors, μ-cut countdowns and the quarantine
        pool all survive save → restore; the restored service resumes the
        ladder (probation release still works)."""
        svc = _svc(fused, S=2)
        svc.admit("q", source=_src(seed=10, faults={i: "nan" for i in range(6)}))
        svc.admit("ok", source=_src(seed=11))
        for _ in range(12):
            svc.run_tick()
            if svc.status("q") == "quarantined":
                break
        assert svc.status("q") == "quarantined"
        ck = Checkpointer(tmp_path)
        life = svc.lifecycle
        svc.save(ck, step=1)
        dup = _svc(fused, S=2)
        dup.restore(ck, lifecycle=life)
        assert dup.status("q") == "quarantined"
        assert dup.status("ok") == "active"
        np.testing.assert_array_equal(
            np.asarray(svc._shadow.B), np.asarray(dup._shadow.B)
        )
        np.testing.assert_array_equal(
            np.asarray(svc._quarantined["q"].record.state.B),
            np.asarray(dup._quarantined["q"].record.state.B),
        )
        assert dup._quarantined["q"].monitor.quarantines == (
            svc._quarantined["q"].monitor.quarantines
        )
        # rebind sources (clean now) and watch probation release fire
        dup.bind_source("ok", _src(seed=11))
        q_src = _src(seed=10)
        dup.bind_source("q", q_src)
        for _ in range(12):
            dup.run_tick()
            if dup.status("q") in ("active", "queued"):
                break
        assert dup.status("q") in ("active", "queued")


def test_donated_state_serves_and_rolls_back_like_undonated(monkeypatch):
    """Accelerators donate the bank state to the kernel on every tick; the
    CPU backend opts out, so nothing else here runs that path.  Forced on,
    a fused service with a health shadow and moment telemetry serves
    bit-identically to an undonated one — through a state corruption and
    its rollback — and never touches a donated buffer."""
    ecfg = EASIConfig(n_components=2, n_features=4, mu=2e-3)
    ocfg = SMBGDConfig(batch_size=8, mu=2e-3, beta=0.9, gamma=0.5)
    pipe = MixedSignals(m=4, n=2, batch=8, seed=3, streams=3)

    def serve(donate):
        monkeypatch.setattr(
            SeparatorBank, "_donate_default",
            staticmethod(lambda d: donate if d is None else d),
        )
        bank = SeparatorBank(ecfg, ocfg, 4, fused=True, moments=True)
        svc = SeparationService(bank, health_policy=HealthPolicy())
        for s in range(3):
            svc.admit(s, source=SyntheticSource(pipe, stream=s))
        outs = []
        for t in range(6):
            if t == 3:
                svc.state = bank.corrupt_slot(svc.state, svc.sessions[1])
            before = svc.state.B
            outs.append(svc.run_tick())
            assert before.is_deleted() is donate
        return svc, outs

    ref, ref_outs = serve(False)
    don, don_outs = serve(True)
    assert [e.action for e in don.health_events] == ["rollback"]
    for a, b in zip(ref_outs, don_outs):
        assert a.keys() == b.keys()
        for sid in a:
            np.testing.assert_array_equal(np.asarray(a[sid]), np.asarray(b[sid]))
    for leaf in ("B", "H_hat", "step", "conv", "health", "moments"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ref.state, leaf)),
            np.asarray(getattr(don.state, leaf)),
        )
