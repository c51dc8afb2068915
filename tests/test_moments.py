"""In-kernel [Σy², Σy⁴] telemetry and the moment-scaled adaptive μ controller.

Three layers under test:

  * the kernel fold — ``ops.smbgd_step_bank(moments=True)`` /
    ``smbgd_probe_bank(moments=True)`` against the naive ``moments_ref``
    whole-array oracle and the vmap bank path, across ragged shapes, every
    nonlinearity, both storage dtypes and several stream blocks — plus the
    bit-identity contract: ``moments`` is purely observational, every other
    output is unchanged by it,
  * the host-side ``MomentController`` — EMA kurtosis → μ multiplier with
    warmup, deadband, clamps, anneal and checkpoint round-trips,
  * the service composition — the three μ ladders (DriftPolicy boost,
    HealthPolicy cut, moment controller) write disjoint state and compose by
    the pinned rule: cut WINS while live, boost × controller MULTIPLY
    (the PR-9 composition bugfix regressions live here).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.easi import EASIConfig
from repro.core.nonlinearities import NONLINEARITIES
from repro.core.smbgd import SMBGDConfig
from repro.data.sources import ReplaySource
from repro.kernels.easi_gradient import ops as easi_ops
from repro.kernels.easi_gradient.ref import moments_ref, smbgd_step_bank_ref
from repro.serve import (
    ConvergencePolicy,
    MomentController,
    MomentPolicy,
    SeparationService,
)
from repro.stream import SeparatorBank


def _cfgs(P=8, n=2, m=4, mu=2e-3):
    return (
        EASIConfig(n_components=n, n_features=m, mu=mu),
        SMBGDConfig(batch_size=P, mu=mu, beta=0.9, gamma=0.5),
    )


def _padded_inputs(S, P, n, m, key, state_dtype=jnp.float32):
    """Persistent-layout operand set with real content in the logical block
    (same recipe as the fused-step sweep) and a mixed active mask."""
    lay = easi_ops.bank_layout(n, m, P)
    X = jnp.zeros((S, lay.P_pad, lay.m_pad)).at[:, :P, :m].set(
        jax.random.normal(key, (S, P, m))
    )
    B = jnp.zeros((S, lay.n_pad, lay.m_pad)).at[:, :n, :m].set(
        jax.random.normal(jax.random.fold_in(key, 1), (S, n, m)) * 0.3
    ).astype(state_dtype)
    H = jnp.zeros((S, lay.n_pad, lay.n_pad)).at[:, :n, :n].set(
        jax.random.normal(jax.random.fold_in(key, 2), (S, n, n)) * 0.1
    ).astype(state_dtype)
    W = jnp.zeros((S, lay.P_pad)).at[:, :P].set(
        jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (S, P))) * 0.01
    )
    step = jnp.arange(S, dtype=jnp.int32)
    gamma_hat = 0.1 + 0.8 * jax.random.uniform(jax.random.fold_in(key, 4), (S,))
    active = (jnp.arange(S) % 3 != 2).astype(jnp.int32)  # freeze every 3rd
    conv0 = jnp.arange(1.0, S + 1.0)
    return lay, (X, W, B, H, step, gamma_hat, active, conv0)


# ---------------------------------------------------------------------------
# kernel fold vs the naive oracle
# ---------------------------------------------------------------------------
class TestKernelMoments:
    def test_step_matches_ref_and_direct_oracle(self):
        S, P, n, m = 4, 16, 3, 5
        lay, args = _padded_inputs(S, P, n, m, jax.random.PRNGKey(0))
        Y, *_rest, mom = easi_ops.smbgd_step_bank(
            *args, block_p=lay.block_p, moments=True
        )
        *_refs, mom_ref = smbgd_step_bank_ref(*args, moments=True)
        np.testing.assert_allclose(
            np.asarray(mom), np.asarray(mom_ref), rtol=1e-5, atol=1e-6
        )
        # and against the whole-array reduction over the kernel's OWN Y —
        # padding contributes exact zeros, so padded ≡ logical sums
        active = np.asarray(args[6])
        for s in range(S):
            want = (
                np.asarray(moments_ref(Y[s]))
                if active[s]
                else np.zeros((2,), np.float32)
            )
            np.testing.assert_allclose(
                np.asarray(mom[s]), want, rtol=1e-4, atol=1e-6
            )
            np.testing.assert_allclose(
                np.asarray(mom[s]),
                np.asarray(moments_ref(Y[s, :P, :n])) if active[s] else 0.0,
                rtol=1e-4,
                atol=1e-6,
            )

    def test_frozen_streams_report_zero(self):
        lay, args = _padded_inputs(6, 8, 2, 4, jax.random.PRNGKey(3))
        _, _, mom = easi_ops.smbgd_probe_bank(
            *args, block_p=lay.block_p, moments=True
        )
        active = np.asarray(args[6])
        np.testing.assert_array_equal(
            np.asarray(mom)[active == 0], np.zeros((2, 2), np.float32)
        )
        assert np.all(np.asarray(mom)[active == 1] > 0)

    def test_probe_moments_equal_step_moments(self):
        """The freeze-only probe folds the same Y as the committing step."""
        lay, args = _padded_inputs(3, 8, 2, 4, jax.random.PRNGKey(5))
        *_outs, mom_step = easi_ops.smbgd_step_bank(
            *args, block_p=lay.block_p, moments=True
        )
        _, _, mom_probe = easi_ops.smbgd_probe_bank(
            *args, block_p=lay.block_p, moments=True
        )
        np.testing.assert_allclose(
            np.asarray(mom_step), np.asarray(mom_probe), rtol=1e-6, atol=0
        )

    @pytest.mark.property
    @given(
        S=st.integers(1, 4),
        shape=st.sampled_from([(2, 4), (3, 5), (2, 6), (4, 4)]),
        P=st.sampled_from([8, 16]),
        nonlinearity=st.sampled_from(sorted(NONLINEARITIES)),
        dtype=st.sampled_from(["f32", "bf16"]),
    )
    @settings(max_examples=20, deadline=None)
    def test_property_sweep(self, S, shape, P, nonlinearity, dtype):
        """Fused fold ≡ naive oracle across ragged shapes, nonlinearities
        and storage dtypes."""
        n, m = shape
        state_dtype = jnp.float32 if dtype == "f32" else jnp.bfloat16
        lay, args = _padded_inputs(
            S, P, n, m, jax.random.PRNGKey(S * 100 + P + n), state_dtype
        )
        *_outs, mom = easi_ops.smbgd_step_bank(
            *args,
            nonlinearity=nonlinearity,
            block_p=lay.block_p,
            moments=True,
        )
        *_refs, mom_ref = smbgd_step_bank_ref(
            *args, nonlinearity=nonlinearity, moments=True
        )
        np.testing.assert_allclose(
            np.asarray(mom), np.asarray(mom_ref), rtol=2e-4, atol=1e-6
        )


class TestMomentsBitIdentity:
    """``moments`` is purely observational: flipping it must not perturb a
    single bit of any other output, and the off paths must be exactly the
    pre-telemetry kernels."""

    def test_step_outputs_identical_on_off(self):
        lay, args = _padded_inputs(4, 16, 2, 4, jax.random.PRNGKey(7))
        off = easi_ops.smbgd_step_bank(*args, block_p=lay.block_p, moments=False)
        on = easi_ops.smbgd_step_bank(*args, block_p=lay.block_p, moments=True)
        for a, b in zip(off[:-1], on[:-1]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(off[-1]), np.zeros((4, 2), np.float32)
        )

    def test_probe_outputs_identical_on_off(self):
        lay, args = _padded_inputs(3, 8, 2, 4, jax.random.PRNGKey(8))
        off = easi_ops.smbgd_probe_bank(*args, block_p=lay.block_p, moments=False)
        on = easi_ops.smbgd_probe_bank(*args, block_p=lay.block_p, moments=True)
        for a, b in zip(off[:-1], on[:-1]):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(off[-1]), np.zeros((3, 2), np.float32)
        )

    def test_moments_identical_across_block_s(self):
        """The moment fold is per stream: splitting the bank into stream
        blocks of 1 or 2 streams changes no bit of any output, moments
        included (the chip runs many stream blocks per launch)."""
        lay, args = _padded_inputs(4, 16, 2, 4, jax.random.PRNGKey(9))
        whole = easi_ops.smbgd_step_bank(
            *args, block_p=lay.block_p, block_s=4, moments=True
        )
        for block_s in (1, 2):
            split = easi_ops.smbgd_step_bank(
                *args, block_p=lay.block_p, block_s=block_s, moments=True
            )
            for a, b in zip(whole, split):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_bank_step_identical_with_moments(self):
        """Bank layer: a ``moments=True`` bank commits the identical state
        trajectory (B/Ĥ/step/conv) as a ``moments=False`` one — the leaf is
        pure telemetry on BOTH execution paths."""
        for fused in (False, True):
            ecfg, ocfg = _cfgs()
            plain = SeparatorBank(ecfg, ocfg, n_streams=3, fused=fused)
            teled = SeparatorBank(
                ecfg, ocfg, n_streams=3, fused=fused, moments=True
            )
            s0p = plain.init(jax.random.PRNGKey(1))
            s0t = teled.init(jax.random.PRNGKey(1))
            X = jax.random.normal(jax.random.PRNGKey(2), (3, 8, 4))
            for _ in range(3):
                s0p, _ = plain.step(s0p, X)
                s0t, _ = teled.step(s0t, X)
            for leaf in ("B", "H_hat", "step", "conv"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(s0p, leaf)),
                    np.asarray(getattr(s0t, leaf)),
                )
            assert np.all(np.asarray(s0t.moments) > 0)

    def test_bank_fused_matches_vmap_moments(self):
        """The in-kernel fold ≡ the vmap fallback's whole-array fold."""
        ecfg, ocfg = _cfgs(n=2, m=4)
        fused = SeparatorBank(ecfg, ocfg, n_streams=3, fused=True, moments=True)
        vmapb = SeparatorBank(ecfg, ocfg, n_streams=3, fused=False, moments=True)
        sf = fused.init(jax.random.PRNGKey(4))
        sv = vmapb.init(jax.random.PRNGKey(4))
        X = jax.random.normal(jax.random.PRNGKey(5), (3, 8, 4))
        sf, _ = fused.step(sf, X)
        sv, _ = vmapb.step(sv, X)
        np.testing.assert_allclose(
            np.asarray(sf.moments), np.asarray(sv.moments), rtol=1e-5, atol=1e-6
        )


# ---------------------------------------------------------------------------
# the host-side controller
# ---------------------------------------------------------------------------
def _feed(ctrl, sid, kappa, ticks=1):
    """Feed ``ticks`` telemetry pairs with exact kurtosis ``kappa``:
    Σy² = N makes κ = N·Σy⁴/(Σy²)² = Σy⁴/N."""
    out = 1.0
    for _ in range(ticks):
        out = ctrl.observe(sid, ctrl.count, kappa * ctrl.count)
    return out


class TestMomentController:
    def test_policy_validation(self):
        with pytest.raises(ValueError, match="ema_slow"):
            MomentPolicy(ema_fast=0.1, ema_slow=0.5)
        with pytest.raises(ValueError, match="warmup"):
            MomentPolicy(warmup_ticks=0)
        with pytest.raises(ValueError, match="gain"):
            MomentPolicy(gain=0.0)
        with pytest.raises(ValueError, match="include 1.0"):
            MomentPolicy(min_scale=2.0, max_scale=4.0)
        with pytest.raises(ValueError, match="deadband"):
            MomentPolicy(deadband=-0.1)
        with pytest.raises(ValueError, match="count"):
            MomentController(MomentPolicy(), count=0)

    def test_warmup_holds_scale_at_one(self):
        ctrl = MomentController(MomentPolicy(warmup_ticks=6, deadband=0.0), 16)
        _feed(ctrl, "a", 9.0, ticks=1)  # seeds both EMAs
        for _ in range(4):  # ticks 2..5 < warmup, despite a huge deviation
            assert _feed(ctrl, "a", 1.0) == 1.0
        assert _feed(ctrl, "a", 1.0) > 1.0  # tick 6 crosses warmup

    def test_deadband_pins_steady_state(self):
        """A converged session's κ jitter inside the deadband NEVER moves μ —
        the scale is exactly 1.0, not 1.0±ε."""
        ctrl = MomentController(
            MomentPolicy(warmup_ticks=2, deadband=0.15), 16
        )
        rng = np.random.default_rng(0)
        for _ in range(50):
            assert _feed(ctrl, "a", 4.0 * (1 + 0.02 * rng.standard_normal())) == 1.0

    def test_drift_boosts_and_clamps(self):
        pol = MomentPolicy(
            warmup_ticks=2, deadband=0.05, ema_fast=0.9, ema_slow=1e-4,
            max_scale=3.0,
        )
        ctrl = MomentController(pol, 16)
        _feed(ctrl, "a", 9.0, ticks=3)  # super-Gaussian reference
        s = _feed(ctrl, "a", 3.0, ticks=5)  # CLT drags κ to Gaussian
        fast, slow = ctrl.estimate("a")
        assert fast < slow  # fast EMA left the reference
        assert s > 1.0
        s = _feed(ctrl, "a", 0.01, ticks=10)  # absurd deviation → clamp
        assert s == 3.0

    def test_sub_gaussian_drift_also_boosts(self):
        """Symmetric response: sub-Gaussian sources drift κ UP toward 3."""
        pol = MomentPolicy(
            warmup_ticks=2, deadband=0.05, ema_fast=0.9, ema_slow=1e-4
        )
        ctrl = MomentController(pol, 16)
        _feed(ctrl, "a", 1.5, ticks=3)
        assert _feed(ctrl, "a", 3.0, ticks=5) > 1.0

    def test_anneals_back_to_one(self):
        """Re-convergence pulls the slow reference to the new κ and the
        scale anneals to exactly 1.0 — the fixed boost cannot do this."""
        pol = MomentPolicy(
            warmup_ticks=2, deadband=0.1, ema_fast=0.5, ema_slow=0.2
        )
        ctrl = MomentController(pol, 16)
        _feed(ctrl, "a", 9.0, ticks=4)
        assert _feed(ctrl, "a", 3.0, ticks=3) > 1.0  # mid-drift: boosted
        assert _feed(ctrl, "a", 3.0, ticks=60) == 1.0  # re-converged: annealed

    def test_activity_floor_and_nonfinite_ignored(self):
        ctrl = MomentController(MomentPolicy(warmup_ticks=1), 16)
        assert ctrl.observe("a", 0.0, 0.0) == 1.0  # frozen slot: all-zero row
        assert len(ctrl) == 0  # ...never even seeds a session
        _feed(ctrl, "a", 4.0, ticks=3)
        before = ctrl.estimate("a")
        assert ctrl.observe("a", float("nan"), 1.0) == ctrl.scale("a")
        assert ctrl.observe("a", 16.0, float("inf")) == ctrl.scale("a")
        assert ctrl.estimate("a") == before  # garbage ticks fold nothing

    def test_state_dict_roundtrip(self):
        pol = MomentPolicy(warmup_ticks=2, ema_fast=0.5, ema_slow=0.1)
        ctrl = MomentController(pol, 16)
        _feed(ctrl, "a", 9.0, ticks=4)
        _feed(ctrl, "a", 3.0, ticks=2)
        _feed(ctrl, 7, 2.0, ticks=3)  # non-string session id
        blob = ctrl.state_dict()
        import json

        blob = json.loads(json.dumps(blob))  # must survive JSON
        ctrl2 = MomentController(pol, 16)
        ctrl2.load_state_dict(blob, key_map={"a": "a", "7": 7})
        for sid in ("a", 7):
            assert ctrl2.scale(sid) == ctrl.scale(sid)
            assert ctrl2.estimate(sid) == ctrl.estimate(sid)
        # the restored EMAs keep evolving identically
        assert _feed(ctrl, "a", 3.0) == _feed(ctrl2, "a", 3.0)

    def test_reset_reseeds_reference(self):
        pol = MomentPolicy(warmup_ticks=2, ema_fast=0.9, ema_slow=1e-4,
                           deadband=0.05)
        ctrl = MomentController(pol, 16)
        _feed(ctrl, "a", 9.0, ticks=3)
        assert _feed(ctrl, "a", 3.0, ticks=5) > 1.0
        ctrl.reset("a")
        assert ctrl.scale("a") == 1.0
        # the next tick re-seeds both EMAs at the CURRENT κ: no stale
        # reference, no spurious boost
        assert _feed(ctrl, "a", 3.0) == 1.0
        assert ctrl.estimate("a") == (3.0, 3.0)


# ---------------------------------------------------------------------------
# service composition: cut wins, boost × controller multiply
# ---------------------------------------------------------------------------
def _moment_svc(S=2, P=8, moment_policy=None, **kw):
    ecfg, ocfg = _cfgs(P=P)
    return SeparationService(
        SeparatorBank(ecfg, ocfg, n_streams=S, moments=True),
        moment_policy=(
            moment_policy if moment_policy is not None else MomentPolicy()
        ),
        **kw,
    )


class TestMuComposition:
    """The PR-9 composition bugfix: the three μ ladders keep DISJOINT state;
    ``μ_eff = cut_on ? cut : boost · ctrl`` and one ladder expiring can never
    clobber another's live multiplier."""

    def test_moment_policy_requires_moments_bank(self):
        ecfg, ocfg = _cfgs()
        with pytest.raises(ValueError, match="moments=True"):
            SeparationService(
                SeparatorBank(ecfg, ocfg, n_streams=2),
                moment_policy=MomentPolicy(),
            )

    def test_boost_and_controller_multiply(self):
        svc = _moment_svc()
        svc._boost_scale[0] = 4.0
        svc._ctrl_scale[0] = 2.0
        np.testing.assert_allclose(svc._effective_mu_scale(), [8.0, 1.0])

    def test_cut_wins_while_live(self):
        svc = _moment_svc()
        svc._boost_scale[0] = 4.0
        svc._ctrl_scale[0] = 2.0
        svc._cut_scale[0] = 0.25
        svc._cut_on[0] = True
        np.testing.assert_allclose(svc._effective_mu_scale(), [0.25, 1.0])
        # cut expiring (the ladder clears ITS OWN state only) re-exposes the
        # still-live boost × controller product — nothing was clobbered
        svc._cut_scale[0] = 1.0
        svc._cut_on[0] = False
        np.testing.assert_allclose(svc._effective_mu_scale(), [8.0, 1.0])

    def test_boost_expiry_preserves_controller(self):
        svc = _moment_svc()
        svc._boost_scale[0] = 4.0
        svc._ctrl_scale[0] = 2.0
        svc._boost_scale[0] = 1.0  # what _apply_policy's expiry now does
        np.testing.assert_allclose(svc._effective_mu_scale(), [2.0, 1.0])

    def test_effective_scale_reaches_the_kernel_mu_row(self):
        svc = _moment_svc()
        svc._ctrl_scale[0] = 2.5
        hp = svc._current_hp()
        base = float(svc.bank.opt.mu)
        np.testing.assert_allclose(
            np.asarray(hp.mu), [base * 2.5, base], rtol=1e-6
        )

    def test_lifecycle_carries_all_ladders(self, tmp_path):
        from repro.checkpoint.checkpointer import Checkpointer

        svc = _moment_svc()
        svc.admit("a", source=ReplaySource(
            np.random.default_rng(0).standard_normal((64, 4)).astype(np.float32),
            loop=True,
        ))
        for _ in range(4):
            svc.run_tick()
        svc._boost_scale[0] = 4.0
        svc._ctrl_scale[0] = 2.0
        svc._cut_scale[1] = 0.5
        life = svc.lifecycle
        assert life["mu_boost_scale"] == [4.0, 1.0]
        assert life["mu_ctrl_scale"] == [2.0, 1.0]
        assert life["mu_cut_scale"] == [1.0, 0.5]
        assert life["mu_cut_on"] == [False, False]
        assert life["mu_scale"] == [8.0, 1.0]  # legacy composite view
        assert "a" in life["moments"] or str("a") in life["moments"]
        # full service round-trip: ladders AND controller EMAs survive
        import json

        ckpt = Checkpointer(tmp_path)
        svc.save(ckpt, step=1)
        svc2 = _moment_svc()
        svc2.restore(ckpt, lifecycle=json.loads(json.dumps(life)))
        np.testing.assert_allclose(svc2._boost_scale, svc._boost_scale)
        np.testing.assert_allclose(svc2._cut_scale, svc._cut_scale)
        np.testing.assert_allclose(svc2._ctrl_scale, svc._ctrl_scale)
        np.testing.assert_array_equal(svc2._cut_on, svc._cut_on)
        assert svc2._moments.estimate("a") == svc._moments.estimate("a")

    def test_restore_rejects_controller_state_without_policy(self, tmp_path):
        from repro.checkpoint.checkpointer import Checkpointer

        svc = _moment_svc()
        svc.admit("a", source=ReplaySource(
            np.random.default_rng(0).standard_normal((64, 4)).astype(np.float32),
            loop=True,
        ))
        for _ in range(3):
            svc.run_tick()
        ckpt = Checkpointer(tmp_path)
        svc.save(ckpt, step=0)
        ecfg, ocfg = _cfgs()
        bare = SeparationService(
            SeparatorBank(ecfg, ocfg, n_streams=2, moments=True)
        )
        with pytest.raises(ValueError, match="moment-controller"):
            bare.restore(ckpt, lifecycle=svc.lifecycle)


# ---------------------------------------------------------------------------
# end-to-end: the controller reacts to a real distribution change
# ---------------------------------------------------------------------------
class TestServiceAdaptiveMu:
    def test_controller_observes_served_sessions(self):
        svc = _moment_svc(
            moment_policy=MomentPolicy(warmup_ticks=10, deadband=0.5)
        )
        rng = np.random.default_rng(1)
        svc.admit("a", source=ReplaySource(
            rng.standard_normal((64, 4)).astype(np.float32), loop=True
        ))
        for _ in range(5):
            svc.run_tick()
        stats = svc.session_stats("a")
        assert stats["mu_ctrl"] == 1.0  # still inside warmup: never scales
        assert stats["kurtosis_fast"] > 0 and stats["kurtosis_slow"] > 0
        assert len(svc._moments) == 1
        svc.evict("a")
        assert len(svc._moments) == 0  # eviction forgets the EMAs

    def test_distribution_change_scales_mu(self):
        """An abrupt source-statistics change (rademacher → gaussian, i.e.
        sub-Gaussian mixture drifting toward Gaussian) drives the fast κ EMA
        off the reference and μ above base — then annealing begins."""
        P = 64
        ecfg, ocfg = _cfgs(P=P, mu=1e-5)  # tiny μ: B is essentially frozen
        svc = SeparationService(
            SeparatorBank(ecfg, ocfg, n_streams=1, moments=True),
            moment_policy=MomentPolicy(
                ema_fast=0.4, ema_slow=0.01, warmup_ticks=4,
                deadband=0.05, gain=2.0,
            ),
        )
        rng = np.random.default_rng(7)
        flat = rng.choice([-1.0, 1.0], size=(30 * P, 4)).astype(np.float32)
        gauss = rng.standard_normal((30 * P, 4)).astype(np.float32)
        svc.admit("a", source=ReplaySource(np.concatenate([flat, gauss])))
        for _ in range(30):
            svc.run_tick()
        assert svc.session_stats("a")["mu_ctrl"] == 1.0  # pre-drift: steady
        peak = 1.0
        for _ in range(25):
            svc.run_tick()
            peak = max(peak, svc.session_stats("a")["mu_ctrl"])
        assert peak > 1.1  # the controller fired on the κ shift
        hp = svc._current_hp()
        assert float(np.asarray(hp.mu)[0]) >= float(svc.bank.opt.mu)
