"""Serving engine: deterministic greedy generation, family coverage, the
adaptive-ICA deployment loop (the paper's streaming use-case)."""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.core import AdaptiveICA, EASIConfig, SMBGDConfig, amari_index, global_system
from repro.core import smbgd as smbgd_lib
from repro.data.pipeline import MixedSignals
from repro.launch.serve import Engine, ServeConfig
from repro.models import model as M
from repro.serve.engine import ConvergencePolicy, SeparationService
from repro.stream import SeparatorBank
from _hypothesis_compat import given, settings, st


@pytest.mark.parametrize("arch", ["smollm-135m", "xlstm-1.3b", "musicgen-large"])
def test_greedy_generation_deterministic(arch):
    cfg = get_config(arch).reduced()  # reduced keeps family periodicity valid
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    scfg = ServeConfig(max_batch=2, max_len=48, temperature=0.0)
    if cfg.n_codebooks:
        prompts = jax.random.randint(key, (2, 8, cfg.n_codebooks), 0, cfg.vocab_size)
    else:
        prompts = jax.random.randint(key, (2, 8), 0, cfg.vocab_size)
    out1, _ = Engine(cfg, params, scfg).prefill_and_generate(prompts, n_new=6)
    out2, _ = Engine(cfg, params, scfg).prefill_and_generate(prompts, n_new=6)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert out1.shape[:2] == (2, 6)
    assert int(out1.max()) < cfg.vocab_size


def test_generation_matches_forward_argmax():
    """Greedy next token after prefill == argmax of the parallel forward."""
    cfg = dataclasses.replace(get_config("smollm-135m").reduced(), n_layers=2)
    key = jax.random.PRNGKey(1)
    params = M.init_params(key, cfg)
    prompts = jax.random.randint(key, (2, 12), 0, cfg.vocab_size)
    logits, _ = M.forward(params, {"tokens": prompts}, cfg)
    expected = jnp.argmax(logits[:, -1], axis=-1)
    out, _ = Engine(cfg, params, ServeConfig(max_batch=2, max_len=32)).prefill_and_generate(
        prompts, n_new=1
    )
    np.testing.assert_array_equal(np.asarray(out[:, 0]), np.asarray(expected))


class TestSampling:
    """Engine._sample: greedy vs temperature, with and without codebooks."""

    def _engine(self, temperature, n_codebooks=0):
        cfg = dataclasses.replace(
            get_config("musicgen-large" if n_codebooks else "smollm-135m").reduced(),
            n_layers=1,
        )
        params = M.init_params(jax.random.PRNGKey(0), cfg)
        return Engine(
            cfg, params, ServeConfig(max_batch=2, max_len=16, temperature=temperature)
        ), cfg

    def test_greedy_is_argmax(self):
        eng, cfg = self._engine(temperature=0.0)
        logits = jax.random.normal(jax.random.PRNGKey(1), (2, 3, cfg.vocab_size))
        tok = eng._sample(logits)
        np.testing.assert_array_equal(
            np.asarray(tok), np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        )

    def test_temperature_samples_in_range_and_advances_key(self):
        eng, cfg = self._engine(temperature=1.0)
        logits = jax.random.normal(jax.random.PRNGKey(2), (2, 3, cfg.vocab_size))
        key_before = np.asarray(eng.key)
        tok = eng._sample(logits)
        assert tok.shape == (2,)
        assert int(tok.max()) < cfg.vocab_size and int(tok.min()) >= 0
        assert not np.array_equal(key_before, np.asarray(eng.key))
        # near-zero temperature concentrates on the argmax
        eng.scfg.temperature = 1e-4
        tok_cold = eng._sample(logits)
        np.testing.assert_array_equal(
            np.asarray(tok_cold), np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        )

    def test_codebook_path_samples_every_codebook(self):
        eng, cfg = self._engine(temperature=0.0, n_codebooks=4)
        K = cfg.n_codebooks
        logits = jax.random.normal(jax.random.PRNGKey(3), (2, 3, K, cfg.vocab_size))
        tok = eng._sample(logits)
        assert tok.shape == (2, K)
        np.testing.assert_array_equal(
            np.asarray(tok), np.asarray(jnp.argmax(logits[:, -1], axis=-1))
        )


def test_separation_service_imports_no_lm_code():
    """``repro.serve`` loads neither the model zoo nor the flash-attention
    kernel: the LM engine lives in ``repro.launch.serve``.  A fresh
    interpreter, since this suite imports both."""
    code = (
        "import sys, repro.serve; print(sorted(m for m in sys.modules if "
        "m.startswith(('repro.models', 'repro.kernels.flash_attention'))))"
    )
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src)
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, check=True, timeout=120,
    )
    assert out.stdout.strip() == "[]"


class TestSeparationService:
    """Continuous-batching admission into SeparatorBank slots."""

    def _svc(self, S=4, P=8):
        ecfg = EASIConfig(n_components=2, n_features=4, mu=2e-3)
        ocfg = SMBGDConfig(batch_size=P, mu=2e-3, beta=0.9, gamma=0.5)
        return SeparationService(SeparatorBank(ecfg, ocfg, n_streams=S), seed=0)

    def test_admit_step_evict_lifecycle(self):
        svc = self._svc()
        slot_a = svc.admit("a")
        svc.admit("b")
        assert svc.n_active == 2 and svc.n_free == 2
        X = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
        out = svc.step({"a": X, "b": X})
        assert set(out) == {"a", "b"} and out["a"].shape == (8, 2)
        final = svc.evict("a")
        assert final.B.shape == (2, 4) and int(final.step) == 1
        # freed slot is reused by the next admission
        assert svc.admit("c") == slot_a

    def test_session_matches_independent_separator(self):
        """A session stepped through the service must follow exactly the
        trajectory of a standalone separator with the same init."""
        from repro.core import smbgd as smbgd_lib

        svc = self._svc()
        svc.admit("only")
        slot = svc._slot_of["only"]
        st_ref = svc.bank.slot_state(svc.state, slot)
        ecfg, ocfg = svc.bank.easi, svc.bank.opt
        for k in range(5):
            X = jax.random.normal(jax.random.PRNGKey(10 + k), (8, 4))
            out = svc.step({"only": X})
            st_ref, Y_ref = smbgd_lib.smbgd_batched_step(st_ref, X, ecfg, ocfg)
            np.testing.assert_allclose(
                np.asarray(out["only"]), np.asarray(Y_ref), rtol=1e-5, atol=1e-6
            )
        final = svc.evict("only")
        np.testing.assert_allclose(
            np.asarray(final.B), np.asarray(st_ref.B), rtol=1e-5, atol=1e-6
        )

    def test_idle_sessions_frozen(self):
        svc = self._svc()
        svc.admit("busy")
        svc.admit("idle")
        idle_before = svc.bank.slot_state(svc.state, svc._slot_of["idle"])
        for k in range(3):
            svc.step({"busy": jax.random.normal(jax.random.PRNGKey(k), (8, 4))})
        idle_after = svc.bank.slot_state(svc.state, svc._slot_of["idle"])
        np.testing.assert_array_equal(
            np.asarray(idle_before.B), np.asarray(idle_after.B)
        )
        assert int(idle_after.step) == 0

    def test_capacity_and_duplicate_guards(self):
        svc = self._svc(S=2)
        svc.admit("a")
        with pytest.raises(ValueError):
            svc.admit("a")
        svc.admit("b")
        with pytest.raises(RuntimeError):
            svc.admit("c")
        with pytest.raises(KeyError):
            svc.step({"ghost": jnp.zeros((8, 4))})

    def test_wrong_batch_shape_rejected(self):
        """A wrong-shaped mini-batch must error, not silently broadcast."""
        svc = self._svc()
        svc.admit("a")
        for bad in ((4,), (1, 4), (5, 4), (8, 3)):
            with pytest.raises(ValueError, match="batch shape"):
                svc.step({"a": jnp.zeros(bad)})

    def test_checkpoint_roundtrip_resumes_sessions(self, tmp_path):
        from repro.checkpoint.checkpointer import Checkpointer

        svc = self._svc()
        svc.admit("a")
        X = jax.random.normal(jax.random.PRNGKey(0), (8, 4))
        svc.step({"a": X})
        ckpt = Checkpointer(tmp_path)
        svc.save(ckpt, step=1)
        sessions = svc.sessions

        svc2 = self._svc()
        got = svc2.restore(ckpt, sessions=sessions)
        assert got == 1
        for a, b in zip(svc.state, svc2.state):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # session "a" resumes in place: same trajectory as the original...
        np.testing.assert_array_equal(
            np.asarray(svc.step({"a": X})["a"]), np.asarray(svc2.step({"a": X})["a"])
        )
        # ...a new admission cannot steal its slot...
        slot_b = svc2.admit("b")
        assert slot_b != sessions["a"]
        # ...and the RNG key resumed too: both services mint the SAME next
        # session (resume equivalence), which differs from session "a"'s init
        slot_b_orig = svc.admit("b")
        np.testing.assert_array_equal(
            np.asarray(svc.bank.slot_state(svc.state, slot_b_orig).B),
            np.asarray(svc2.bank.slot_state(svc2.state, slot_b).B),
        )
        assert not np.array_equal(
            np.asarray(svc2.bank.slot_state(svc2.state, slot_b).B),
            np.asarray(svc2.bank.slot_state(svc2.state, sessions["a"]).B),
        )

    def test_restore_validates_session_map(self, tmp_path):
        from repro.checkpoint.checkpointer import Checkpointer

        svc = self._svc()
        svc.admit("live")
        svc.step({"live": jax.random.normal(jax.random.PRNGKey(0), (8, 4))})
        ckpt = Checkpointer(tmp_path)
        svc.save(ckpt, step=0)
        state_before = jax.tree.map(np.asarray, svc.state._asdict())
        with pytest.raises(ValueError, match="out of range"):
            svc.restore(ckpt, sessions={"a": 99})
        with pytest.raises(ValueError, match="duplicate"):
            svc.restore(ckpt, sessions={"a": 0, "b": 0})
        # a rejected restore must leave the live service fully untouched
        assert svc.sessions == {"live": 0}
        for k, v in svc.state._asdict().items():
            np.testing.assert_array_equal(np.asarray(v), state_before[k])

    def test_empty_tick_is_noop(self):
        svc = self._svc()
        svc.admit("a")
        state_before = svc.state
        assert svc.step({}) == {}
        assert svc.state is state_before  # no fused launch dispatched

    def test_fused_service_matches_vmap_service(self):
        """The zero-copy fused tick (padded staging + megakernel + donated
        state) must serve the same outputs as the vmap bank service."""
        ecfg = EASIConfig(n_components=2, n_features=4, mu=2e-3)
        ocfg = SMBGDConfig(batch_size=8, mu=2e-3, beta=0.9, gamma=0.5)
        svc_r = SeparationService(SeparatorBank(ecfg, ocfg, n_streams=4), seed=0)
        svc_f = SeparationService(
            SeparatorBank(ecfg, ocfg, n_streams=4, fused=True), seed=0
        )
        for svc in (svc_r, svc_f):
            svc.admit("u1")
            svc.admit("u2")
        for k in range(5):
            X1 = jax.random.normal(jax.random.PRNGKey(10 + k), (8, 4))
            X2 = jax.random.normal(jax.random.PRNGKey(20 + k), (8, 4))
            o_r = svc_r.step({"u1": X1, "u2": X2})
            o_f = svc_f.step({"u1": X1, "u2": X2})
            assert o_f["u1"].shape == (8, 2)  # padded Y sliced at the boundary
            for sid in o_r:
                np.testing.assert_allclose(
                    np.asarray(o_r[sid]), np.asarray(o_f[sid]), rtol=1e-5, atol=1e-5
                )
        f_r, f_f = svc_r.evict("u1"), svc_f.evict("u1")
        assert f_f.B.shape == (2, 4)  # eviction hands back logical state
        np.testing.assert_allclose(
            np.asarray(f_r.B), np.asarray(f_f.B), rtol=1e-5, atol=1e-5
        )


def _mk_svc(S=2, P=8, fused=False, **kw):
    ecfg = EASIConfig(n_components=2, n_features=4, mu=2e-3)
    ocfg = SMBGDConfig(batch_size=P, mu=2e-3, beta=0.9, gamma=0.5)
    return SeparationService(
        SeparatorBank(ecfg, ocfg, n_streams=S, fused=fused), seed=0, **kw
    )


def _batch(seed, P=8, m=4):
    return jax.random.normal(jax.random.PRNGKey(seed), (P, m))


class TestAdmissionQueue:
    """Bounded backpressure: admit() enqueues instead of raising."""

    def test_queue_fifo_order_under_backpressure(self):
        svc = _mk_svc(S=2, max_queue=3)
        assert svc.admit("a") is not None and svc.admit("b") is not None
        assert svc.admit("c") is None and svc.admit("d") is None
        assert svc.admit("e") is None
        assert svc.queued == ("c", "d", "e")
        assert svc.status("c") == "queued" and svc.metrics["n_queued"] == 3
        with pytest.raises(RuntimeError, match="bank full"):
            svc.admit("f")  # queue full too → backpressure raises
        with pytest.raises(ValueError, match="already admitted"):
            svc.admit("c")  # queued ids are already admitted
        # manual evictions drain the queue head-first into the freed slots
        slot_a = svc.sessions["a"]
        svc.evict("a")
        assert svc.status("c") == "active" and svc.sessions["c"] == slot_a
        svc.evict("b")
        assert svc.status("d") == "active"
        assert svc.queued == ("e",)

    def test_zero_queue_keeps_legacy_backpressure(self):
        svc = _mk_svc(S=1)  # max_queue defaults to 0
        svc.admit("a")
        with pytest.raises(RuntimeError, match="bank full"):
            svc.admit("b")

    def test_queued_session_activates_with_gamma_gate(self):
        """A backfilled session's separator is born at activation: step==0, so
        its first served tick gates γ (the paper's first-batch rule)."""
        svc = _mk_svc(S=1, max_queue=1)
        svc.admit("a")
        svc.admit("b")
        for k in range(3):
            svc.step({"a": _batch(k)})
        svc.evict("a")
        slot = svc.sessions["b"]
        assert int(svc.bank.slot_state(svc.state, slot).step) == 0

    def test_evict_queued_dequeues(self):
        svc = _mk_svc(S=1, max_queue=2)
        svc.admit("a")
        svc.admit("q1")
        svc.admit("q2")
        assert svc.evict("q1") is None  # cancellation: no device state
        assert svc.queued == ("q2",)
        assert svc.status("q1") == "unknown"
        # the free list was untouched: evicting the active session now
        # backfills q2 into the single slot
        svc.evict("a")
        assert svc.status("q2") == "active" and svc.n_free == 0

    def test_step_rejects_queued_and_unknown_ids(self):
        """The bugfix: a batch for a session with no slot must raise a
        KeyError that names the id's actual state (queued vs unknown) —
        never silently drop the data (mirrors the PR-3 ``evict`` fix)."""
        svc = _mk_svc(S=1, max_queue=2)
        svc.admit("active")
        svc.admit("waiting")
        with pytest.raises(KeyError, match="queued with no slot yet.*waiting"):
            svc.step({"active": _batch(0), "waiting": _batch(1)})
        with pytest.raises(KeyError, match="not active.*ghost"):
            svc.step({"ghost": _batch(2)})
        # the rejected tick touched nothing: the active session still serves
        assert svc.session_stats("active")["ticks"] == 0
        out = svc.step({"active": _batch(3)})
        assert set(out) == {"active"}

    def test_evict_unknown_raises_keyerror_and_corrupts_nothing(self):
        """The bugfix: an unknown id must raise KeyError without touching the
        free list (previously .pop(...) raised but a later variant could have
        appended a bogus slot)."""
        svc = _mk_svc(S=2, max_queue=1)
        svc.admit("a")
        free_before, sessions_before = svc.n_free, svc.sessions
        with pytest.raises(KeyError, match="neither active nor queued"):
            svc.evict("ghost")
        assert svc.n_free == free_before and svc.sessions == sessions_before
        # the service still serves and admits normally afterwards
        svc.admit("b")
        out = svc.step({"a": _batch(0), "b": _batch(1)})
        assert set(out) == {"a", "b"}


class TestSchedulers:
    """Pluggable admission policy: priority + per-tenant quotas, EDF."""

    def _svc(self, scheduler, S=2, **kw):
        from repro.serve import SeparationService
        from repro.stream import SeparatorBank

        ecfg = EASIConfig(n_components=2, n_features=4, mu=2e-3)
        ocfg = SMBGDConfig(batch_size=8, mu=2e-3, beta=0.9, gamma=0.5)
        return SeparationService(
            SeparatorBank(ecfg, ocfg, n_streams=S), seed=0,
            scheduler=scheduler, **kw,
        )

    def test_priority_orders_backfill(self):
        from repro.serve import PriorityScheduler

        svc = self._svc(PriorityScheduler(max_queue=4), S=1)
        svc.admit("running")
        svc.admit("low", priority=1.0)
        svc.admit("high", priority=9.0)
        svc.admit("mid", priority=5.0)
        assert svc.queued == ("high", "mid", "low")  # pop order, not FIFO
        svc.evict("running")
        assert svc.status("high") == "active"
        svc.evict("high")
        assert svc.status("mid") == "active"

    def test_priority_fifo_within_level(self):
        from repro.serve import PriorityScheduler

        svc = self._svc(PriorityScheduler(max_queue=4), S=1)
        svc.admit("running")
        svc.admit("first", priority=3.0)
        svc.admit("second", priority=3.0)
        svc.evict("running")
        assert svc.status("first") == "active"
        assert svc.queued == ("second",)

    def test_tenant_quota_gates_direct_admission_and_pop(self):
        from repro.serve import PriorityScheduler

        svc = self._svc(
            PriorityScheduler(max_queue=4, quotas={"acme": 1}), S=3
        )
        assert svc.admit("a1", tenant="acme") is not None
        # free slots exist, but acme is at quota → queued, not activated
        assert svc.admit("a2", tenant="acme") is None
        assert svc.status("a2") == "queued"
        # another tenant sails through
        assert svc.admit("b1", tenant="bravo") is not None
        # a2 activates only when acme's own slot frees
        svc.evict("b1")
        assert svc.status("a2") == "queued"  # b's slot freed: still gated
        svc.evict("a1")
        assert svc.status("a2") == "active"

    def test_deadline_scheduler_is_edf(self):
        from repro.serve import DeadlineScheduler

        svc = self._svc(DeadlineScheduler(max_queue=4), S=1)
        svc.admit("running")
        svc.admit("lax", deadline=90.0)
        svc.admit("urgent", deadline=10.0)
        svc.admit("whenever")  # no deadline: sorts last
        assert svc.queued == ("urgent", "lax", "whenever")
        svc.evict("running")
        assert svc.status("urgent") == "active"

    def test_scheduler_snapshot_roundtrip_preserves_meta(self):
        from repro.serve import PriorityScheduler, SessionMeta

        sched = PriorityScheduler(max_queue=4)
        sched.push("a", SessionMeta(tenant="t", priority=2.0, order=0))
        sched.push("b", SessionMeta(priority=7.0, order=1))
        snap = sched.snapshot()
        fresh = PriorityScheduler(max_queue=4)
        fresh.load(snap)
        assert fresh.ids() == ("b", "a")
        assert fresh.meta_of("a").tenant == "t"
        # PR-3 plain-sid lists still load (metadata defaults)
        legacy = PriorityScheduler(max_queue=4)
        legacy.load(["x", "y"])
        assert legacy.ids() == ("x", "y")

    def test_backpressure_still_raises_when_full(self):
        from repro.serve import PriorityScheduler

        svc = self._svc(PriorityScheduler(max_queue=1), S=1)
        svc.admit("a")
        svc.admit("b", priority=1.0)
        with pytest.raises(RuntimeError, match="bank full"):
            svc.admit("c", priority=99.0)  # priority buys order, not capacity


class TestSchedulerPropertyInvariants:
    """Satellite property sweep: random admit/evict/park/readmit traffic
    against the pluggable schedulers must never exceed tenant quotas, never
    drop or duplicate a session id, and always pop in EDF/priority order."""

    QUOTAS = {"t0": 1, "t1": 2}

    def _mk(self, kind):
        from repro.serve import (
            DeadlineScheduler,
            DriftPolicy,
            PriorityScheduler,
            SeparationService,
        )
        from repro.stream import SeparatorBank

        ecfg = EASIConfig(n_components=2, n_features=4, mu=2e-3)
        ocfg = SMBGDConfig(batch_size=8, mu=2e-3, beta=0.9, gamma=0.5)
        sched = (
            PriorityScheduler(max_queue=6, quotas=dict(self.QUOTAS))
            if kind == "priority"
            else DeadlineScheduler(max_queue=6)
        )
        return SeparationService(
            SeparatorBank(ecfg, ocfg, n_streams=3),
            seed=0,
            # trivially-satisfiable convergence → sessions park quickly, and
            # an always-above retrigger readmits them — maximum lifecycle
            # churn through the scheduler per tick
            policy=ConvergencePolicy(threshold=1e9, patience=1, min_ticks=2),
            drift_policy=DriftPolicy(
                mode="readmit", retrigger=1e-12, patience=1, cooldown=0,
                probe_every=1, probe_batch=4,
            ),
            scheduler=sched,
        )

    def _check_invariants(self, svc, kind, admitted, cancelled, meta_of):
        S = svc.bank.n_streams
        # slots conserved, never double-booked
        assert svc.n_active + svc.n_free == S
        slots = list(svc._slot_of.values())
        assert len(set(slots)) == len(slots)
        # no sid dropped or duplicated: every admitted sid is in exactly one
        # lifecycle bucket (cancelled queued sessions leave the system)
        buckets = {
            "active": set(svc.sessions),
            "queued": set(svc.queued),
            "parked": set(svc.parked),
            "finished": set(svc.finished),
        }
        seen = set()
        for ids in buckets.values():
            assert not ids & seen, f"sid in two buckets: {ids & seen}"
            seen |= ids
        for sid in admitted:
            if sid in cancelled:
                assert sid not in seen
            else:
                assert sid in seen, f"sid dropped: {sid}"
        # tenant quotas bound ACTIVE sessions at all times
        if kind == "priority":
            counts = {}
            for sid in svc.sessions:
                t = meta_of[sid][0]
                counts[t] = counts.get(t, 0) + 1
            for t, q in self.QUOTAS.items():
                assert counts.get(t, 0) <= q, f"tenant {t} over quota"
        # pop order: queued ids sorted by the policy's advertised key
        queued = svc.queued
        if kind == "priority":
            prios = [meta_of[sid][1] for sid in queued]
            assert prios == sorted(prios, reverse=True)
        else:
            deadlines = [meta_of[sid][2] for sid in queued]
            dated = [d for d in deadlines if d is not None]
            # every dated session pops before every dateless one, EDF inside
            assert deadlines[: len(dated)] == sorted(dated)
            assert all(d is None for d in deadlines[len(dated):])

    @pytest.mark.property
    @given(
        seed=st.integers(0, 10_000),
        kind=st.sampled_from(["priority", "deadline"]),
    )
    @settings(max_examples=10, deadline=None)
    def test_random_lifecycle_preserves_invariants(self, seed, kind):
        from repro.data.sources import ReplaySource

        rng = np.random.default_rng(seed)
        svc = self._mk(kind)
        data = rng.standard_normal((64 * 8, 4)).astype(np.float32)
        admitted, cancelled = [], set()
        meta_of = {}
        next_id = 0
        for _ in range(40):
            op = ("admit", "evict", "tick", "tick")[rng.integers(4)]
            if op == "admit":
                sid = f"s{next_id}"
                next_id += 1
                tenant = (None, "t0", "t1")[rng.integers(3)]
                priority = float(rng.integers(10))
                deadline = (
                    None if rng.integers(2) else float(rng.integers(100))
                )
                try:
                    svc.admit(
                        sid,
                        source=ReplaySource(data, loop=True),
                        tenant=tenant,
                        priority=priority,
                        deadline=deadline,
                    )
                except RuntimeError:
                    pass  # backpressure: sid never entered the system
                else:
                    admitted.append(sid)
                    meta_of[sid] = (tenant, priority, deadline)
            elif op == "evict" and admitted:
                sid = admitted[rng.integers(len(admitted))]
                status = svc.status(sid)
                try:
                    out = svc.evict(sid)
                except KeyError:
                    assert status in ("finished", "unknown")
                else:
                    if status == "queued":
                        assert out is None
                        cancelled.add(sid)
            else:
                svc.run_tick()
            self._check_invariants(svc, kind, admitted, cancelled, meta_of)


class TestConvergenceLifecycle:
    """Auto-eviction on convergence + same-tick backfill."""

    # random normal data keeps the separator jittering around a small but
    # finite update magnitude, so a generous threshold makes "convergence"
    # deterministic after min_ticks/patience — the machinery under test is
    # the lifecycle, not the ICA (tests/test_convergence.py covers that)
    POLICY = ConvergencePolicy(threshold=10.0, patience=2, min_ticks=3)

    @pytest.mark.parametrize("fused", [False, True])
    def test_auto_evict_and_same_tick_backfill(self, fused):
        events = []
        svc = _mk_svc(
            S=2, fused=fused, policy=self.POLICY, max_queue=2,
            on_admit=lambda sid, slot: events.append(("admit", sid, slot)),
            on_evict=lambda sid, rec: events.append(("evict", sid, rec.reason)),
        )
        for sid in ("a", "b", "c", "d"):
            svc.admit(sid)
        assert svc.queued == ("c", "d")
        ticks_to_evict = None
        for k in range(6):
            served = [s for s in ("a", "b") if svc.status(s) == "active"]
            if not served:
                break
            svc.step({sid: _batch(10 * k + i) for i, sid in enumerate(served)})
            if svc.status("a") == "finished" and ticks_to_evict is None:
                ticks_to_evict = k + 1
        # converged exactly when min_ticks AND patience were first satisfied
        assert ticks_to_evict == max(self.POLICY.min_ticks, self.POLICY.patience)
        rec = svc.finished["a"]
        assert rec.reason == "converged"
        assert rec.stats.ticks == ticks_to_evict
        assert rec.monitor.below >= self.POLICY.patience
        # same-tick backfill: at the eviction tick the queue head was already
        # active (events interleave evict→admit within one step() call)
        i_evict = events.index(("evict", "a", "converged"))
        backfills = [e for e in events[i_evict:] if e[0] == "admit"]
        assert backfills and backfills[0][1] == "c"
        assert svc.status("c") == "active"
        assert svc.metrics["n_auto_evicted"] >= 1

    def test_evicted_state_fidelity(self):
        """The auto-evicted SMBGDState must equal slot_state at eviction: a
        session stepped through churn follows exactly the trajectory of a
        standalone separator with the same init."""
        svc = _mk_svc(S=2, policy=self.POLICY, max_queue=2)
        svc.admit("only")
        slot = svc.sessions["only"]
        st_ref = svc.bank.slot_state(svc.state, slot)
        ecfg, ocfg = svc.bank.easi, svc.bank.opt
        k = 0
        while svc.status("only") == "active":
            X = _batch(100 + k)
            svc.step({"only": X})
            st_ref, _ = smbgd_lib.smbgd_batched_step(st_ref, X, ecfg, ocfg)
            k += 1
            assert k < 20, "policy never fired"
        final = svc.finished["only"].state
        np.testing.assert_allclose(
            np.asarray(final.B), np.asarray(st_ref.B), rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            np.asarray(final.H_hat), np.asarray(st_ref.H_hat), rtol=1e-5, atol=1e-6
        )
        assert int(final.step) == int(st_ref.step)

    def test_min_ticks_and_patience_gate_eviction(self):
        svc = _mk_svc(
            S=1, policy=ConvergencePolicy(threshold=10.0, patience=3, min_ticks=5)
        )
        svc.admit("a")
        for k in range(4):
            svc.step({"a": _batch(k)})
            assert svc.status("a") == "active"  # min_ticks floor holds
        svc.step({"a": _batch(99)})
        assert svc.status("a") == "finished"

    def test_idle_ticks_do_not_advance_convergence(self):
        """Only data ticks count: an idle session's monitor must not move."""
        svc = _mk_svc(S=2, policy=self.POLICY)
        svc.admit("busy")
        svc.admit("idle")
        k = 0
        while svc.status("busy") == "active":
            svc.step({"busy": _batch(k)})
            k += 1
            assert k < 10, "policy never fired"
        assert svc.status("busy") == "finished"
        assert svc.status("idle") == "active"
        assert svc.session_stats("idle")["conv_below"] == 0

    def test_amari_gate_vetoes_blind_eviction(self):
        """With a registered mixing matrix and an unreachable Amari bar, the
        blind statistic alone must NOT evict."""
        svc = _mk_svc(
            S=1,
            policy=ConvergencePolicy(
                threshold=10.0, patience=2, min_ticks=2, amari_threshold=1e-9
            ),
        )
        svc.admit("a")
        svc.set_mixing("a", jnp.eye(4)[:, :2])
        for k in range(6):
            svc.step({"a": _batch(k)})
        assert svc.status("a") == "active"  # vetoed every tick
        # unknown mixing → the blind statistic decides (same policy)
        svc2 = _mk_svc(
            S=1,
            policy=ConvergencePolicy(
                threshold=10.0, patience=2, min_ticks=2, amari_threshold=1e-9
            ),
        )
        svc2.admit("a")
        k = 0
        while svc2.status("a") == "active":
            svc2.step({"a": _batch(k)})
            k += 1
            assert k < 10, "policy never fired"
        assert svc2.status("a") == "finished"

    def test_seeded_churn_scenario(self):
        """Admissions interleaved with convergence-driven evictions: every
        session is served, evicted exactly once, keeps its stats, and the
        bank never over- or under-fills."""
        svc = _mk_svc(S=2, fused=True, policy=self.POLICY, max_queue=8)
        all_sids = [f"s{i}" for i in range(8)]
        pending = list(all_sids)
        for sid in pending[:4]:
            svc.admit(sid)
        pending = pending[4:]
        rng = np.random.default_rng(0)
        for tick in range(40):
            if pending and rng.random() < 0.5:  # interleaved arrivals
                svc.admit(pending.pop(0))
            served = [s for s in all_sids if svc.status(s) == "active"]
            if not served and not pending and not svc.queued:
                break
            if served:
                svc.step(
                    {s: _batch(1000 + 31 * tick + i) for i, s in enumerate(served)}
                )
            assert svc.n_active + svc.n_free == 2  # slots conserved
        finished = svc.pop_finished()
        assert sorted(finished) == sorted(all_sids)
        for sid, rec in finished.items():
            assert rec.reason == "converged"
            # per-session stats preserved through eviction
            assert rec.stats.ticks >= self.POLICY.min_ticks
            assert rec.stats.samples == rec.stats.ticks * 8
            assert rec.monitor.below >= self.POLICY.patience
        assert svc.metrics["n_auto_evicted"] == len(all_sids)
        assert svc.pop_finished() == {}  # drained

    def test_monitor_ema_matches_metrics_ema_update(self):
        """ConvergenceMonitor's host-side EMA must track core.metrics'
        jit-safe ema_update exactly (the two implementations are twins and
        must not drift)."""
        from repro.core import ema_update
        from repro.serve.engine import ConvergenceMonitor

        pol = ConvergencePolicy(threshold=0.1, patience=2, min_ticks=1, ema=0.7)
        mon = ConvergenceMonitor()
        smoothed = jnp.asarray(float("inf"))
        for x in (0.8, 0.4, 0.2, 0.05, 0.03):
            mon.update(x, pol)
            smoothed = ema_update(smoothed, x, pol.ema)
            np.testing.assert_allclose(mon.stat, float(smoothed), rtol=1e-6)
        # ema=0 passes raw values through in both
        mon0 = ConvergenceMonitor()
        pol0 = ConvergencePolicy(threshold=0.1, ema=0.0)
        mon0.update(0.25, pol0)
        assert mon0.stat == 0.25 == float(ema_update(jnp.inf, 0.25, 0.0))

    def test_lifecycle_snapshot_roundtrip_in_memory(self):
        svc = _mk_svc(S=2, policy=self.POLICY, max_queue=3)
        for sid in ("a", "b", "c"):
            svc.admit(sid)
        svc.step({"a": _batch(0), "b": _batch(1)})
        snap = svc.lifecycle
        assert snap["sessions"] == {"a": 0, "b": 1}
        # queue entries carry scheduling metadata now ([sid, meta] pairs);
        # restore() still also accepts the PR-3 plain-sid list format
        assert [sid for sid, _meta in snap["queue"]] == ["c"]
        assert snap["monitors"]["a"]["ticks"] == 1


class TestServiceMetrics:
    """Per-tick latency and per-session samples/sec counters (the ROADMAP
    metrics stub): counted on every flavour of bank."""

    def _svc(self, fused=False, **kw):
        ecfg = EASIConfig(n_components=2, n_features=4, mu=2e-3)
        ocfg = SMBGDConfig(batch_size=8, mu=2e-3, beta=0.9, gamma=0.5)
        return SeparationService(
            SeparatorBank(ecfg, ocfg, n_streams=4, fused=fused), seed=0, **kw
        )

    @pytest.mark.parametrize("fused", [False, True])
    def test_tick_and_sample_counters(self, fused):
        svc = self._svc(fused=fused, block_ticks=True)
        svc.admit("a")
        svc.admit("b")
        m0 = svc.metrics
        assert m0["n_ticks"] == 0 and np.isnan(m0["last_tick_s"])
        X = jax.random.normal(jax.random.PRNGKey(0), (8, 4))
        svc.step({"a": X, "b": X})
        svc.step({"a": X})
        m = svc.metrics
        assert m["n_ticks"] == 2
        assert m["total_samples"] == 8 * 3  # two sessions + one session
        assert m["last_tick_s"] > 0 and m["mean_tick_s"] > 0
        assert m["samples_per_s"] > 0
        assert m["n_active"] == 2 and m["n_free"] == 2

    def test_per_session_stats(self):
        svc = self._svc(block_ticks=True)
        svc.admit("busy")
        svc.admit("idle")
        X = jax.random.normal(jax.random.PRNGKey(1), (8, 4))
        for _ in range(3):
            svc.step({"busy": X})
        busy, idle = svc.session_stats("busy"), svc.session_stats("idle")
        assert busy["ticks"] == 3 and busy["samples"] == 24
        assert busy["samples_per_s"] > 0
        assert idle["ticks"] == 0 and idle["samples"] == 0
        svc.evict("busy")
        with pytest.raises(KeyError):
            svc.session_stats("busy")

    def test_restore_restarts_counters(self, tmp_path):
        from repro.checkpoint.checkpointer import Checkpointer

        svc = self._svc()
        svc.admit("a")
        X = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
        svc.step({"a": X})
        ckpt = Checkpointer(tmp_path)
        svc.save(ckpt, step=1)
        svc2 = self._svc()
        svc2.admit("a")
        svc2.step({"a": X})
        assert svc2.metrics["n_ticks"] == 1  # pre-restore traffic...
        svc2.restore(ckpt, sessions=svc.sessions)
        stats = svc2.session_stats("a")  # re-attached session is countable
        assert stats["ticks"] == 0
        # ...and BOTH observability surfaces restart at the restored epoch
        m = svc2.metrics
        assert m["n_ticks"] == 0 and m["total_samples"] == 0
        assert np.isnan(m["last_tick_s"])
        svc2.step({"a": X})
        assert svc2.session_stats("a")["ticks"] == 1
        assert svc2.metrics["n_ticks"] == 1


class TestAdaptiveICADeployment:
    """The paper's deployment story: train+deploy in one system, tracking
    non-stationary mixing."""

    @pytest.mark.slow
    def test_streaming_partial_fit_tracks_drift(self):
        ecfg = EASIConfig(n_components=2, n_features=4, mu=3e-3)
        ocfg = SMBGDConfig(batch_size=16, mu=3e-3, beta=0.9, gamma=0.5)
        ica = AdaptiveICA(ecfg, ocfg)
        state = ica.init(jax.random.PRNGKey(0))
        pipe = MixedSignals(m=4, n=2, batch=16, seed=0, drift_rate=2e-6)
        fit = jax.jit(lambda s, x: ica.partial_fit(s, x))

        # converge on early mixing
        for step in range(1500):
            state, _ = fit(state, pipe.batch_for_step(step))
        pi_early = float(amari_index(global_system(state.B, pipe.mixing_at(1500))))
        # keep streaming while A(t) drifts; separator must keep tracking
        for step in range(1500, 3000):
            state, _ = fit(state, pipe.batch_for_step(step))
        pi_late = float(amari_index(global_system(state.B, pipe.mixing_at(3000))))
        assert pi_early < 0.2
        assert pi_late < 0.25, f"lost track under drift: {pi_late}"

    def test_transform_is_pure_deployment(self):
        ecfg = EASIConfig(n_components=2, n_features=4)
        ica = AdaptiveICA(ecfg, SMBGDConfig())
        state = ica.init(jax.random.PRNGKey(0))
        X = jax.random.normal(jax.random.PRNGKey(1), (100, 4))
        Y1 = ica.transform(state, X)
        Y2 = ica.transform(state, X)
        np.testing.assert_array_equal(np.asarray(Y1), np.asarray(Y2))
        assert Y1.shape == (100, 2)
