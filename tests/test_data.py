"""Data pipelines: determinism, rank-disjointness, elastic invariance."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.registry import get_config
from repro.data.pipeline import MixedSignals, SyntheticLM, make_lm_pipeline
from repro.data import signals


class TestSyntheticLM:
    def _pipe(self, **kw):
        base = dict(vocab_size=1000, seq_len=64, global_batch=8, seed=3)
        base.update(kw)
        return SyntheticLM(**base)

    def test_deterministic(self):
        p = self._pipe()
        a = p.batch_for_step(5)["tokens"]
        b = p.batch_for_step(5)["tokens"]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_steps_differ(self):
        p = self._pipe()
        a = p.batch_for_step(5)["tokens"]
        b = p.batch_for_step(6)["tokens"]
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    @given(dp=st.sampled_from([1, 2, 4, 8]))
    @settings(max_examples=4, deadline=None)
    def test_elastic_invariance(self, dp):
        """The global stream must not depend on dp_size (restart at a new
        cluster size sees the same data)."""
        p = self._pipe()
        full = p.batch_for_step(9, 0, 1)["tokens"]
        parts = [p.batch_for_step(9, r, dp)["tokens"] for r in range(dp)]
        np.testing.assert_array_equal(
            np.asarray(full), np.asarray(jnp.concatenate(parts, axis=0))
        )

    def test_rank_disjoint(self):
        p = self._pipe()
        a = p.batch_for_step(2, 0, 2)["tokens"]
        b = p.batch_for_step(2, 1, 2)["tokens"]
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_tokens_in_range_and_learnable_structure(self):
        p = self._pipe()
        t = np.asarray(p.batch_for_step(0)["tokens"])
        assert t.min() >= 0 and t.max() < 1000
        # bigram structure: odd positions are a deterministic fn of evens
        nxt = (t[:, 0::2] * 31 + 7) % 1000
        assert np.array_equal(t[:, 1::2], nxt[:, : t[:, 1::2].shape[1]])

    def test_modality_variants(self):
        mg = make_lm_pipeline(get_config("musicgen-large").reduced(), 32, 4)
        b = mg.batch_for_step(0)
        assert b["tokens"].shape == (4, 32, 4)
        vl = make_lm_pipeline(get_config("internvl2-76b").reduced(), 32, 4)
        b = vl.batch_for_step(0)
        assert b["tokens"].shape == (4, 32 - 8)
        assert b["vision_embeds"].shape == (4, 8, 64)


class TestMixedSignals:
    def test_deterministic_and_elastic(self):
        p = MixedSignals(m=4, n=2, batch=8, seed=0)
        a = p.batch_for_step(3)
        b = p.batch_for_step(3)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        parts = [p.batch_for_step(3, r, 2) for r in range(2)]
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(jnp.concatenate(parts, axis=0))
        )

    def test_drift_changes_mixing(self):
        p = MixedSignals(m=4, n=2, batch=8, seed=0, drift_rate=1e-3)
        A0 = p.mixing_at(0)
        A1 = p.mixing_at(500)
        assert float(jnp.max(jnp.abs(A0 - A1))) > 1e-2

    def test_stationary_mixing_constant(self):
        p = MixedSignals(m=4, n=2, batch=8, seed=0, drift_rate=0.0)
        np.testing.assert_array_equal(
            np.asarray(p.mixing_at(0)), np.asarray(p.mixing_at(999))
        )

    def test_stream_seeds_fit_int32_at_bank_scale(self):
        """4096 streams: every per-stream seed is distinct and traces as an
        int32 (stream 2146 of seed 0 used to overflow), and the bank block of
        the last stream is what that stream's own source generates."""
        from repro.data.sources import SyntheticSource

        p = MixedSignals(m=4, n=2, batch=8, seed=0, streams=4096)
        seeds = [p._stream_seed(s) for s in range(4096)]
        assert len(set(seeds)) == 4096
        assert max(seeds) + 1 <= np.iinfo(np.int32).max  # PRNGKey(seed + 1)
        np.testing.assert_allclose(
            np.asarray(p.batch_for_step(0)[4095]),
            SyntheticSource(p, stream=4095).next_block(8).T,
            rtol=1e-6, atol=1e-6,
        )


class TestSignalBank:
    def test_sources_zero_mean_unit_var(self):
        S = signals.source_bank(jax.random.PRNGKey(0), 4, 20_000)
        m = np.asarray(jnp.mean(S, axis=0))
        v = np.asarray(jnp.std(S, axis=0))
        np.testing.assert_allclose(m, 0, atol=1e-2)
        np.testing.assert_allclose(v, 1, atol=1e-2)

    def test_sources_sub_gaussian(self):
        """Cubic-nonlinearity EASI needs negative-kurtosis sources."""
        S = np.asarray(signals.source_bank(jax.random.PRNGKey(1), 4, 50_000))
        kurt = ((S**4).mean(0) / (S**2).mean(0) ** 2) - 3.0
        assert (kurt < 0).all(), kurt

    def test_mixing_matrix_conditioned(self):
        A = signals.random_mixing_matrix(jax.random.PRNGKey(2), 6, 3)
        s = np.linalg.svd(np.asarray(A), compute_uv=False)
        assert s[-1] > 0.05 * s[0]

    def test_nonstationary_mix_shapes(self):
        At = signals.drifting_mixing_matrix(jax.random.PRNGKey(3), 4, 2, 100)
        S = signals.source_bank(jax.random.PRNGKey(4), 2, 100)
        X = signals.mix_nonstationary(At, S)
        assert X.shape == (100, 4)


class TestDriftingMixing:
    """``drifting_mixing_matrix``/``mix_nonstationary``: rotation-rate
    correctness and determinism — the ground truth the drift pipeline's
    watchdog is measured against."""

    def test_rotation_rate_is_exact(self):
        """A(t) must equal R(rate·t)·A(0) — rotation by exactly ``rate``
        radians per step in the (0, 1) plane."""
        rate, T = 3e-3, 200
        At = np.asarray(
            signals.drifting_mixing_matrix(jax.random.PRNGKey(0), 4, 2, T, rate=rate)
        )
        for t in (1, 57, T - 1):
            theta = rate * t
            R = np.eye(4, dtype=np.float32)
            R[0, 0] = R[1, 1] = np.cos(theta)
            R[0, 1], R[1, 0] = -np.sin(theta), np.sin(theta)
            np.testing.assert_allclose(At[t], R @ At[0], rtol=1e-4, atol=1e-5)

    def test_rotation_preserves_conditioning(self):
        """Rotations are orthogonal: singular values of A(t) never change —
        the drifting problem stays exactly as solvable as the original."""
        At = np.asarray(
            signals.drifting_mixing_matrix(jax.random.PRNGKey(1), 4, 2, 300, rate=5e-3)
        )
        sv0 = np.linalg.svd(At[0], compute_uv=False)
        svT = np.linalg.svd(At[-1], compute_uv=False)
        np.testing.assert_allclose(sv0, svT, rtol=1e-4)

    def test_zero_rate_is_stationary(self):
        At = np.asarray(
            signals.drifting_mixing_matrix(jax.random.PRNGKey(2), 4, 2, 50, rate=0.0)
        )
        np.testing.assert_allclose(At, np.broadcast_to(At[0], At.shape), atol=1e-7)

    def test_deterministic_per_seed_distinct_across_seeds(self):
        a1 = np.asarray(signals.drifting_mixing_matrix(jax.random.PRNGKey(7), 4, 2, 40))
        a2 = np.asarray(signals.drifting_mixing_matrix(jax.random.PRNGKey(7), 4, 2, 40))
        b = np.asarray(signals.drifting_mixing_matrix(jax.random.PRNGKey(8), 4, 2, 40))
        np.testing.assert_array_equal(a1, a2)
        assert np.abs(a1 - b).max() > 1e-3

    def test_mix_nonstationary_matches_per_step_matmul(self):
        key = jax.random.PRNGKey(3)
        At = signals.drifting_mixing_matrix(key, 4, 2, 30, rate=1e-2)
        S = signals.source_bank(jax.random.PRNGKey(4), 2, 30)
        X = np.asarray(signals.mix_nonstationary(At, S))
        expected = np.stack(
            [np.asarray(At[t]) @ np.asarray(S[t]) for t in range(30)]
        )
        np.testing.assert_allclose(X, expected, rtol=1e-5, atol=1e-6)

    def test_mix_nonstationary_constant_equals_stationary_mix(self):
        A = signals.random_mixing_matrix(jax.random.PRNGKey(5), 4, 2)
        S = signals.source_bank(jax.random.PRNGKey(6), 2, 25)
        At = jnp.broadcast_to(A, (25, 4, 2))
        np.testing.assert_allclose(
            np.asarray(signals.mix_nonstationary(At, S)),
            np.asarray(signals.mix(A, S)),
            rtol=1e-5,
            atol=1e-6,
        )
