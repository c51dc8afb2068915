"""Deterministic, shard-aware, exactly-resumable data pipelines.

Every batch is a pure function of ``(seed, step)`` — there is no iterator
state to checkpoint: after a restart the trainer just continues from
``step+1`` and sees exactly the stream it would have seen.  This is the
property that makes checkpoint/restore and elastic restarts exact.

Sources:
  * ``SyntheticLM`` — structured pseudo-text (Zipfian unigrams + deterministic
    bigram chains) so perplexity actually falls during the example runs,
  * ``MemmapTokens`` — binary token file (np.memmap) with step-derived offsets,
  * ``MixedSignals`` — the ICA substrate: mixed sources for EASI training.

Shard-awareness: ``batch_for_step`` takes (dp_rank, dp_size) and returns the
local slice of the global batch — ranks see disjoint data, and the global
stream is invariant to dp_size (elastic-safe).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


@dataclasses.dataclass(frozen=True)
class SyntheticLM:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    n_codebooks: int = 0
    vision_tokens: int = 0
    d_model: int = 0  # for vision stub embeddings

    def batch_for_step(self, step: int, dp_rank: int = 0, dp_size: int = 1) -> Dict[str, jnp.ndarray]:
        """The GLOBAL batch is a pure function of (seed, step); ranks slice it.
        The global stream is therefore invariant to dp_size (elastic-safe)."""
        assert self.global_batch % dp_size == 0
        local = self.global_batch // dp_size
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), step)
        kz, kc, kv = jax.random.split(key, 3)
        T = self.seq_len - (self.vision_tokens or 0)
        gb = self.global_batch
        shape = (gb, T, self.n_codebooks) if self.n_codebooks else (gb, T)
        # Zipf-ish unigram draw via exponential transform of uniforms
        u = jax.random.uniform(kz, shape, minval=1e-6, maxval=1.0)
        zipf = jnp.minimum(
            (1.0 / u**0.7).astype(jnp.int32) % self.vocab_size, self.vocab_size - 1
        )
        # deterministic bigram structure: every other token = f(prev) → learnable
        nxt = (zipf * 31 + 7) % self.vocab_size
        toks = jnp.where(
            (jnp.arange(T) % 2 == 1)[(None,) * (zipf.ndim - (2 if self.n_codebooks else 1))].reshape(
                (1, T) + ((1,) if self.n_codebooks else ())
            ),
            jnp.roll(nxt, 1, axis=1),
            zipf,
        )
        sl = slice(dp_rank * local, (dp_rank + 1) * local)
        out = {"tokens": toks[sl]}
        if self.vision_tokens:
            out["vision_embeds"] = (
                jax.random.normal(kv, (gb, self.vision_tokens, self.d_model))[sl]
                * 0.02
            )
        return out


@dataclasses.dataclass(frozen=True)
class MemmapTokens:
    """Pretokenized corpus: flat int32 file, step-derived strided windows."""

    path: str
    seq_len: int
    global_batch: int
    seed: int = 0

    def __post_init__(self):
        object.__setattr__(
            self, "_data", np.memmap(self.path, dtype=np.int32, mode="r")
        )

    @property
    def n_tokens(self) -> int:
        return len(self._data)

    def batch_for_step(self, step: int, dp_rank: int = 0, dp_size: int = 1) -> Dict[str, jnp.ndarray]:
        assert self.global_batch % dp_size == 0
        local = self.global_batch // dp_size
        n_windows = self.n_tokens // (self.seq_len + 1)
        rng = np.random.default_rng(self.seed + step * 1_000_003)
        idx = rng.integers(0, n_windows, size=(self.global_batch,))
        idx = idx[dp_rank * local : (dp_rank + 1) * local]
        rows = np.stack(
            [self._data[i * (self.seq_len + 1) : i * (self.seq_len + 1) + self.seq_len] for i in idx]
        )
        return {"tokens": jnp.asarray(rows)}


@functools.lru_cache(maxsize=64)
def _base_mixing_cached(pipe: "MixedSignals", lo: int, hi: int) -> jnp.ndarray:
    """Per-stream stationary mixing matrices ``(hi-lo, m, n)`` — a pure
    function of the pipe's seeds, so computed (batched SVD) once per
    (pipe, range), not once per tick."""
    seeds, _ = pipe._stream_params(lo, hi)
    return jax.jit(jax.vmap(pipe._base_mixing))(seeds)


@functools.partial(jax.jit, static_argnums=0)  # frozen dataclass → hashable
def _streamed_batch_jit(pipe: "MixedSignals", seeds, A0s, phases, step) -> jnp.ndarray:
    """vmap the per-stream generator over the (local) stream axis; jitted so a
    bank serving loop pays one compiled dispatch per tick, not S traces."""
    return jax.vmap(lambda sd, a0, ph: pipe._stream_batch(sd, a0, ph, step))(
        seeds, A0s, phases
    )


def make_lm_pipeline(cfg: ModelConfig, seq_len: int, global_batch: int, seed: int = 0) -> SyntheticLM:
    return SyntheticLM(
        vocab_size=cfg.vocab_size,
        seq_len=seq_len,
        global_batch=global_batch,
        seed=seed,
        n_codebooks=cfg.n_codebooks,
        vision_tokens=cfg.vision_tokens,
        d_model=cfg.d_model,
    )


@dataclasses.dataclass(frozen=True)
class MixedSignals:
    """Streaming ICA input: (optionally drifting) mixtures, step-addressable.

    With ``streams == 0`` (default) this is the legacy single-stream source:
    ``batch_for_step`` returns ``(batch, m)`` and dp ranks slice the batch.

    With ``streams = S > 0`` the pipeline grows a leading stream axis — the
    substrate for ``repro.stream.SeparatorBank``: ``batch_for_step`` returns
    ``(S, batch, m)`` where stream s has its own seed (own sources, own mixing
    matrix) and its own drift phase, so a bank sees S genuinely distinct
    separation problems.  dp ranks then slice the *stream* axis (streams are
    the unit of device parallelism; ``streams % dp_size == 0``), matching
    ``stream.sharding.make_sharded_bank_step``.
    """

    m: int = 4
    n: int = 2
    batch: int = 8
    seed: int = 0
    drift_rate: float = 0.0  # >0: non-stationary mixing (adaptive regime)
    streams: int = 0  # 0 → legacy single-stream; S>0 → leading (S, ...) axis

    # per-stream seed/drift-phase derivation (stream=None → legacy stream)
    def _stream_seed(self, stream: Optional[int]) -> int:
        # reduced modulo the Mersenne prime 2^31 - 1 so every seed fits the
        # int32 JAX traces it as (unreduced, stream 2146 of seed 0 overflows);
        # the multiplier is invertible mod a prime, so seeds never collide,
        # and streams below the overflow keep their seeds
        if stream is None:
            return self.seed
        return (self.seed + 1_000_003 * (stream + 1)) % (2**31 - 1)

    def _drift_phase(self, stream: Optional[int]) -> float:
        # golden-angle stagger so concurrent streams never drift in phase
        return 0.0 if stream is None else 2.399963229728653 * (stream + 1)

    def _base_mixing(self, seed) -> jnp.ndarray:
        """Stationary mixing matrix A0 from a (traced) seed."""
        from repro.data import signals

        return signals.random_mixing_matrix(jax.random.PRNGKey(seed), self.m, self.n)

    def _drift(self, A0: jnp.ndarray, phase, step) -> jnp.ndarray:
        """Apply the drift rotation (no-op when drift_rate == 0)."""
        if not self.drift_rate:
            return A0
        theta = self.drift_rate * step * self.batch + phase
        c, s = jnp.cos(theta), jnp.sin(theta)
        R = jnp.eye(self.m).at[0, 0].set(c).at[1, 1].set(c).at[0, 1].set(-s).at[1, 0].set(s)
        return R @ A0

    def _mixing_traced(self, seed, phase, step) -> jnp.ndarray:
        """Mixing matrix from traced (seed, phase, step) — vmap/jit-safe."""
        return self._drift(self._base_mixing(seed), phase, step)

    def mixing_at(self, step: int, stream: Optional[int] = None) -> jnp.ndarray:
        """Mixing matrix at ``step``: ``(m, n)`` for one stream, or stacked
        ``(S, m, n)`` when ``streams > 0`` and ``stream`` is omitted."""
        if self.streams and stream is None:
            seeds, phases = self._stream_params(0, self.streams)
            return jax.vmap(lambda sd, ph: self._mixing_traced(sd, ph, step))(
                seeds, phases
            )
        return self._mixing_traced(
            self._stream_seed(stream), self._drift_phase(stream), step
        )

    def _stream_batch(self, seed, A0, phase, step) -> jnp.ndarray:
        """One stream's ``(batch, m)`` mini-batch from traced params (``A0``
        is the precomputed stationary mixing matrix — drift applied here)."""
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), step)
        t = step * self.batch + jnp.arange(self.batch)
        # mixed sub-Gaussian bank: even components sinusoidal, odd uniform
        s_sine = jnp.sin(0.05 * t[:, None] + jnp.arange(self.n)[None, :] * 2.1)
        s_unif = jax.random.uniform(
            key, (self.batch, self.n), minval=-1.7320508, maxval=1.7320508
        )
        S = jnp.where(jnp.arange(self.n)[None, :] % 2 == 0, s_sine * 2**0.5, s_unif)
        A = self._drift(A0, phase, step)
        return S @ A.T

    @functools.lru_cache(maxsize=64)
    def _stream_params(self, lo: int, hi: int):
        """Per-stream (seeds, phases) arrays — pure in (self, lo, hi), cached
        so the per-tick path doesn't rebuild O(S) host lists."""
        seeds = jnp.asarray([self._stream_seed(s) for s in range(lo, hi)])
        phases = jnp.asarray([self._drift_phase(s) for s in range(lo, hi)])
        return seeds, phases

    def batch_for_step(self, step: int, dp_rank: int = 0, dp_size: int = 1) -> jnp.ndarray:
        """Global mini-batch is a pure function of (seed, step); ranks slice —
        the batch axis in single-stream mode, the stream axis in bank mode."""
        if self.streams:
            # one traced program generates the whole (local_S, batch, m) block:
            # at bank scale the fused separator step is a single dispatch, so
            # host-side data gen must not become an O(S) Python loop per tick
            assert self.streams % dp_size == 0
            local = self.streams // dp_size
            lo = dp_rank * local
            seeds, phases = self._stream_params(lo, lo + local)
            A0s = _base_mixing_cached(self, lo, lo + local)
            return _streamed_batch_jit(self, seeds, A0s, phases, step)
        assert self.batch % dp_size == 0
        X = self._stream_batch(
            self._stream_seed(None),
            self._base_mixing(self._stream_seed(None)),
            self._drift_phase(None),
            step,
        )
        local = self.batch // dp_size
        return X[dp_rank * local : (dp_rank + 1) * local]
