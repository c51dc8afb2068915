"""Pluggable signal sources for the drift-aware serving pipeline.

The paper's datapath is fed by a fixed synthetic mixing experiment; a real
deployment (arXiv:2201.03206's CORTEX-style front end) sees heterogeneous
sources — EEG/RF channel banks, replayed recordings, synthetic drills — all
delivering windowed multi-channel blocks.  This module is the contract
between those feeds and ``serve.SeparationService.run_tick``:

``SignalSource`` protocol (structural — any object with the methods works):
  * ``next_block(n_samples) -> (m, n_samples)`` — the next contiguous
    channel-major block (CORTEX convention: channels are rows).  Raises
    ``SourceExhausted`` when the feed ends.
  * ``true_mixing() -> (m, n) | None`` — optional: the ground-truth mixing at
    the CURRENT cursor (synthetic/replayed workloads), used by the service's
    Amari confirmation and by drift experiments.  Real recordings return
    ``None`` or omit the method (see ``true_mixing_of``).
  * ``position`` / ``seek(position)`` — optional sample cursor, used by the
    service's lifecycle snapshots so a re-bound source resumes exactly where
    the checkpointed one stopped (see ``SeparationService.bind_source``).

Adapters:
  * ``SyntheticSource``   — wraps a ``MixedSignals`` stream (optionally one
    stream of a multi-stream pipe) behind a sample cursor, with an optional
    ``drift_start`` so the mixing rotates only after a known onset (the
    drift-watchdog drill).
  * ``ChannelBankSource`` — windowed reads from an ``.npy`` multi-channel
    recording (memory-mapped by default: the file never fully loads).
  * ``ReplaySource``      — a fixed in-memory array, for deterministic
    regression runs.

Trace capture + replay (the SLO harness's load-test substrate — see
``serve.slo``):
  * ``RecordingSource``   — transparent wrapper over ANY source: every block
    it serves (and the exhaustion point) is captured in served order.
  * ``save_recording`` / ``load_recording`` — persist captured blocks plus
    admission/eviction event stamps to one ``.npz`` trace and load them back
    as ``RecordedSource``s, which serve the captured blocks verbatim — a
    replayed run sees bit-identical data in bit-identical order, whatever
    the original source computed.
"""
from __future__ import annotations

import dataclasses
import functools
import json
from typing import Dict, Hashable, List, Optional, Protocol, Sequence, Union, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import MixedSignals


class SourceExhausted(Exception):
    """Raised by ``next_block`` when a finite source has no more samples.

    ``SeparationService.run_tick`` turns this into an eviction with reason
    ``"exhausted"`` — a drained recording is a finished session, not an error.
    """


@runtime_checkable
class SignalSource(Protocol):
    """Structural protocol for serving feeds (see module docstring)."""

    def next_block(self, n_samples: int) -> np.ndarray:  # (m, n_samples)
        ...


def true_mixing_of(source) -> Optional[np.ndarray]:
    """``source.true_mixing()`` if the source exposes one, else ``None`` —
    the service-side accessor that makes the method genuinely optional."""
    fn = getattr(source, "true_mixing", None)
    return None if fn is None else fn()


@functools.partial(jax.jit, static_argnums=0)  # frozen dataclass → hashable
def _source_batch_jit(pipe: MixedSignals, seed, A, phase, step) -> jnp.ndarray:
    """Module-level jit of the per-source block generator, keyed on the
    (frozen, hashable) stationary pipe: every ``SyntheticSource`` over the
    same pipe shape shares ONE compiled program.  A per-instance
    ``jax.jit(lambda ...)`` would give each source its own cache — and a
    full trace+compile on its first block, which on the serving path lands
    on whatever ``run_tick`` first pulls from a freshly activated session
    (ruinous right after an elastic grow backfills several at once)."""
    return pipe._stream_batch(seed, A, phase, step)


@functools.lru_cache(maxsize=1)
def _host_device() -> Optional[jax.Device]:
    """The CPU device ``SyntheticSource`` generates on: a source is a host
    feed, and on an accelerator every block made there would cost a launch
    and a device-to-host copy per session per tick.  ``None`` (JAX's default
    device) when the process has no CPU backend."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def _givens(m: int, theta) -> jnp.ndarray:
    """Rotation by ``theta`` in the (0, 1) plane of R^m — the same plane
    ``MixedSignals._drift`` and ``signals.drifting_mixing_matrix`` rotate."""
    c, s = jnp.cos(theta), jnp.sin(theta)
    return jnp.eye(m).at[0, 0].set(c).at[1, 1].set(c).at[0, 1].set(-s).at[1, 0].set(s)


class SyntheticSource:
    """A ``MixedSignals`` stream behind a sample cursor.

    Each ``next_block(P)`` (``P`` must equal ``pipe.batch`` — the generator's
    RNG is block-addressed) returns the next mini-batch as a channel-major
    ``(m, P)`` block and advances the cursor; two sources built from the same
    ``(pipe, stream)`` replay identical data (pure function of the cursor).

    Drift: the source applies the pipe's rotation itself — ``A(t) =
    R(drift_rate·(clip(t, start, stop)−start)·batch + phase)·A0`` — so
    ``drift_start`` delays the onset (stationary until a known block, then
    drifting: the watchdog drill) and ``drift_stop`` ends it (the mixing
    settles at a NEW stationary rotation, so a re-adapted separator can
    re-converge).  With ``drift_start == 0`` and no stop the blocks match
    ``pipe.batch_for_step`` exactly.  ``true_mixing()`` reports the mixing at
    the current cursor, which the service's Amari confirmation tracks live.
    """

    def __init__(
        self,
        pipe: MixedSignals,
        stream: Optional[int] = None,
        drift_start: int = 0,
        drift_stop: Optional[int] = None,
    ):
        if pipe.streams and stream is None:
            raise ValueError(
                f"pipe has {pipe.streams} streams; pass stream= to select one"
            )
        if drift_stop is not None and drift_stop < drift_start:
            raise ValueError(
                f"drift_stop {drift_stop} < drift_start {drift_start}"
            )
        self.pipe = pipe
        self.stream = stream
        self.drift_start = int(drift_start)
        self.drift_stop = None if drift_stop is None else int(drift_stop)
        self._seed = pipe._stream_seed(stream)
        self._phase = pipe._drift_phase(stream)
        with jax.default_device(_host_device()):
            self._A0 = pipe._base_mixing(self._seed)
        self._step = 0
        # one trace for every block: (seed, A_eff, phase, step) are traced,
        # the stationary-pipe shape knobs come from the frozen dataclass —
        # shared across instances via the module-level jit (see
        # ``_source_batch_jit``)
        pipe0 = dataclasses.replace(pipe, drift_rate=0.0, streams=0)
        self._gen = functools.partial(_source_batch_jit, pipe0)

    @property
    def n_channels(self) -> int:
        return self.pipe.m

    @property
    def block_size(self) -> int:
        return self.pipe.batch

    @property
    def position(self) -> int:
        """Sample cursor (``steps_served * batch``)."""
        return self._step * self.pipe.batch

    def seek(self, position: int) -> None:
        if position % self.pipe.batch:
            raise ValueError(
                f"position {position} not a multiple of batch {self.pipe.batch}"
            )
        self._step = position // self.pipe.batch

    def mixing_at(self, step: int) -> jnp.ndarray:
        """Ground-truth mixing at block ``step`` — the pipe's rotation with a
        delayed onset and optional end; ``drift_start == 0`` with no stop
        reproduces ``pipe.mixing_at`` exactly.  (Evaluating a separator
        against wall-clock time uses this directly; ``true_mixing`` is the
        cursor-relative protocol view.)"""
        if not self.pipe.drift_rate:
            return self._A0
        t = step if self.drift_stop is None else min(step, self.drift_stop)
        theta = (
            self.pipe.drift_rate
            * max(0, t - self.drift_start)
            * self.pipe.batch
            + self._phase
        )
        with jax.default_device(_host_device()):
            return _givens(self.pipe.m, theta) @ self._A0

    def true_mixing(self) -> np.ndarray:
        """Ground-truth mixing at the CURRENT cursor ``(m, n)``."""
        return np.asarray(self.mixing_at(self._step))

    def next_block(self, n_samples: int) -> np.ndarray:
        if n_samples != self.pipe.batch:
            raise ValueError(
                f"SyntheticSource generates fixed blocks of {self.pipe.batch} "
                f"samples (the pipe's RNG is block-addressed); got "
                f"n_samples={n_samples}"
            )
        A = self.mixing_at(self._step)
        with jax.default_device(_host_device()):
            X = self._gen(self._seed, A, self._phase, self._step)  # (P, m)
        self._step += 1
        return np.asarray(X, dtype=np.float32).T


class _WindowCursor:
    """Shared sample cursor over a finite recording: bounds-checked ``seek``
    and the loop-wrap / exhaustion advance both finite adapters use (one
    implementation, so the wrap-seam semantics cannot diverge).  Subclasses
    provide ``n_samples``, ``loop`` and ``_what`` (the noun for errors)."""

    _what = "source"

    @property
    def position(self) -> int:
        return self._pos

    def seek(self, position: int) -> None:
        if not 0 <= position <= self.n_samples:
            raise ValueError(f"position {position} outside [0, {self.n_samples}]")
        self._pos = position

    def _advance(self, n_samples: int) -> int:
        """Claim the next contiguous window; returns its start index.
        Wraps when ``loop`` (blocks never straddle the seam), raises
        ``SourceExhausted`` otherwise."""
        T = self.n_samples
        if self._pos + n_samples > T:
            if not self.loop:
                raise SourceExhausted(
                    f"{self._what} drained: {T - self._pos} of {T} samples "
                    f"left, {n_samples} requested"
                )
            self._pos %= T
            if self._pos + n_samples > T:
                self._pos = 0
        start = self._pos
        self._pos += n_samples
        return start


class ReplaySource(_WindowCursor):
    """A fixed ``(T, m)`` array served in order — deterministic regression
    feeds (and the adapter for data that is already in memory).

    ``loop=True`` wraps at the end; otherwise ``next_block`` raises
    ``SourceExhausted`` once fewer than ``n_samples`` remain.  ``mixing``
    (optional, ``(m, n)`` or ``(T, m, n)`` per-sample) makes the replay
    ground-truth-aware: ``true_mixing()`` reports the matrix at the cursor.
    """

    _what = "replay"

    def __init__(
        self,
        X: np.ndarray,
        loop: bool = False,
        mixing: Optional[np.ndarray] = None,
    ):
        X = np.asarray(X, dtype=np.float32)
        if X.ndim != 2:
            raise ValueError(f"X must be (T, m); got shape {X.shape}")
        self._X = X
        self.loop = loop
        self._mixing = None if mixing is None else np.asarray(mixing)
        if self._mixing is not None and self._mixing.ndim == 3:
            if self._mixing.shape[0] != X.shape[0]:
                raise ValueError(
                    f"per-sample mixing length {self._mixing.shape[0]} != "
                    f"T={X.shape[0]}"
                )
        self._pos = 0

    @property
    def n_channels(self) -> int:
        return self._X.shape[1]

    @property
    def n_samples(self) -> int:
        return self._X.shape[0]

    def reset(self) -> None:
        self._pos = 0

    def true_mixing(self) -> Optional[np.ndarray]:
        if self._mixing is None:
            return None
        if self._mixing.ndim == 3:
            return self._mixing[min(self._pos, self.n_samples - 1)]
        return self._mixing

    def next_block(self, n_samples: int) -> np.ndarray:
        start = self._advance(n_samples)
        return self._X[start : start + n_samples].T.copy()


class ChannelBankSource(_WindowCursor):
    """Windowed reads from a multi-channel ``.npy`` recording — the
    CORTEX-style channel bank (arXiv:2201.03206): a rack of EEG/RF channels
    mapped onto separator streams.

    ``path_or_array`` is an ``.npy`` file (memory-mapped by default, so a
    multi-GB recording streams without loading) or an in-memory array.
    ``layout="ct"`` (default) expects channel-major ``(C, T)``; ``"tc"``
    expects sample-major ``(T, C)``.  ``channels`` selects a sub-bank (one
    electrode group per session).  Each ``next_block(n)`` returns the next
    contiguous ``(C_sel, n)`` window and advances the cursor; ``loop=True``
    wraps, otherwise the source raises ``SourceExhausted`` at the end.
    ``center=True`` removes the per-channel mean of each window (EASI assumes
    zero-mean inputs; recordings have electrode offsets).
    """

    def __init__(
        self,
        path_or_array: Union[str, "np.ndarray"],
        channels: Optional[Sequence[int]] = None,
        layout: str = "ct",
        mmap: bool = True,
        loop: bool = False,
        center: bool = True,
    ):
        if layout not in ("ct", "tc"):
            raise ValueError(f"layout must be 'ct' or 'tc', got {layout!r}")
        if isinstance(path_or_array, (str,)) or hasattr(path_or_array, "__fspath__"):
            data = np.load(path_or_array, mmap_mode="r" if mmap else None)
        else:
            data = np.asarray(path_or_array)
        if data.ndim != 2:
            raise ValueError(f"recording must be 2-D, got shape {data.shape}")
        self._data = data if layout == "ct" else data.T  # view: (C, T)
        self._channels = None if channels is None else list(channels)
        if self._channels is not None:
            C = self._data.shape[0]
            bad = [c for c in self._channels if not 0 <= c < C]
            if bad:
                raise ValueError(f"channels {bad} outside [0, {C})")
        self.loop = loop
        self.center = center
        self._pos = 0

    @property
    def n_channels(self) -> int:
        return len(self._channels) if self._channels is not None else self._data.shape[0]

    _what = "recording"

    @property
    def n_samples(self) -> int:
        return self._data.shape[1]

    def next_block(self, n_samples: int) -> np.ndarray:
        start = self._advance(n_samples)
        win = self._data[:, start : start + n_samples]
        if self._channels is not None:
            win = win[self._channels]
        blk = np.asarray(win, dtype=np.float32)  # mmap → RAM only here
        if self.center:
            blk = blk - blk.mean(axis=1, keepdims=True)
        return blk


# -- trace capture + deterministic replay (serve.slo load tests) ------------


class RecordingSource:
    """Transparent tap over any ``SignalSource``: every block the wrapped
    source serves is captured (in served order, as f32 copies), and the
    exhaustion point is remembered — the raw material of a ``.npz`` trace
    (``save_recording``) that replays as a deterministic load test.

    Everything else (``position``/``seek``/``true_mixing``/``n_samples``/
    retry counters/...) delegates to the wrapped source via ``__getattr__``,
    so ``hasattr`` probes see exactly the inner source's capabilities and
    the wrapper is invisible to the serving engine."""

    def __init__(self, inner):
        self.inner = inner
        self._blocks: List[np.ndarray] = []
        self.exhausted = False

    def next_block(self, n_samples: int) -> np.ndarray:
        try:
            blk = self.inner.next_block(n_samples)
        except SourceExhausted:
            self.exhausted = True
            raise
        blk = np.asarray(blk, dtype=np.float32)
        self._blocks.append(blk.copy())
        return blk

    @property
    def blocks(self) -> List[np.ndarray]:
        """Captured ``(m, P)`` blocks, in served order (copies)."""
        return list(self._blocks)

    def __getattr__(self, name):
        # only reached when normal lookup fails → pure delegation
        return getattr(self.inner, name)


class RecordedSource:
    """Blocks captured by a ``RecordingSource``, served back verbatim.

    Serves the stacked ``(k, m, P)`` blocks in recorded order and raises
    ``SourceExhausted`` past the end — the replayed session drains exactly
    where the recording stopped.  Deliberately exposes NO ``seek``/cursor:
    a replay is faithful to the *served block sequence*, not to the wrapped
    source's sample clock (probe-time seek-ahead was already resolved into
    the recorded blocks at capture time)."""

    _what = "recorded trace"

    def __init__(self, blocks: np.ndarray, exhausted: bool = True):
        blocks = np.asarray(blocks, dtype=np.float32)
        if blocks.ndim != 3 and blocks.size:
            raise ValueError(
                f"blocks must be (k, m, P), got shape {blocks.shape}"
            )
        self._blocks = blocks
        self.exhausted = bool(exhausted)
        self._i = 0

    @property
    def n_blocks(self) -> int:
        return int(self._blocks.shape[0]) if self._blocks.size else 0

    @property
    def n_channels(self) -> int:
        return int(self._blocks.shape[1]) if self._blocks.size else 0

    def next_block(self, n_samples: int) -> np.ndarray:
        if self._i >= self.n_blocks:
            raise SourceExhausted(
                f"{self._what} drained: {self.n_blocks} recorded blocks served"
            )
        blk = self._blocks[self._i]
        if blk.shape[1] != n_samples:
            raise ValueError(
                f"recorded block {self._i} is {blk.shape[1]} samples wide; "
                f"{n_samples} requested (replay must use the recorded P)"
            )
        self._i += 1
        return blk.copy()


@dataclasses.dataclass
class Recording:
    """A loaded ``.npz`` trace: per-session ``RecordedSource``s (keyed by the
    recorded session ids — JSON round-tripped, so non-str/int ids come back
    stringified), the admission/eviction event stamps captured alongside
    (``[{"action": "admit"|"evict", "sid": ..., "tick": ...}, ...]``), and
    free-form metadata (bank geometry, seed, ...) for the harness that
    replays it."""

    sources: Dict[Hashable, RecordedSource]
    events: List[Dict]
    meta: Dict


def save_recording(
    path,
    sources: Dict[Hashable, RecordingSource],
    events: Optional[List[Dict]] = None,
    meta: Optional[Dict] = None,
) -> None:
    """Persist captured blocks + event stamps to one compressed ``.npz``.

    ``sources`` maps session id → its ``RecordingSource`` tap; ``events`` is
    the admission/eviction log (JSON-able dicts with at least ``action``/
    ``sid``/``tick`` — ``serve.slo.replay`` re-admits from the ``admit``
    entries); ``meta`` is free-form JSON-able context.  The manifest rides as
    a uint8 JSON leaf, so one file carries arrays and bookkeeping together."""
    arrays = {}
    manifest: Dict = {
        "version": 1,
        "sessions": [],
        "events": list(events or []),
        "meta": dict(meta or {}),
    }
    for i, (sid, rec) in enumerate(sources.items()):
        blocks = (
            np.stack(rec.blocks).astype(np.float32)
            if rec.blocks
            else np.zeros((0, 0, 0), dtype=np.float32)
        )
        key = f"blocks_{i}"
        arrays[key] = blocks
        manifest["sessions"].append(
            {
                "sid": sid,
                "key": key,
                "exhausted": bool(getattr(rec, "exhausted", True)),
                "n_blocks": int(blocks.shape[0]),
            }
        )
    arrays["manifest"] = np.frombuffer(
        json.dumps(manifest, default=str).encode("utf-8"), dtype=np.uint8
    )
    np.savez_compressed(path, **arrays)


def load_recording(path) -> Recording:
    """Load a ``save_recording`` trace back as replayable sources + events."""
    with np.load(path) as z:
        if "manifest" not in z:
            raise ValueError(f"{path}: not a recording (no manifest leaf)")
        manifest = json.loads(bytes(z["manifest"]).decode("utf-8"))
        srcs: Dict[Hashable, RecordedSource] = {}
        for s in manifest["sessions"]:
            srcs[s["sid"]] = RecordedSource(
                z[s["key"]], exhausted=s.get("exhausted", True)
            )
    return Recording(
        sources=srcs,
        events=list(manifest.get("events") or []),
        meta=dict(manifest.get("meta") or {}),
    )
