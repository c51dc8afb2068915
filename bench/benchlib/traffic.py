"""The benchmark's load generator: every session's signals, mixing,
starting separator and lifetime, and when sessions arrive, made from the
seed with numpy alone.

One generator reads every traffic mix (``bench/traffic/<mix>.json``); the
signals come from the configuration's signal model
(``bench/signals/<model>.py``, named by ``signals.model`` in
``bench/configs/<config>.json``).  It is the benchmark's own copy of the
source math: the program's ``SyntheticSource`` is never used, so a change
to the program cannot move the yardstick.

A mix is data.  Its keys:

* ``ring_blocks``: blocks ``(m, P)`` in each stream's ring; a pull is a view
  into the ring (it wraps), so pulling costs the service almost nothing;
* ``streams``: distinct streams made at set-up, as a multiple of the slots
  (default 1); session ``i`` is fed by stream ``i mod streams``;
* ``warm_ticks``: ticks served before the window, under the same traffic;
* ``initial``: the share of the slots admitted at set-up (default 1);
* ``lifetime``: blocks a session lives before its source drains (the
  service then releases it): ``{"kind": "forever"}``, ``{"kind": "fixed",
  "blocks": L}`` or ``{"kind": "geometric", "mean_blocks": L}``;
* ``arrivals``: sessions that arrive before each tick: ``replace`` (every
  session that left in the last tick is replaced by a fresh one),
  ``per_tick`` (a Poisson mean), ``burst_every`` and ``burst_size`` (a
  burst every so many ticks); all default to none;
* ``queue``: how many sessions may wait for a slot (default 0: an arrival
  that finds the bank full is refused and counts as failed).

Every draw depends on the seed and on the tick or session index alone,
never on the clock, so every seed gives the same kind of work: the same
sizes and schedule law, different numbers.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Traffic:
    """The streams the seed fixes for one run of one cell."""

    blocks: np.ndarray  # (N, R, m, P) float32: stream j's ring of mixed blocks
    mixing: np.ndarray  # (N, m, n) float32: the true mixing A of each stream
    B0: np.ndarray  # (N, n, m) float32: the starting separator of each stream

    @property
    def streams(self) -> int:
        return self.blocks.shape[0]

    def stream_of(self, session: int) -> int:
        return session % self.streams

    def block(self, session: int, k: int) -> np.ndarray:
        """Session ``session``'s ``k``-th block ``(m, P)`` (the ring wraps)."""
        return self.blocks[self.stream_of(session), k % self.blocks.shape[1]]

    def batch(self, sessions: np.ndarray, k: int) -> np.ndarray:
        """The ``k``-th block of each of ``sessions``, sample-major:
        ``(len(sessions), P, m)``."""
        rows = np.asarray(sessions) % self.streams
        return np.ascontiguousarray(
            self.blocks[rows, k % self.blocks.shape[1]].transpose(0, 2, 1)
        )


class RingSource:
    """One session's feed, in the program's ``SignalSource`` protocol
    (``next_block``).  It counts its pulls, so the reference knows which
    blocks the service took, and after ``lifetime`` blocks it raises
    ``exhausted`` (the program's end-of-stream signal)."""

    def __init__(self, traffic: Traffic, session: int, lifetime=None,
                 exhausted=StopIteration, span=None):
        self._traffic = traffic
        self.session = session
        self.lifetime = lifetime
        self.pulls = 0
        self._exhausted = exhausted
        self._span = span

    def next_block(self, n_samples: int) -> np.ndarray:
        P = self._traffic.blocks.shape[3]
        if n_samples != P:
            raise ValueError(f"pull of {n_samples} samples; blocks hold {P}")
        if self.lifetime is not None and self.pulls >= self.lifetime:
            raise self._exhausted(f"session {self.session} ended")
        if self._span is None:
            blk = self._traffic.block(self.session, self.pulls)
        else:
            with self._span("pull"):
                blk = self._traffic.block(self.session, self.pulls)
        self.pulls += 1
        return blk


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose): any non-negative seed,
    however large."""
    tag = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence([int(seed)] + tag))


def unit_variance(s: np.ndarray) -> np.ndarray:
    """Each row of the last axis centred and scaled to unit variance."""
    s = s - s.mean(axis=-1, keepdims=True)
    return s / (s.std(axis=-1, keepdims=True) + 1e-8)


def _mixing(g, N: int, m: int, n: int, min_sv: float) -> np.ndarray:
    """(N, m, n) well-conditioned mixings with unit-norm rows: singular
    values clamped to at least ``min_sv`` of the largest."""
    A = g.standard_normal((N, m, n))
    u, s, vt = np.linalg.svd(A, full_matrices=False)
    s = np.maximum(s, min_sv * s.max(axis=-1, keepdims=True))
    A = np.einsum("sik,sk,skj->sij", u, s, vt)
    return A / np.linalg.norm(A, axis=-1, keepdims=True)


def make_traffic(config: Dict, mix: Dict, seed: int, sources: Callable,
                 slots: Optional[int] = None) -> Traffic:
    """The seed's streams for ``config`` under ``mix``.  ``sources`` is the
    configuration's signal model: ``sources(rng, N, n, T, params) -> (N, n,
    T)`` unit-variance sources.  ``slots`` overrides the configuration's
    session count (CPU rehearsals only)."""
    S = int(config["sessions"] if slots is None else slots)
    N = max(1, int(round(float(mix.get("streams", 1)) * S)))
    m, n, P = int(config["m"]), int(config["n"]), int(config["P"])
    R = int(mix["ring_blocks"])
    sig = config["signals"]
    src = sources(rng(seed, "sources"), N, n, R * P, sig)
    A = _mixing(rng(seed, "mixing"), N, m, n, float(sig["mixing_min_sv"]))
    X = np.einsum("smn,snt->smt", A, src)  # (N, m, R*P)
    blocks = X.reshape(N, m, R, P).transpose(0, 2, 1, 3)
    g = rng(seed, "separator")
    B0 = np.eye(n, m)[None] + config["b0_scale"] * g.standard_normal((N, n, m))
    return Traffic(
        blocks=np.ascontiguousarray(blocks, dtype=np.float32),
        mixing=A.astype(np.float32),
        B0=B0.astype(np.float32),
    )


LIFETIMES = ("forever", "fixed", "geometric")


class Population:
    """Who arrives when, and how long each session lives: session ids are
    handed out in order, each id's lifetime drawn when it is made, each
    tick's arrivals drawn from the tick's index."""

    def __init__(self, mix: Dict, seed: int, slots: int):
        self._life = dict(mix.get("lifetime", {"kind": "forever"}))
        if self._life["kind"] not in LIFETIMES:
            raise ValueError(f"unknown lifetime kind {self._life['kind']!r}")
        arr = dict(mix.get("arrivals", {}))
        self._replace = bool(arr.get("replace", False))
        self._per_tick = float(arr.get("per_tick", 0.0))
        self._burst_every = int(arr.get("burst_every", 0))
        self._burst_size = int(arr.get("burst_size", 0))
        self._initial = int(round(float(mix.get("initial", 1.0)) * slots))
        self._life_rng = rng(seed, "lifetime")
        self._seed = int(seed)
        self.lifetimes: List[Optional[int]] = []

    def _new(self, k: int) -> List[int]:
        ids = list(range(len(self.lifetimes), len(self.lifetimes) + k))
        for _ in ids:
            kind = self._life["kind"]
            if kind == "forever":
                self.lifetimes.append(None)
            elif kind == "fixed":
                self.lifetimes.append(int(self._life["blocks"]))
            else:
                p = 1.0 / float(self._life["mean_blocks"])
                self.lifetimes.append(int(self._life_rng.geometric(p)))
        return ids

    def initial(self) -> List[int]:
        """The sessions admitted at set-up."""
        return self._new(self._initial)

    def arrivals(self, tick: int, departed: int) -> List[int]:
        """The sessions that arrive before tick ``tick``, given that
        ``departed`` left in the tick before."""
        k = departed if self._replace else 0
        if self._per_tick > 0:
            k += int(rng(self._seed, f"arrivals/{tick}").poisson(self._per_tick))
        if self._burst_every > 0 and tick > 0 and tick % self._burst_every == 0:
            k += self._burst_size
        return self._new(k)
