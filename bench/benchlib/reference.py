"""The plain reference: the paper's SMBGD step (Eq. 1, closed form) for
every session, in straightforward ``jax.numpy``.

It imports nothing of the program and takes nothing the program made: the
blocks and each session's starting separator come from the benchmark's own
generator.  Per session and tick, for ``X (P, m)``, ``B (n, m)``::

    Y  = X Bᵀ                     G = g(Y)      w_p = μ β^(P-1-p)
    S  = (Σw) I − Yᵀ W Y − Gᵀ W Y + (Gᵀ W Y)ᵀ
    Ĥ′ = γ̂ Ĥ + S                  γ̂ = γ β^(P-1), 0 on a session's first tick
    B′ = B + Ĥ′ B

and the configuration's health guarantee: a tick whose ``B′``, ``Ĥ′`` or
``Y`` is not finite, or whose relative update ``‖Ĥ′B‖_F / ‖B‖_F`` exceeds
the blow-up bound, is flagged and not committed.

``precision`` picks how the matmuls round.  ``"highest"`` is f32 (what the
configuration states); ``"high"`` is the three-pass bf16 product (the
operands split into a bf16 head and a bf16 tail, the tail-by-tail term
dropped), the step below it, written out so that it rounds the same on
every backend.  The benchmark's control runs the reference at ``"high"``
in the program's place.  A run replays the reference on the chip, after
the window, where the program's f32 runs too, at the stated precision and
at the step below it (``BELOW``): the comparison reads the worst session's
error as a share of that step's.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

BELOW = {"highest": "high"}  # each stated precision and the step below it

NONLINEARITIES = {
    "cubic": lambda y: y * y * y,
    "tanh": jnp.tanh,
}


def _split(a):
    """``a`` as a bf16 head and a bf16 tail: the head is ``a`` with its low
    16 bits cleared, exact in bf16 (a bit mask, so no compiler can fold the
    round trip away), the tail the rest, rounded to bf16."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(0xFFFF0000)
    hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _dot(spec: str, a, b, precision: str):
    if precision == "highest":
        return jnp.einsum(
            spec, a, b, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    if precision == "high":
        ah, al = _split(a)
        bh, bl = _split(b)
        mm = functools.partial(
            jnp.einsum, spec, preferred_element_type=jnp.float32
        )
        return mm(ah, bh) + (mm(ah, bl) + mm(al, bh))
    raise ValueError(f"unknown precision {precision!r}")


def make_step(config: Dict, precision: str = "highest"):
    """Jitted reference tick for all sessions:
    ``(B, H, step, X (S, P, m)) -> (B′, H′, step′, Y (S, P, n), health (S,),
    delta (S,))``, ``delta`` the relative update ``‖Ĥ′B‖_F / ‖B‖_F``."""
    P, n = int(config["P"]), int(config["n"])
    mu, beta, gamma = (float(config[k]) for k in ("mu", "beta", "gamma"))
    bound = float(config["health_blowup_bound"])
    g = NONLINEARITIES[config["nonlinearity"]]
    w = jnp.asarray(mu * beta ** (P - 1 - np.arange(P)), jnp.float32)
    gamma_hat = np.float32(gamma * beta ** (P - 1))

    def one(B, H, step, X):
        Y = _dot("pm,nm->pn", X, B, precision)
        G = g(Y)
        Yw = Y * w[:, None]
        gram = _dot("pi,pj->ij", Y, Yw, precision)
        cross = _dot("pi,pj->ij", G, Yw, precision)
        S = jnp.sum(w) * jnp.eye(n, dtype=jnp.float32) - gram - cross + cross.T
        gh = jnp.where(step == 0, jnp.float32(0), gamma_hat)
        H_new = gh * H + S
        dB = _dot("ij,jm->im", H_new, B, precision)
        B_new = B + dB
        delta = jnp.sqrt(jnp.sum(dB * dB)) / jnp.maximum(
            jnp.sqrt(jnp.sum(B * B)), 1e-12
        )
        word = (
            jnp.where(jnp.all(jnp.isfinite(B_new)), 0, 1)
            + jnp.where(jnp.all(jnp.isfinite(H_new)), 0, 2)
            + jnp.where(jnp.all(jnp.isfinite(Y)), 0, 4)
            + jnp.where(delta <= bound, 0, 8)
        ).astype(jnp.int32)
        ok = word == 0
        return (
            jnp.where(ok, B_new, B),
            jnp.where(ok, H_new, H),
            jnp.where(ok, step + 1, step),
            Y,
            word,
            delta,
        )

    return jax.jit(jax.vmap(one))


class _Run:
    """One precision's replay of every session, a step at a time, on
    ``device`` (the default device when None)."""

    def __init__(self, config: Dict, traffic, sessions, steps, precision: str,
                 device=None):
        self.sessions = np.asarray(sessions, dtype=np.int64)
        self.steps = np.asarray(steps, dtype=np.int64)
        N, n = len(self.sessions), int(config["n"])
        self.traffic = traffic
        self.step_fn = make_step(config, precision)
        self.put = functools.partial(jax.device_put, device=device)
        B0 = traffic.B0[self.sessions % traffic.streams]
        self.B, self.H = self.put(B0), self.put(np.zeros((N, n, n), np.float32))
        self.st = self.put(np.zeros((N,), np.int32))
        self.B_end, self.H_end = B0.copy(), np.zeros((N, n, n), np.float32)
        self.words = np.zeros((N,), np.int32)
        self.delta_max = np.zeros((N,))
        self.k = 0

    def step(self) -> np.ndarray:
        """Every session's next block; returns the outputs ``(N, P, n)``."""
        k, steps = self.k, self.steps
        self.B, self.H, self.st, Y, word, delta = self.step_fn(
            self.B, self.H, self.st, self.put(self.traffic.batch(self.sessions, k)))
        Y, word, delta = jax.device_get((Y, word, delta))
        live = steps > k
        self.words |= np.where(live, word, 0)
        self.delta_max = np.where(live, np.fmax(self.delta_max, delta), self.delta_max)
        done = steps == k + 1
        if done.any():
            self.B_end[done] = np.asarray(self.B)[done]
            self.H_end[done] = np.asarray(self.H)[done]
        self.k = k + 1
        return Y

    def state(self) -> Dict[str, np.ndarray]:
        """Each session's state, flags and largest update after
        ``min(steps taken, steps[i])`` steps."""
        on = (self.steps > self.k)[:, None, None]
        return {
            "B": np.where(on, np.asarray(self.B), self.B_end),
            "H": np.where(on, np.asarray(self.H), self.H_end),
            "flagged": self.words != 0, "word": self.words.copy(),
            "delta_max": self.delta_max.copy(),
        }


def replay(config: Dict, traffic, sessions, steps, precision: str = "highest",
           device=None, marks=()) -> Dict[str, np.ndarray]:
    """Run each of ``sessions`` (ids into ``traffic``) from its ``B0`` over
    its first ``steps[i]`` blocks, on ``device`` (the default device when
    None).  Returns the outputs ``Y (K, N, P, n)`` (``K = max(steps)``;
    rows past a session's own steps are not its), the state each session
    reached after its own steps (``B``, ``H``), which sessions were ever
    flagged, with the OR of their health words (``word``), and each
    session's largest relative update ``delta_max``.  For each step count
    in ``marks`` the result's ``at[mark]`` holds the same state, flags and
    updates as they stood after ``min(mark, steps[i])`` steps."""
    run = _Run(config, traffic, sessions, steps, precision, device)
    K = int(run.steps.max()) if len(run.steps) else 0
    Ys = np.zeros((K, len(run.sessions), int(config["P"]), int(config["n"])), np.float32)
    at = {}
    for k in range(K):
        Ys[k] = run.step()
        if k + 1 in marks:
            at[k + 1] = run.state()
    return {"Y": Ys, **run.state(), "at": at}


def replay_beside(config: Dict, traffic, sessions, steps, served_Y, delivered,
                  device=None, marks=(), head=None):
    """The reference at the configuration's precision and the control one
    step below it, replayed in lockstep as ``replay`` does, each step's
    outputs compared as they come (``compare.OutputGaps``) with the served
    ones ``served_Y (K, N, P, n)`` and the control's with the reference's,
    over the rows ``delivered (K, N)`` keeps: neither replay's outputs are
    kept.  Returns ``(ref, ctl)``, each as ``replay``'s result without
    ``Y`` and with ``y_err``: per session, the served outputs' error
    against the reference (in ``ref``) and the control's (in ``ctl``), and
    ``y_err_head``, the same over each session's first ``head`` outputs
    (all, where None); ``at[mark]`` holds them too."""
    from benchlib.compare import OutputGaps

    stated = config["matmul_precision"]
    runs = [_Run(config, traffic, sessions, steps, p, device)
            for p in (stated, BELOW[stated])]
    N = len(runs[0].sessions)
    gaps = [OutputGaps(N), OutputGaps(N)]
    heads = [None, None]
    at = ({}, {})

    def errors(i):
        e = gaps[i].errors()
        return {"y_err": e, "y_err_head": e if heads[i] is None else heads[i]}

    for k in range(int(runs[0].steps.max()) if N else 0):
        y_ref, y_ctl = runs[0].step(), runs[1].step()
        keep = delivered[k][None]
        gaps[0].add(served_Y[k][None], y_ref[None], keep)
        gaps[1].add(y_ctl[None], y_ref[None], keep)
        if k + 1 == head:
            heads = [g.errors() for g in gaps]
        if k + 1 in marks:
            for i, run in enumerate(runs):
                at[i][k + 1] = {**run.state(), **errors(i)}
    return tuple({**run.state(), **errors(i), "at": at[i]} for i, run in enumerate(runs))
