"""The comparison that decides ``correct``: what the served path delivered
against the plain reference replaying the same blocks.

Session ``i``'s ``k``-th output is compared with the reference's ``k``-th
output for the same session, whatever tick it was served in.

Which sessions are compared by value: every session the reference never
flagged under the configuration's health guarantee (one it flags is rolled
back and slowed by the service by design, so it parts ways with the
reference for reasons that are not faults).  A compared session that the
service flags is rolled back too: its values then part ways.

The per-session error of ``Y``, ``Ĥ`` or ``B`` is ``max |served − ref| /
max |ref|`` over the session's outputs, or over its committed state after
its last step.  Rounding differences grow with the steps, and some
sessions pass near a point where their trajectory turns on the last bit:
there any two computations, the reference on two backends too, part ways
far more than in the median session.  So the worst session's error swings
from seed to seed by a factor of 50, as far as the control's does, and no
limit holds on it.  The median session's error is steady from seed to
seed, but it grows tenfold and more between tens and thousands of steps,
the control's with it.  So both are read as shares of the control's: the
control (the reference one matmul precision step lower, replayed over the
same blocks) parts from the reference in the same sessions and over the
same steps, and the shares are steady.  The worst session's share is read only
over the sessions whose reference update ``‖Ĥ′B‖_F / ‖B‖_F`` never passed
``max_update`` (``bench/limits``), and catches a fault in one session that
the median cannot see.

Numbers compared, each against its own limit from ``bench/limits`` (a
number the file gives no limit is read and printed, not held):

* ``missing``: outputs due to a compared session (one per block it pulled)
  that never reached the client, and compared sessions whose final state
  cannot be read back (limit 0: an answer that never comes is wrong);
* ``health_missed``: sessions whose delivered outputs or final state are
  not finite, and sessions that blew up in the reference (a non-finite
  step, or an update past ``blowup_margin`` times the bound) that the
  service never flagged: the health guarantee broken (limit 0);
* ``y_ctl_med``, ``h_ctl_med``, ``b_ctl_med``: the median compared
  session's error over the control's median compared session's error,
  over the same sessions and outputs (for ``h`` and ``b``, the sessions
  whose state is known).  Both grow with the steps a session takes, and
  their ratio holds still, so the limit holds at any step count; the
  control reads 1;
* ``y_ctl_share``, ``h_ctl_share``, ``b_ctl_share``: the largest, over the
  compared sessions whose update stayed at or under ``max_update``, of the
  session's error over the control's error in that session (floored at the
  control's median session); the control reads 1.  Over thousands of steps
  the served path and the control each turn a few sessions' trajectories
  apart from the reference's, in different sessions, and these shares pass
  1 in sound runs; so ``y_ctl_share`` is read over each session's first
  ``share_steps`` outputs where the limits file gives that count, and the
  state's shares are printed, not held;
* ``y_med``, ``h_med``, ``b_med``: the median compared session's error
  itself, printed beside the shares and not held: it grows with the steps.

The outputs are reduced a few steps at a time (``CHUNK``), so a run of
thousands of steps per session needs no float64 copy of them.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

ORDER = ("missing", "health_missed", "y_ctl_med", "h_ctl_med", "b_ctl_med",
         "y_ctl_share", "h_ctl_share", "b_ctl_share", "y_med", "h_med", "b_med")
NON_FINITE = 1 | 2 | 4  # the health word's bits for B′, Ĥ′, Y
CHUNK = 1 << 22  # output entries reduced at a time


def _per_session(got: np.ndarray, ref: np.ndarray, mask=None) -> np.ndarray:
    """``max|got_s − ref_s| / max|ref_s|`` for each session ``s`` of the
    leading axis, over the entries ``mask`` (broadcast) keeps; a non-finite
    value reads as infinitely wrong, a session with nothing kept as 0."""
    got = got.astype(np.float64)
    ref = ref.astype(np.float64)
    diff = np.abs(got - ref)
    diff = np.where(np.isfinite(diff), diff, np.inf)
    mag = np.abs(ref)
    if mask is not None:
        diff = np.where(mask, diff, 0.0)
        mag = np.where(mask, mag, 0.0)
    axes = tuple(range(1, got.ndim))
    return diff.max(axis=axes) / np.maximum(mag.max(axis=axes), 1e-30)


class OutputGaps:
    """Each session's largest output gap and largest reference magnitude,
    kept as blocks of output rows come: ``errors()`` is ``_per_session``
    over every row added.  In float32: a gap of two float32 numbers rounds
    by at most 2^-24 of itself."""

    def __init__(self, sessions: int):
        self.gap = np.zeros((sessions,))
        self.mag = np.zeros((sessions,))

    def add(self, got: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> None:
        """Rows ``(c, N, P, n)`` of the outputs and of the reference, and
        which of them count, ``keep (c, N)``."""
        d = np.abs(got.astype(np.float32) - ref.astype(np.float32))
        d[~np.isfinite(d)] = np.inf
        a = np.abs(ref.astype(np.float32))
        d[~keep] = 0.0
        a[~keep] = 0.0
        self.gap = np.maximum(self.gap, d.max(axis=(0, 2, 3)))
        self.mag = np.maximum(self.mag, a.max(axis=(0, 2, 3)))

    def errors(self) -> np.ndarray:
        return self.gap / np.maximum(self.mag, 1e-30)


def _steps_per_chunk(Y: np.ndarray, sessions: int) -> int:
    return max(1, CHUNK // max(1, sessions * int(np.prod(Y.shape[2:]))))


def _per_session_steps(got: np.ndarray, ref: np.ndarray, delivered: np.ndarray,
                       sessions: np.ndarray) -> np.ndarray:
    """``_per_session`` of the outputs ``(K, N, P, n)`` for each of
    ``sessions`` (indices into axis 1), over the rows ``delivered (K, N)``
    keeps, reduced a few steps at a time (``OutputGaps``): no whole copy
    of the outputs is made."""
    sessions = np.asarray(sessions, dtype=np.int64)
    every = sessions.size == got.shape[1] and (sessions == np.arange(got.shape[1])).all()
    gaps = OutputGaps(sessions.size)
    step = _steps_per_chunk(got, sessions.size)
    for k0 in range(0, got.shape[0], step):
        rows = slice(k0, k0 + step)
        g, r, keep = got[rows], ref[rows], delivered[rows]
        if not every:
            g, r, keep = g[:, sessions], r[:, sessions], keep[:, sessions]
        gaps.add(g, r, keep)
    return gaps.errors()


def _worst(e: np.ndarray) -> float:
    return float(e.max()) if e.size else 0.0


def _median(e: np.ndarray) -> float:
    return float(np.median(e)) if e.size else 0.0


def compared_sessions(ref: Dict, max_update: float = float("inf")) -> np.ndarray:
    """Indices of the sessions the reference never flagged whose update
    stayed at or under ``max_update``."""
    return np.flatnonzero(~ref["flagged"] & (ref["delta_max"] <= max_update))


def health_missed(served: Dict, ref: Dict, bound: float, margin: float) -> int:
    """Sessions with non-finite delivered outputs or final state, and clear
    blow-ups of the reference that the service never flagged."""
    Y, delivered = served["Y"], served["delivered"]
    known = served["known"]
    bad = np.zeros((Y.shape[1],), bool)
    step = _steps_per_chunk(Y, Y.shape[1])
    for k0 in range(0, Y.shape[0], step):
        rows = slice(k0, k0 + step)
        ok = np.isfinite(Y[rows]) | ~delivered[rows][:, :, None, None]
        bad |= ~ok.all(axis=(0, 2, 3))
    bad |= known & ~np.isfinite(served["B"]).all(axis=(1, 2))
    bad |= known & ~np.isfinite(served["H"]).all(axis=(1, 2))
    blew = ((ref["word"] & NON_FINITE) != 0) | (ref["delta_max"] > margin * bound)
    flagged = np.zeros_like(bad)
    flagged[list(served["flagged"])] = True
    return int((bad | (blew & ~flagged)).sum())


def _errors(got: Dict, ref: Dict, clean: np.ndarray, delivered: np.ndarray,
            head: Optional[int], given: Dict) -> Dict:
    """Per-session ``y``, ``h``, ``b`` errors of ``got`` against ``ref``
    over the sessions ``clean``: ``y`` over the output rows ``delivered``
    keeps, ``y_head`` over each session's first ``head`` of them (all,
    where None).  Where ``given`` holds ``y_err`` (``reference.replay_beside``
    took every session's output errors as it went), those are the ``y``s."""
    if "y_err" in given:
        y, y_head = given["y_err"][clean], given["y_err_head"][clean]
    else:
        y = _per_session_steps(got["Y"], ref["Y"], delivered, clean)
        y_head = y if head is None or head >= len(got["Y"]) else _per_session_steps(
            got["Y"][:head], ref["Y"][:head], delivered[:head], clean)
    return {
        "y": y, "y_head": y_head,
        "h": _per_session(got["H"][clean], ref["H"][clean]),
        "b": _per_session(got["B"][clean], ref["B"][clean]),
    }


def compare(served: Dict, ref: Dict, ctl: Dict, max_update: float = float("inf"),
            bound: float = float("inf"), blowup_margin: float = 10.0,
            share_steps: Optional[int] = None) -> Dict[str, float]:
    """``served`` holds, for N sessions, ``Y (K, N, P, n)`` (session ``i``'s
    ``k``-th output in row ``k``), ``delivered (K, N)``, ``pulls (N,)``,
    ``B``/``H`` after each session's last step where ``known (N,)`` and
    ``flagged`` (a set of session indices); ``ref`` and ``ctl`` are
    ``reference.replay`` over the same sessions and as many steps as each
    pulled, at the stated precision and at the step below it.  A session
    that pulled nothing is not compared.  A session whose state cannot be
    read back counts as missing.  Where ``ref`` and ``ctl`` are
    ``reference.replay_beside``'s, with no ``Y`` and every session's
    output error taken as the replay went (``y_err``: the served path's
    in ``ref``, the control's in ``ctl``), the outputs' errors are those."""
    pulled = served["pulls"] > 0
    clean = compared_sessions(ref)
    clean = clean[pulled[clean]]
    calm = (ref["delta_max"][clean] <= max_update)
    outputs = served["delivered"].sum(axis=0)
    known = served["known"][clean]
    flag_ref = set(np.flatnonzero(ref["flagged"]).tolist())
    lost = (served["pulls"][clean] - outputs[clean]).sum() + (~known).sum()
    e = _errors(served, ref, clean, served["delivered"], share_steps, ref)
    e_ctl = _errors(ctl, ref, clean, served["delivered"], share_steps, ctl)
    out = {
        "missing": float(lost),
        "health_missed": float(health_missed(served, ref, bound, blowup_margin)),
        "compared": float(len(clean)),
        "compared_worst": float(calm.sum()),
        "flagged_served": float(len(served["flagged"])),
        "flagged_ref": float(len(flag_ref)),
        "flag_diff": float(len(set(served["flagged"]) ^ flag_ref)),
        "flagged_compared": float(len(set(served["flagged"]) & set(clean.tolist()))),
    }
    for k in ("y", "h", "b"):
        sel = np.ones_like(known) if k == "y" else known
        out[f"{k}_med"] = _median(e[k][sel])
        out[f"{k}_ctl_med"] = out[f"{k}_med"] / max(_median(e_ctl[k][sel]), 1e-30)
        w = "y_head" if k == "y" else k
        share = e[w] / np.maximum(e_ctl[w], max(_median(e_ctl[w]), 1e-30))
        out[f"{k}_ctl_share"] = _worst(share[calm & sel])
    return out


def diagnostics(served: Dict, ref: Dict) -> Dict[str, float]:
    """Readings beside the compared numbers, for setting limits: quantiles
    of the per-session errors over the sessions the reference never
    flagged, and the worst of them under other ``max_update`` rules."""
    ok = ~ref["flagged"] & served["known"] & (served["pulls"] > 0)
    ey = ref.get("y_err")
    if ey is None:
        ey = _per_session_steps(served["Y"], ref["Y"], served["delivered"],
                                np.arange(served["Y"].shape[1]))
    eb = _per_session(served["B"], ref["B"])
    eh = _per_session(served["H"], ref["H"])
    out = {}
    for name, e in (("y", ey), ("b", eb), ("h", eh)):
        for q in (50, 90, 99):
            out[f"{name}_p{q}"] = float(np.percentile(e[ok], q)) if ok.any() else 0.0
        for D in (0.25, 0.5, 1.0, 2.0, float("inf")):
            w = ok & (ref["delta_max"] <= D)
            out[f"{name}_max_d{D}"] = float(e[w].max()) if w.any() else 0.0
    for D in (0.25, 0.5, 1.0, 2.0):
        out[f"n_d{D}"] = float((ok & (ref["delta_max"] <= D)).sum())
    fl = ref["flagged"]
    out["ref_flag_delta_min"] = float(ref["delta_max"][fl].min()) if fl.any() else 0.0
    out["ref_flag_nonfinite"] = float(((ref["word"] & NON_FINITE) != 0).sum())
    return out


def options(limits_file: Dict, config: Dict) -> Dict[str, float]:
    """The comparison's settings from a ``bench/limits/<config>.json`` and
    the configuration's health bound."""
    out = {k: float(limits_file[k]) for k in ("max_update", "blowup_margin")
           if k in limits_file}
    out["bound"] = float(config["health_blowup_bound"])
    out["share_steps"] = limits_file.get("share_steps")
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every limited number at or under its limit (NaN fails)."""
    return all(numbers[k] <= limits[k] for k in limits)
