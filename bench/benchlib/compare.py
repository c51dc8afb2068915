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
seed and tells the two apart by a factor of 15 or more.  The worst session
is read as a share of the control's error: the control (the reference one
matmul precision step lower, replayed over the same blocks) parts from the
reference in the same sessions, and the share is steady; it is read only
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
* ``y_med``, ``h_med``, ``b_med``: the median compared session's error;
* ``y_ctl_share``, ``h_ctl_share``, ``b_ctl_share``: the largest, over the
  compared sessions whose update stayed at or under ``max_update``, of the
  session's error over the control's error in that session (floored at the
  control's median session); the control reads 1.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

ORDER = ("missing", "health_missed", "y_med", "h_med", "b_med",
         "y_ctl_share", "h_ctl_share", "b_ctl_share")
NON_FINITE = 1 | 2 | 4  # the health word's bits for B′, Ĥ′, Y


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


def _worst(e: np.ndarray) -> float:
    return float(e.max()) if e.size else 0.0


def _median(e: np.ndarray) -> float:
    return float(np.median(e)) if e.size else 0.0


def _y_mask(served: Dict) -> np.ndarray:
    """(N, K, 1, 1): which of each session's output rows were delivered."""
    return served["delivered"].T[:, :, None, None]


def compared_sessions(ref: Dict, max_update: float = float("inf")) -> np.ndarray:
    """Indices of the sessions the reference never flagged whose update
    stayed at or under ``max_update``."""
    return np.flatnonzero(~ref["flagged"] & (ref["delta_max"] <= max_update))


def health_missed(served: Dict, ref: Dict, bound: float, margin: float) -> int:
    """Sessions with non-finite delivered outputs or final state, and clear
    blow-ups of the reference that the service never flagged."""
    Y = np.where(served["delivered"][:, :, None, None], served["Y"], 0.0)
    known = served["known"]
    bad = ~np.isfinite(Y).all(axis=(0, 2, 3))
    bad |= known & ~np.isfinite(served["B"]).all(axis=(1, 2))
    bad |= known & ~np.isfinite(served["H"]).all(axis=(1, 2))
    blew = ((ref["word"] & NON_FINITE) != 0) | (ref["delta_max"] > margin * bound)
    flagged = np.zeros_like(bad)
    flagged[list(served["flagged"])] = True
    return int((bad | (blew & ~flagged)).sum())


def _errors(got: Dict, ref: Dict, clean: np.ndarray, mask: np.ndarray) -> Dict:
    """Per-session ``y``, ``h``, ``b`` errors of ``got`` against ``ref``
    over the sessions ``clean``, ``y`` over the output rows ``mask`` keeps."""
    return {
        "y": _per_session(got["Y"][:, clean].transpose(1, 0, 2, 3),
                          ref["Y"][:, clean].transpose(1, 0, 2, 3), mask[clean]),
        "h": _per_session(got["H"][clean], ref["H"][clean]),
        "b": _per_session(got["B"][clean], ref["B"][clean]),
    }


def compare(served: Dict, ref: Dict, ctl: Dict, max_update: float = float("inf"),
            bound: float = float("inf"), blowup_margin: float = 10.0) -> Dict[str, float]:
    """``served`` holds, for N sessions, ``Y (K, N, P, n)`` (session ``i``'s
    ``k``-th output in row ``k``), ``delivered (K, N)``, ``pulls (N,)``,
    ``B``/``H`` after each session's last step where ``known (N,)`` and
    ``flagged`` (a set of session indices); ``ref`` and ``ctl`` are
    ``reference.replay`` over the same sessions and as many steps as each
    pulled, at the stated precision and at the step below it.  A session
    that pulled nothing is not compared.  A session whose state cannot be
    read back counts as missing."""
    pulled = served["pulls"] > 0
    clean = compared_sessions(ref)
    clean = clean[pulled[clean]]
    calm = (ref["delta_max"][clean] <= max_update)
    outputs = served["delivered"].sum(axis=0)
    known = served["known"][clean]
    flag_ref = set(np.flatnonzero(ref["flagged"]).tolist())
    lost = (served["pulls"][clean] - outputs[clean]).sum() + (~known).sum()
    mask = _y_mask(served)
    e = _errors(served, ref, clean, mask)
    e_ctl = _errors(ctl, ref, clean, mask)
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
        share = e[k] / np.maximum(e_ctl[k], max(_median(e_ctl[k]), 1e-30))
        out[f"{k}_ctl_share"] = _worst(share[calm & sel])
    return out


def diagnostics(served: Dict, ref: Dict) -> Dict[str, float]:
    """Readings beside the compared numbers, for setting limits: quantiles
    of the per-session errors over the sessions the reference never
    flagged, and the worst of them under other ``max_update`` rules."""
    ok = ~ref["flagged"] & served["known"] & (served["pulls"] > 0)
    ey = _per_session(served["Y"].transpose(1, 0, 2, 3),
                      ref["Y"].transpose(1, 0, 2, 3), _y_mask(served))
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
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every limited number at or under its limit (NaN fails)."""
    return all(numbers[k] <= limits[k] for k in limits)
