"""The comparison's worst-session numbers, on made-up replays: a session
whose trajectory any two computations part ways on reads as a share of
the control's error there, and one altered session stands out."""
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import compare  # noqa: E402

N, K, P, n, m = 16, 6, 8, 2, 4


def _replay(seed=0):
    g = np.random.default_rng(seed)
    return {
        "Y": g.standard_normal((K, N, P, n)).astype(np.float32),
        "B": g.standard_normal((N, n, m)).astype(np.float32),
        "H": g.standard_normal((N, n, n)).astype(np.float32),
        "flagged": np.zeros((N,), bool), "word": np.zeros((N,), np.int32),
        "delta_max": np.full((N,), 0.1),
    }


def _off(ref, scale, seed):
    """``ref`` moved by a relative ``scale`` per session."""
    g = np.random.default_rng(seed)
    out = dict(ref)
    for k in ("Y", "B", "H"):
        s = scale.reshape((1, N, 1, 1) if k == "Y" else (N, 1, 1))
        out[k] = (ref[k] * (1 + s * g.standard_normal(ref[k].shape))).astype(np.float32)
    return out


def _served(run):
    return {**run, "delivered": np.ones((K, N), bool), "pulls": np.full((N,), K),
            "known": np.ones((N,), bool), "flagged": set()}


def test_a_sensitive_session_reads_as_a_share_of_the_control():
    """Session 3 parts from the reference a hundred times more than the
    rest, in the served run and in the control alike: the worst session's
    share stays where the others' is."""
    ref = _replay()
    scale = np.full((N,), 1e-6)
    scale[3] = 1e-4
    served = _served(_off(ref, scale, 1))
    ctl = _off(ref, scale * 30, 2)
    got = compare.compare(served, ref, ctl)
    even = compare.compare(_served(_off(ref, np.full((N,), 1e-6), 1)), ref,
                           _off(ref, np.full((N,), 3e-5), 2))
    for k in ("y", "h", "b"):
        assert got[f"{k}_ctl_share"] < 0.2
        assert got[f"{k}_ctl_share"] < 2 * even[f"{k}_ctl_share"]


def test_the_control_reads_one_and_an_altered_session_stands_out():
    ref = _replay()
    ctl = _off(ref, np.full((N,), 3e-5), 2)
    got = compare.compare(_served(ctl), ref, ctl)
    assert all(got[f"{k}_ctl_share"] == 1.0 for k in ("y", "h", "b"))
    served = _served(_off(ref, np.full((N,), 1e-6), 1))
    served["Y"] = served["Y"].copy()
    served["Y"][2, 5] *= -1
    got = compare.compare(served, ref, ctl)
    assert got["y_ctl_share"] > 100
    assert got["y_med"] < 1e-5
