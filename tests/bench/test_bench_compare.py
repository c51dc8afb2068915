"""The comparison's worst-session numbers, on made-up replays: a session
whose trajectory any two computations part ways on reads as a share of
the control's error there, and one altered session stands out."""
import sys
from pathlib import Path

import numpy as np
import pytest

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


def test_the_control_reads_one_on_the_median_shares():
    ref = _replay()
    ctl = _off(ref, np.full((N,), 3e-5), 2)
    got = compare.compare(_served(ctl), ref, ctl)
    assert all(got[f"{k}_ctl_med"] == 1.0 for k in ("y", "h", "b"))


def test_every_session_moved_alike_stands_out_on_the_median_shares():
    """Every session moved by a tenth of the control's error, as one
    rounding step more on every output and state would: no session stands
    out of the control's spread, so the worst-session shares stay small,
    but the median shares read ten times a sound run's."""
    ref = _replay()
    ctl = _off(ref, np.full((N,), 3e-5), 2)
    sound = compare.compare(_served(_off(ref, np.full((N,), 1e-7), 1)), ref, ctl)
    moved = compare.compare(_served(_off(ref, np.full((N,), 3e-6), 1)), ref, ctl)
    for k in ("y", "h", "b"):
        assert moved[f"{k}_ctl_med"] > 10 * sound[f"{k}_ctl_med"]
        assert moved[f"{k}_ctl_med"] > 0.05
        assert moved[f"{k}_ctl_share"] < 0.25


@pytest.mark.parametrize("chunk", [1, 40, 1 << 22])
def test_chunked_reduction_equals_the_whole_array_one(chunk, monkeypatch):
    """The outputs' per-session errors reduced a few steps at a time equal
    the whole float64 reduction, on random outputs with rows left out and
    non-finite entries, for every session and for a subset."""
    monkeypatch.setattr(compare, "CHUNK", chunk)
    g = np.random.default_rng(7)
    Kc, Nc = 37, 11
    scale = 10.0 ** g.integers(-3, 3, (1, Nc, 1, 1))
    ref = (g.standard_normal((Kc, Nc, P, n)) * scale).astype(np.float32)
    got = (ref * (1 + 1e-5 * g.standard_normal(ref.shape))).astype(np.float32)
    got[5, 2, 1, 0] = np.nan
    got[30, 4, 0, 1] = np.inf
    delivered = g.random((Kc, Nc)) < 0.8
    delivered[:, 6] = False  # a session with nothing delivered reads 0
    whole = compare._per_session(got.transpose(1, 0, 2, 3), ref.transpose(1, 0, 2, 3),
                                 delivered.T[:, :, None, None])
    for ids in (np.arange(Nc), np.array([9, 2, 6, 0])):
        part = compare._per_session_steps(got, ref, delivered, ids)
        expect = whole[ids]
        assert np.array_equal(np.isinf(part), np.isinf(expect))
        fin = np.isfinite(expect)
        np.testing.assert_allclose(part[fin], expect[fin], rtol=1e-6, atol=0)
    assert compare._per_session_steps(got, ref, delivered, np.array([], int)).shape == (0,)


def test_replay_marks_hold_the_state_of_a_shorter_replay():
    """``replay(..., marks=)`` gives at each mark what a replay of that
    many steps gives, also for a session whose own steps end sooner."""
    from benchlib import reference, registry
    from benchlib.traffic import make_traffic

    config = registry.config(ROOT, "paper_m4n2")
    signals = registry.signal_model(ROOT, config["signals"]["model"])
    traffic = make_traffic(config, {"ring_blocks": 8}, 3, signals, slots=4)
    ids, steps = np.arange(4), np.array([12, 12, 5, 12])
    full = reference.replay(config, traffic, ids, steps, marks=(7,))
    short = reference.replay(config, traffic, ids, np.minimum(steps, 7))
    for key in ("B", "H", "flagged", "word", "delta_max"):
        np.testing.assert_array_equal(full["at"][7][key], short[key])
    np.testing.assert_array_equal(full["Y"][:7], short["Y"])


def test_lockstep_replay_compares_as_the_whole_outputs_do():
    """``replay_beside`` keeps no outputs: its per-session errors, states
    and flags, at the end and at a mark, are those of two whole replays
    compared afterwards."""
    from benchlib import reference, registry
    from benchlib.traffic import make_traffic

    config = registry.config(ROOT, "eeg_bci4_2a")
    signals = registry.signal_model(ROOT, config["signals"]["model"])
    traffic = make_traffic(config, {"ring_blocks": 8}, 4, signals, slots=5)
    ids, steps = np.arange(5), np.array([9, 9, 4, 9, 9])
    ref = reference.replay(config, traffic, ids, steps, "highest", marks=(3, 6))
    ctl = reference.replay(config, traffic, ids, steps, "high", marks=(3, 6))
    g = np.random.default_rng(5)
    served = (ref["Y"] * (1 + 1e-6 * g.standard_normal(ref["Y"].shape))).astype(np.float32)
    delivered = np.arange(9)[:, None] < steps[None]
    got = reference.replay_beside(config, traffic, ids, steps, served, delivered,
                                  marks=(3, 6), head=5)
    for K, pick in ((9, lambda run: run), (6, lambda run: run["at"][6]),
                    (3, lambda run: run["at"][3])):
        for key, rows in (("y_err", slice(0, K)), ("y_err_head", slice(0, min(K, 5)))):
            want_y = [compare._per_session_steps(y[rows], ref["Y"][rows], delivered[rows], ids)
                      for y in (served, ctl["Y"])]
            for mine, y in zip(got, want_y):
                np.testing.assert_array_equal(pick(mine)[key], y)
        for mine, whole in zip(got, (ref, ctl)):
            for key in ("B", "H", "flagged", "word", "delta_max"):
                np.testing.assert_array_equal(pick(mine)[key], pick(whole)[key])


def test_the_worst_output_share_reads_the_first_share_steps_outputs():
    """With ``share_steps``, a session's outputs past that count leave its
    worst-session share alone, whatever they hold; an altered output
    within it stands out as before.  The median shares read every output."""
    ref = _replay()
    ctl = _off(ref, np.full((N,), 3e-5), 2)
    for row, counted in ((K - 1, False), (1, True)):
        served = _served(_off(ref, np.full((N,), 1e-6), 1))
        served["Y"] = served["Y"].copy()
        served["Y"][row, 5] *= -1
        whole = compare.compare(served, ref, ctl)
        head = compare.compare(served, ref, ctl, share_steps=K - 1)
        assert whole["y_ctl_share"] > 100
        assert (head["y_ctl_share"] > 100) == counted
        assert head["y_ctl_med"] == whole["y_ctl_med"]
