"""The benchmark's logical-work function and its table of peaks."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import work  # noqa: E402


def test_paper_shape_moves_336_bytes_per_session_and_tick():
    # read X 128 + W 32 + B 32 + H 16, write B 32 + H 16 + Y 64 + conv 4
    # + health 4 + moments 8
    assert work.step_bytes_per_session(4, 2, 8, "float32") == 336


def test_eeg_shape_moves_9200_bytes_per_session_and_tick():
    assert work.step_bytes_per_session(22, 22, 8, "float32") == 9200


@pytest.mark.parametrize("block_p", [8, 16, 64])
@pytest.mark.parametrize("interpret", [False, True])
def test_work_does_not_follow_the_padded_layout(block_p, interpret):
    """The program's layout pads to other shapes for each geometry; the
    logical count is the same for all of them."""
    from repro.kernels.easi_gradient import ops as easi_ops

    lay = easi_ops.bank_layout(2, 4, 8, block_p=block_p, interpret=interpret)
    assert lay.tick_hbm_bytes_per_stream != work.step_bytes_per_session(4, 2, 8)
    assert work.step_bytes_per_session(lay.m, lay.n, lay.P) == 336


def test_flops_count_the_four_matmuls():
    # Y 2*8*4*2 + two Gram products 2*2*8*2*2 + commit 2*2*2*4
    assert work.step_flops_per_session(4, 2, 8) == 128 + 128 + 32


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")


def test_v5e_least_time_is_bytes_bound_at_both_shapes():
    paper = {"m": 4, "n": 2, "P": 8, "dtype": "float32"}
    eeg = {"m": 22, "n": 22, "P": 8, "dtype": "float32"}
    for cfg, per in ((paper, 336), (eeg, 9200)):
        got = work.least_time_s(cfg, 256, "TPU v5 lite")
        assert got["bound"] == "bytes"
        assert got["seconds"] == pytest.approx(256 * per / 819e9)
