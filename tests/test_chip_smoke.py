"""The chip smoke script and the plumbing it relies on, rehearsed on the CPU.

``chip_smoke.py`` needs a TPU to pass; here its phases run at a tiny size in
interpret mode (the same control flow, references and checks), and its entry
point must refuse the CPU.  The lowering rule, the compile-cache helper and
the default ``block_s`` rule it depends on are checked alongside.
"""
import importlib.util
from pathlib import Path

import jax
import pytest

from repro import compile_cache
from repro.kernels import interpret_mode
from repro.kernels.easi_gradient import ops as easi_ops
from repro.stream import autotune as autotune_lib

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_the_cpu(smoke, capsys):
    """No accelerator: non-zero exit and no result line."""
    assert smoke.main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_serve_phase_rehearsal(smoke):
    """16 sessions, 3 ticks: admission, serving, the f32 reference, the
    probe and separation all pass in interpret mode."""
    got = smoke.serve_phase(
        16, 3, seed=1, sample=8, epoch_blocks=300, on_chip=False
    )
    # interpret mode runs the same f32 math as the reference: far inside
    # the chip tolerance, which budgets for bf16-pass matmuls
    assert max(got["errs"].values()) < 1e-5


def test_sharded_phase_rehearsal(smoke):
    """The four-chip phase's control flow on the devices this process has
    (one CPU device in the tier-1 run): sharded and single-device steps
    agree bit for bit."""
    got = smoke.sharded_phase(
        len(jax.devices()), 8, ticks=2, seed=0, on_chip=False
    )
    assert got["bit_identical"]


def test_lowering_follows_the_backend():
    """One rule: interpret iff the backend is the CPU, and the layout and
    the autotune key follow it."""
    assert interpret_mode() is (jax.default_backend() == "cpu")
    lay = easi_ops.bank_layout(2, 4, 8)
    assert lay.m_pad == (8 if interpret_mode() else 128)
    assert autotune_lib.backend_tag().endswith("-interpret") is interpret_mode()


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_config(self):
        saved = (
            jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs,
        )
        yield
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", saved[1]
        )

    def test_defaults_to_a_fixed_repo_path(self, monkeypatch):
        monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
        path = compile_cache.enable_compile_cache()
        assert Path(path) == ROOT / ".jax_cache"
        assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0

    def test_environment_wins(self, monkeypatch, tmp_path):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched


class TestDefaultBlockS:
    """The stream-block rule: ``S`` itself or a multiple of 8 dividing it."""

    @pytest.mark.parametrize(
        "S, cap, want",
        [(36, 40, 36), (1024, 44, 32), (4096, 53, 32), (48, 47, 24), (8, 8, 8)],
    )
    def test_compiled_blocks_are_tileable(self, monkeypatch, S, cap, want):
        monkeypatch.setenv(easi_ops._VMEM_BUDGET_ENV, str(cap * 1000))
        bs = easi_ops._default_block_s(S, resident_bytes=1000, interpret=False)
        assert bs == want
        assert bs == S or bs % 8 == 0

    def test_compiled_width_with_no_tileable_block_raises(self, monkeypatch):
        """36 sessions that do not fit as one block have no tileable
        divisor (18 was picked before and Mosaic refused it)."""
        monkeypatch.setenv(easi_ops._VMEM_BUDGET_ENV, str(20 * 1000))
        with pytest.raises(ValueError, match="exceeds the VMEM budget"):
            easi_ops._default_block_s(36, resident_bytes=1000, interpret=False)
        # the interpreter has no tiling rule: any divisor will do
        assert (
            easi_ops._default_block_s(36, resident_bytes=1000, interpret=True)
            == 18
        )

    @pytest.mark.parametrize(
        "policy, block_p, measured_kib",
        [("f32", None, 1044), ("bf16", None, 768), ("f32", 512, 5308)],
    )
    def test_residency_covers_the_compiled_allocation(
        self, policy, block_p, measured_kib
    ):
        """The count over-covers what the v5e compiler allocated per stream
        for the HIGHEST-precision step megakernel at m=4, n=2 (found by
        bisecting ``vmem_limit_bytes`` in 32 KiB steps at block_s 8 and
        16)."""
        P = 8 if block_p is None else block_p
        lay = easi_ops.bank_layout(
            2, 4, P, interpret=False, dtype_policy=policy, block_p=block_p
        )
        assert lay.vmem_resident_bytes_per_stream() >= measured_kib * 1024

