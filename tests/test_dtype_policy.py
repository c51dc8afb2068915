"""PR-6 memory system: dtype policy, VMEM-derived block_s, and the
persisted autotune cache.

The differential contracts:

  * bf16 storage vs the f32 oracle — same trajectory within checked-in
    Amari/conv tolerances across ragged shapes and all nonlinearities
    (accumulation is f32 either way; only the stored B/Ĥ quantize),
  * stream blocks: a bank stepped and probed in blocks of block_s < S
    streams is bit-identical to one block of all S (per-stream math is
    independent),
  * the default block_s derives from the layout's actual VMEM residency
    (no hardcoded caps; compiled backends fail loudly when one stream
    can't fit),
  * geometry knobs resolve from the autotune cache; dtype_policy never does.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import metrics as metrics_lib
from repro.core.easi import EASIConfig
from repro.core.nonlinearities import NONLINEARITIES
from repro.core.smbgd import SMBGDConfig
from repro.kernels.easi_gradient import ops as easi_ops
from repro.stream import SeparatorBank
from repro.stream import autotune as autotune_lib
from repro.stream.bank import BankState

# Checked-in bf16-vs-f32 tolerances (empirical worst over the sweep below at
# 20 ticks: conv ≈ 5e-4, Amari ≈ 4.6e-2 — an order of margin on conv, ~2x on
# Amari, which is still well under the ≈0.5 scale of an unseparated system)
BF16_CONV_TOL = 5e-3
BF16_AMARI_TOL = 1e-1


def _cfgs(P=8, n=2, m=4, nonlinearity="cubic", mu=1e-3):
    return (
        EASIConfig(n_components=n, n_features=m, mu=mu, nonlinearity=nonlinearity),
        SMBGDConfig(batch_size=P, mu=mu, beta=0.9, gamma=0.5),
    )


def _mixed_batches(key, S, K, P, m, n):
    """K ticks of (S, P, m) mixtures of a fixed random (m, n) mixing —
    unit-norm columns keep every shape/nonlinearity combo in EASI's stable
    region (an un-normalized mixing diverges BOTH dtypes at some seeds,
    which tests nothing about precision)."""
    A = jax.random.normal(jax.random.fold_in(key, 7), (m, n))
    A = A / jnp.linalg.norm(A, axis=0, keepdims=True)
    src = jax.random.normal(jax.random.fold_in(key, 8), (S, K, P, n))
    return A, jnp.einsum("skpn,mn->skpm", src, A)


class TestBf16VsF32Oracle:
    @pytest.mark.property
    @settings(max_examples=12, deadline=None)
    @given(
        shape=st.sampled_from([(8, 2, 4), (13, 3, 5), (32, 4, 6), (5, 2, 7)]),
        nonlinearity=st.sampled_from(sorted(NONLINEARITIES)),
        seed=st.integers(min_value=0, max_value=3),
    )
    def test_trajectory_within_tolerance(self, shape, nonlinearity, seed):
        """20-tick bf16 bank vs the f32 oracle from the same init: per-stream
        conv statistics and Amari indices agree within checked-in tolerance
        across ragged (padded) shapes and every nonlinearity."""
        P, n, m = shape
        ecfg, ocfg = _cfgs(P=P, n=n, m=m, nonlinearity=nonlinearity)
        S, K = 3, 20
        key = jax.random.PRNGKey(seed)
        A, X = _mixed_batches(key, S, K, P, m, n)
        f32 = SeparatorBank(ecfg, ocfg, S, fused=True, autotune=False)
        bf16 = SeparatorBank(
            ecfg, ocfg, S, fused=True, dtype_policy="bf16", autotune=False
        )
        st_f = f32.init(key)
        st_b = bf16.pad_state(f32.unpad_state(st_f))
        assert st_b.B.dtype == jnp.bfloat16
        for k in range(K):
            st_f, _ = f32.step(st_f, X[:, k])
            st_b, _ = bf16.step(st_b, X[:, k])
        assert st_b.B.dtype == jnp.bfloat16  # storage dtype survives stepping
        assert st_b.conv.dtype == jnp.float32  # statistic stays f32
        assert float(jnp.abs(st_f.conv - st_b.conv).max()) <= BF16_CONV_TOL
        am_f = f32.performance_index(st_f, A)
        am_b = bf16.performance_index(st_b, A)
        assert float(jnp.abs(am_f - am_b).max()) <= BF16_AMARI_TOL

    def test_nonfused_paths_follow_policy(self):
        """The vmap fallbacks honor the policy too: bf16 storage, f32 compute
        (upcast/downcast at the same boundaries the kernel uses)."""
        ecfg, ocfg = _cfgs()
        S = 4
        key = jax.random.PRNGKey(0)
        X = jax.random.normal(jax.random.fold_in(key, 1), (S, 8, 4))
        for kwargs in ({}, {"use_pallas": True}):
            bank = SeparatorBank(
                ecfg, ocfg, S, dtype_policy="bf16", autotune=False, **kwargs
            )
            st0 = bank.init(key)
            assert st0.B.dtype == jnp.bfloat16
            st1, Y = bank.step(st0, X)
            assert st1.B.dtype == jnp.bfloat16
            assert st1.H_hat.dtype == jnp.bfloat16
            # f32 compute: Y comes from the upcast B, not bf16 math
            assert Y.dtype == jnp.float32

    def test_probe_matches_between_policies(self):
        """The no-commit probe statistic agrees across storage dtypes within
        the conv tolerance (frozen parked separators are probed at whatever
        dtype they were parked in)."""
        ecfg, ocfg = _cfgs()
        S = 4
        key = jax.random.PRNGKey(2)
        X = jax.random.normal(jax.random.fold_in(key, 1), (S, 8, 4))
        f32 = SeparatorBank(ecfg, ocfg, S, fused=True, autotune=False)
        bf16 = SeparatorBank(
            ecfg, ocfg, S, fused=True, dtype_policy="bf16", autotune=False
        )
        st = f32.init(key)
        st, _ = f32.step(st, X)  # step once so the probe sees a real state
        conv_f, health_f, _mom_f = f32.probe(st, X)
        conv_b, health_b, _mom_b = bf16.probe(bf16.pad_state(f32.unpad_state(st)), X)
        assert float(jnp.abs(conv_f - conv_b).max()) <= BF16_CONV_TOL
        # a healthy state probes healthy at either storage dtype
        assert not health_f.any() and not health_b.any()

    def test_slot_boundary_casts(self):
        """Logical interchange stays at the config compute dtype: slot_state /
        unstack_states upcast, set_slot / pad_state cast back in, and a
        frozen slot round-trips bf16 → f32 → bf16 exactly."""
        ecfg, ocfg = _cfgs()
        bank = SeparatorBank(
            ecfg, ocfg, 3, fused=True, dtype_policy="bf16", autotune=False
        )
        key = jax.random.PRNGKey(3)
        st, _ = bank.step(
            bank.init(key),
            jax.random.normal(jax.random.fold_in(key, 1), (3, 8, 4)),
        )
        sub = bank.slot_state(st, 0)
        assert sub.B.dtype == jnp.float32  # logical boundary is f32
        back = bank.set_slot(st, 0, sub)
        np.testing.assert_array_equal(np.asarray(back.B[0]), np.asarray(st.B[0]))
        subs = bank.unstack_states(st)
        assert all(s.B.dtype == jnp.float32 for s in subs)
        stacked = bank.pad_state(SeparatorBank.stack_states(subs))
        assert stacked.B.dtype == jnp.bfloat16

    def test_persistent_bytes_reduction_meets_bar(self):
        """The acceptance number: bf16 storage cuts persistent HBM bytes per
        session ≥ 1.5x vs f32 at the benchmark shape."""
        lay_f32 = easi_ops.bank_layout(2, 4, 32)
        lay_bf16 = easi_ops.bank_layout(2, 4, 32, dtype_policy="bf16")
        reduction = (
            lay_f32.persistent_bytes_per_session
            / lay_bf16.persistent_bytes_per_session
        )
        assert reduction >= 1.5
        # and the tick-traffic estimate shrinks too (X/Y/W bytes are shared)
        assert (
            lay_bf16.tick_hbm_bytes_per_stream < lay_f32.tick_hbm_bytes_per_stream
        )


class TestStreamBlockBitIdentity:
    """The chip runs many stream blocks per launch (64 in the 256-channel
    cell), while CPU tests mostly run one: a bank split into blocks of
    ``block_s < S`` streams must give the bits of one block of all S."""

    @pytest.mark.parametrize("policy", [None, "bf16"])
    def test_step_bit_identical(self, policy):
        """Three steps at block_s 1 and 2 against block_s = S = 4: every
        output, moments included, bit for bit."""
        ecfg, ocfg = _cfgs(P=13, n=3, m=5)
        S = 4
        key = jax.random.PRNGKey(4)
        X = jax.random.normal(jax.random.fold_in(key, 1), (S, 13, 5))
        mk = lambda bs: SeparatorBank(
            ecfg, ocfg, S, fused=True, dtype_policy=policy, block_s=bs,
            moments=True, autotune=False,
        )
        whole = mk(S)
        st0 = whole.init(key)
        for bs in (1, 2):
            split = mk(bs)
            st_w, st_b = st0, st0
            for _ in range(3):
                st_w, Y_w = whole.step(st_w, X)
                st_b, Y_b = split.step(st_b, X)
                for a, b in zip(st_w, st_b):
                    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
                np.testing.assert_array_equal(np.asarray(Y_w), np.asarray(Y_b))

    @pytest.mark.parametrize("policy", [None, "bf16"])
    def test_probe_bit_identical(self, policy):
        """A masked probe at block_s 1 and 2 against block_s = S = 4."""
        ecfg, ocfg = _cfgs(P=13, n=3, m=5)
        S = 4
        key = jax.random.PRNGKey(5)
        X = jax.random.normal(jax.random.fold_in(key, 1), (S, 13, 5))
        mk = lambda bs: SeparatorBank(
            ecfg, ocfg, S, fused=True, dtype_policy=policy, block_s=bs,
            moments=True, autotune=False,
        )
        whole = mk(S)
        st0, _ = whole.step(whole.init(key), X)
        active = jnp.asarray([1, 0, 1, 1], jnp.int32)  # mask crosses blocks
        want = whole.probe(st0, X, active=active)
        for bs in (1, 2):
            got = mk(bs).probe(st0, X, active=active)
            for a, b in zip(want, got):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_stream_blocks_over_several_p_tiles(self):
        """S=6 in blocks of 2 with four P-tiles each (block_p 8 of P 32):
        the accumulator re-initialises at every stream block's first tile
        and commits at its last."""
        ecfg, ocfg = _cfgs(P=32)
        S = 6
        key = jax.random.PRNGKey(6)
        X = jax.random.normal(jax.random.fold_in(key, 1), (S, 32, 4))
        mk = lambda bs: SeparatorBank(
            ecfg, ocfg, S, fused=True, block_p=8, block_s=bs, autotune=False,
        )
        st0 = mk(S).init(key)
        st_w, Y_w = mk(S).step(st0, X)
        st_b, Y_b = mk(2).step(st0, X)
        for a, b in zip(st_w, st_b):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(Y_w), np.asarray(Y_b))


class TestVmemDerivedBlockS:
    def test_default_is_budget_derived(self, monkeypatch):
        """block_s = largest divisor of S with residency x block_s ≤ budget."""
        lay = easi_ops.bank_layout(2, 4, 32)
        resident = lay.vmem_resident_bytes_per_stream()
        monkeypatch.setenv(easi_ops._VMEM_BUDGET_ENV, str(3 * resident))
        # cap 3 → largest divisor of 8 that is ≤ 3 is 2
        assert easi_ops.default_block_s(8, lay, interpret=True) == 2
        monkeypatch.setenv(easi_ops._VMEM_BUDGET_ENV, str(64 * resident))
        assert easi_ops.default_block_s(8, lay, interpret=True) == 8

    def test_x_double_buffer_costs_block_s(self, monkeypatch):
        """The X double buffer counts against the budget.  The kernel's
        block pipeline holds two X tiles per stream, the one being folded
        and the next one in flight: the residency grows by two X tiles per
        extra row block of ``block_p``, and a budget one X tile short of four
        streams derives block_s 2."""
        narrow = easi_ops.bank_layout(2, 4, 32, block_p=8)
        wide = easi_ops.bank_layout(2, 4, 32, block_p=16)
        x_tile = 8 * wide.m_pad * 4  # 8 more rows of one f32 X tile
        grown = (
            wide.vmem_resident_bytes_per_stream()
            - narrow.vmem_resident_bytes_per_stream()
        )
        assert grown >= 2 * x_tile
        sync = wide.vmem_resident_bytes_per_stream()
        monkeypatch.setenv(easi_ops._VMEM_BUDGET_ENV, str(4 * sync))
        assert easi_ops.default_block_s(8, wide, interpret=True) == 4
        monkeypatch.setenv(easi_ops._VMEM_BUDGET_ENV, str(4 * sync - x_tile))
        assert easi_ops.default_block_s(8, wide, interpret=True) == 2

    def test_compiled_raises_when_one_stream_cannot_fit(self, monkeypatch):
        """No silent VMEM blowups on real hardware: a shape whose single
        stream exceeds the budget fails loudly on compiled backends and
        clamps to 1 on the interpreter (host memory, nothing to blow)."""
        lay = easi_ops.bank_layout(2, 4, 32)
        monkeypatch.setenv(easi_ops._VMEM_BUDGET_ENV, "64")
        with pytest.raises(ValueError, match="exceeds the VMEM budget"):
            easi_ops.default_block_s(8, lay, interpret=False)
        assert easi_ops.default_block_s(8, lay, interpret=True) == 1

    def test_large_shape_shrinks_block_s(self):
        """A big (m, n) shape derives a smaller block_s than a toy shape
        under the same budget — the hardcoded-cap bug this replaces."""
        small = easi_ops.bank_layout(2, 4, 32)
        big = easi_ops.bank_layout(64, 256, 256)
        assert (
            big.vmem_resident_bytes_per_stream()
            > small.vmem_resident_bytes_per_stream()
        )
        bs_small = easi_ops._default_block_s(
            64, resident_bytes=small.vmem_resident_bytes_per_stream(),
            interpret=False,
        )
        bs_big = easi_ops._default_block_s(
            64, resident_bytes=big.vmem_resident_bytes_per_stream(),
            interpret=False,
        )
        assert bs_big < bs_small


class TestAutotuneCache:
    def _seed_cache(self, monkeypatch, tmp_path, entry, S=4, P=8, m=4, n=2):
        path = tmp_path / "autotune.json"
        monkeypatch.setenv(autotune_lib.CACHE_ENV, str(path))
        autotune_lib.store(S, P, m, n, entry)
        return path

    def test_store_lookup_roundtrip(self, monkeypatch, tmp_path):
        entry = {"block_p": 8, "block_s": 2}
        self._seed_cache(monkeypatch, tmp_path, entry)
        assert autotune_lib.lookup(4, 8, 4, 2) == entry
        assert autotune_lib.lookup(5, 8, 4, 2) is None  # different shape key
        # different backend tag: the interpret entry must not leak
        assert autotune_lib.lookup(4, 8, 4, 2, interpret=False) is None

    def test_bank_resolves_geometry_from_cache(self, monkeypatch, tmp_path):
        self._seed_cache(
            monkeypatch, tmp_path,
            {"block_p": 8, "block_s": 2, "dtype_policy": "bf16"},
        )
        ecfg, ocfg = _cfgs()
        bank = SeparatorBank(ecfg, ocfg, 4, fused=True)
        assert (bank.block_p, bank.block_s) == (8, 2)
        # dtype_policy is recorded but NEVER auto-applied
        assert bank.dtype_policy is None
        assert bank.storage_dtype == jnp.float32

    def test_explicit_knobs_and_opt_out_win(self, monkeypatch, tmp_path):
        self._seed_cache(
            monkeypatch, tmp_path, {"block_p": 8, "block_s": 2}
        )
        ecfg, ocfg = _cfgs()
        explicit = SeparatorBank(ecfg, ocfg, 4, fused=True, block_p=16)
        assert explicit.block_p == 16  # explicit beats cached
        assert explicit.block_s == 2  # unset knobs still fill in
        opt_out = SeparatorBank(ecfg, ocfg, 4, fused=True, autotune=False)
        assert (opt_out.block_p, opt_out.block_s) == (None, None)

    def test_non_dividing_cached_block_s_skipped(self, monkeypatch, tmp_path):
        self._seed_cache(
            monkeypatch, tmp_path,
            {"block_p": 8, "block_s": 3},
        )
        ecfg, ocfg = _cfgs()
        bank = SeparatorBank(ecfg, ocfg, 4, fused=True)  # 4 % 3 != 0
        assert bank.block_s is None
        assert bank.block_p == 8

    def test_corrupt_cache_never_breaks_construction(self, monkeypatch, tmp_path):
        path = tmp_path / "autotune.json"
        path.write_text("{not json")
        monkeypatch.setenv(autotune_lib.CACHE_ENV, str(path))
        assert autotune_lib.load_cache() == {}
        ecfg, ocfg = _cfgs()
        bank = SeparatorBank(ecfg, ocfg, 4, fused=True)
        assert bank.block_p is None  # fell back to derived defaults
        key = jax.random.PRNGKey(0)
        X = jax.random.normal(jax.random.fold_in(key, 1), (4, 8, 4))
        st, _ = bank.step(bank.init(key), X)  # and still steps fine

    def test_cached_geometry_is_numerically_invariant(
        self, monkeypatch, tmp_path
    ):
        """Adopting tuned geometry must never change results: a cache-tuned
        bank matches the default-geometry bank bit for bit."""
        ecfg, ocfg = _cfgs()
        key = jax.random.PRNGKey(1)
        X = jax.random.normal(jax.random.fold_in(key, 1), (4, 8, 4))
        default = SeparatorBank(ecfg, ocfg, 4, fused=True, autotune=False)
        st0 = default.init(key)
        st_d, Y_d = default.step(st0, X)
        self._seed_cache(
            monkeypatch, tmp_path, {"block_p": 8, "block_s": 2}
        )
        tuned = SeparatorBank(ecfg, ocfg, 4, fused=True)
        st_t, Y_t = tuned.step(st0, X)
        for a, b in zip(st_d, st_t):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(Y_d), np.asarray(Y_t))

    def test_resize_re_resolves_at_new_width_key(self, monkeypatch, tmp_path):
        """``with_streams`` re-runs autotune resolution keyed on the NEW
        (S, P, m, n, backend): each width adopts its own tuned geometry."""
        self._seed_cache(
            monkeypatch, tmp_path,
            {"block_p": 8, "block_s": 2}, S=4,
        )
        autotune_lib.store(8, 8, 4, 2, {"block_p": 4, "block_s": 4})
        ecfg, ocfg = _cfgs()
        bank = SeparatorBank(ecfg, ocfg, 4, fused=True)
        assert (bank.block_p, bank.block_s) == (8, 2)
        wide = bank.with_streams(8)
        assert (wide.block_p, wide.block_s) == (4, 4)
        # and back: the original width's entry re-adopts, not the wide one's
        back = wide.with_streams(4)
        assert (back.block_p, back.block_s) == (8, 2)

    def test_resize_keeps_explicit_knobs_winning(self, monkeypatch, tmp_path):
        self._seed_cache(
            monkeypatch, tmp_path,
            {"block_p": 8, "block_s": 2}, S=4,
        )
        autotune_lib.store(8, 8, 4, 2, {"block_p": 4, "block_s": 4})
        ecfg, ocfg = _cfgs()
        bank = SeparatorBank(ecfg, ocfg, 4, fused=True, block_p=16)
        assert bank.block_p == 16 and bank.block_s == 2
        wide = bank.with_streams(8)
        # the hand-set knob survives the resize; unset knobs re-resolve
        assert wide.block_p == 16
        assert wide.block_s == 4
        # opt-out stays opted out at every width
        opt_out = SeparatorBank(ecfg, ocfg, 4, fused=True, autotune=False)
        wide_out = opt_out.with_streams(8)
        assert (wide_out.block_p, wide_out.block_s) == (None, None)

    def test_resize_with_missing_or_corrupt_cache_falls_back(
        self, monkeypatch, tmp_path
    ):
        """No entry at the new width (or a corrupt cache file) degrades to
        the VMEM-budget derived defaults — and the resized bank still steps."""
        self._seed_cache(
            monkeypatch, tmp_path,
            {"block_p": 8, "block_s": 2}, S=4,
        )
        ecfg, ocfg = _cfgs()
        bank = SeparatorBank(ecfg, ocfg, 4, fused=True)
        assert bank.block_p == 8
        wide = bank.with_streams(8)  # no S=8 entry seeded
        assert (wide.block_p, wide.block_s) == (None, None)
        key = jax.random.PRNGKey(0)
        X = jax.random.normal(jax.random.fold_in(key, 1), (8, 8, 4))
        wide.step(wide.init(key), X)  # budget-derived geometry serves
        # corrupt cache mid-flight: the resize itself must not raise
        (tmp_path / "autotune.json").write_text("{not json")
        narrow = wide.with_streams(2)
        assert (narrow.block_p, narrow.block_s) == (None, None)

    def test_checked_in_cache_parses_and_keys_well_formed(self):
        """The committed AUTOTUNE.json artifact stays loadable and every
        entry carries the geometry schema the resolver reads."""
        cache = json.loads(autotune_lib._DEFAULT_PATH.read_text())
        assert cache  # the repo ships tuned entries
        for key, entry in cache.items():
            assert "backend=" in key and "S=" in key
            for field in autotune_lib.GEOMETRY_KEYS:
                assert field in entry, (key, field)
