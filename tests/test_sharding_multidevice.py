"""Multi-device validation of ``make_sharded_bank_step``.

Runs only with ≥ 8 devices — CI invokes this file separately under

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m pytest tests/test_sharding_multidevice.py

(the flag must be set before jax initializes, hence the dedicated pytest
invocation; in the ordinary 1-device suite these tests skip).  Asserts that
an 8-way stream-sharded bank step — vmap path, PR-1 Pallas path, fused
megakernel, heterogeneous hyperparams — matches the unsharded bank
bit-for-bit-to-float-tolerance per shard.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.core.easi import EASIConfig
from repro.core.smbgd import SMBGDConfig
from repro.stream import BankHyperparams, SeparatorBank, bank_sharding, make_sharded_bank_step

N_DEV = 8

pytestmark = [
    pytest.mark.multidevice,
    pytest.mark.skipif(
        jax.device_count() < N_DEV,
        reason=f"needs {N_DEV} devices (XLA_FLAGS=--xla_force_host_platform_device_count={N_DEV})",
    ),
]


def _cfgs(P=8, n=2, m=4):
    return (
        EASIConfig(n_components=n, n_features=m, mu=2e-3),
        SMBGDConfig(batch_size=P, mu=2e-3, beta=0.9, gamma=0.5),
    )


def _mesh():
    return jax.make_mesh((N_DEV,), ("stream",))


@pytest.mark.parametrize(
    "kwargs",
    [
        dict(),
        dict(use_pallas=True),
        dict(fused=True),
    ],
    ids=["vmap", "pallas_grad", "fused_megakernel"],
)
def test_8dev_sharded_step_matches_unsharded(kwargs):
    ecfg, ocfg = _cfgs()
    S = 2 * N_DEV  # 2 local streams per device
    bank = SeparatorBank(ecfg, ocfg, n_streams=S, **kwargs)
    key = jax.random.PRNGKey(0)
    state = bank.init(key)
    X = jax.random.normal(jax.random.fold_in(key, 1), (S, 8, 4))
    if bank.fused:
        X = bank.pad_batch(X)
    mesh = _mesh()
    placed = jax.device_put(state, bank_sharding(mesh))
    sharded_step = make_sharded_bank_step(bank, mesh)
    st_sh, Y_sh = sharded_step(placed, X)
    st_lo, Y_lo = bank.step(state, X)
    # per-shard (= per-stream) equality against the unsharded program
    np.testing.assert_allclose(
        np.asarray(st_sh.B), np.asarray(st_lo.B), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(st_sh.H_hat), np.asarray(st_lo.H_hat), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(st_sh.step), np.asarray(st_lo.step))
    np.testing.assert_allclose(np.asarray(Y_sh), np.asarray(Y_lo), rtol=1e-6, atol=1e-6)
    # the convergence statistic shards with its streams and matches exactly
    np.testing.assert_allclose(
        np.asarray(st_sh.conv), np.asarray(st_lo.conv), rtol=1e-6, atol=1e-7
    )
    # the state really is laid out over 8 devices
    assert len(st_sh.B.sharding.device_set) == N_DEV


def test_8dev_hetero_hyperparams_shard_with_streams():
    """Per-stream (μ, β, γ) must travel with their streams, not replicate."""
    ecfg, ocfg = _cfgs()
    S = 2 * N_DEV
    key = jax.random.PRNGKey(3)
    hp = BankHyperparams(
        mu=1e-3 + 2e-3 * jax.random.uniform(key, (S,)),
        beta=0.8 + 0.19 * jax.random.uniform(jax.random.fold_in(key, 1), (S,)),
        gamma=0.7 * jax.random.uniform(jax.random.fold_in(key, 2), (S,)),
    )
    bank = SeparatorBank(ecfg, ocfg, n_streams=S, fused=True, hyperparams=hp)
    state = bank.init(key)
    X = bank.pad_batch(jax.random.normal(jax.random.fold_in(key, 4), (S, 8, 4)))
    sharded_step = make_sharded_bank_step(bank, _mesh())
    st_sh, _ = sharded_step(jax.device_put(state, bank_sharding(_mesh())), X)
    st_lo, _ = bank.step(state, X)
    np.testing.assert_allclose(
        np.asarray(st_sh.B), np.asarray(st_lo.B), rtol=1e-6, atol=1e-6
    )


def test_8dev_active_mask_and_multiple_steps():
    """A 3-tick sharded trajectory with a changing active mask matches the
    unsharded bank (the serving scenario on a device rack)."""
    ecfg, ocfg = _cfgs()
    S = 2 * N_DEV
    bank = SeparatorBank(ecfg, ocfg, n_streams=S, fused=True)
    key = jax.random.PRNGKey(5)
    mesh = _mesh()
    sharded_step = make_sharded_bank_step(bank, mesh, donate=False)
    st_sh = jax.device_put(bank.init(key), bank_sharding(mesh))
    st_lo = bank.init(key)
    for k in range(3):
        X = bank.pad_batch(
            jax.random.normal(jax.random.fold_in(key, 10 + k), (S, 8, 4))
        )
        active = (jnp.arange(S) % (k + 2) != 0)
        st_sh, _ = sharded_step(st_sh, X, active)
        st_lo, _ = bank.step(st_lo, X, active=active)
    np.testing.assert_allclose(
        np.asarray(st_sh.B), np.asarray(st_lo.B), rtol=1e-6, atol=1e-6
    )
    np.testing.assert_array_equal(np.asarray(st_sh.step), np.asarray(st_lo.step))


@pytest.mark.parametrize(
    "fused, axis",
    [
        pytest.param(False, AxisType.Auto, id="vmap"),
        pytest.param(True, AxisType.Auto, id="fused_megakernel"),
        pytest.param(True, AxisType.Explicit, id="fused_megakernel_explicit"),
    ],
)
def test_8dev_slot_outputs_follow_the_sharded_y(fused, axis):
    """The served outputs are each slot's exact ``(P, n)`` values of a
    stream-sharded ``Y``, on a mesh whose axis is automatic or explicit:
    the one host read gathers the shards."""
    ecfg, ocfg = _cfgs()
    S = 2 * N_DEV
    bank = SeparatorBank(ecfg, ocfg, n_streams=S, fused=fused)
    key = jax.random.PRNGKey(7)
    X = jax.random.normal(jax.random.fold_in(key, 1), (S, 8, 4))
    if fused:
        X = bank.pad_batch(X)
    mesh = jax.make_mesh((N_DEV,), ("stream",), axis_types=(axis,))
    state = jax.device_put(bank.init(key), bank_sharding(mesh))
    _, Y = make_sharded_bank_step(bank, mesh)(state, X)
    assert len(Y.sharding.device_set) == N_DEV
    slots = [5, 0, 15, 9, 8]
    out = bank.slot_outputs(Y, slots)
    host = np.asarray(Y)
    assert len(out) == len(slots)
    for y, slot in zip(out, slots):
        assert type(y) is np.ndarray
        assert np.asarray(y).tobytes() == host[slot, :8, :2].tobytes()
