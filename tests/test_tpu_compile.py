"""Ahead-of-time compiles of the bank megakernels for a described TPU v5e.

Nothing runs: each test lowers and compiles for ``v5e:2x2`` with the TPU
compiler that ships with libtpu, so a kernel Mosaic would refuse (a layout it
aborts on, more VMEM than the scoped limit, a block the tiling rule rejects)
fails here instead of on the chip.  The topology is described inside a
fixture — never at import — and the tests skip where it cannot be described.

The backend of this process is the CPU, so the code that picks the lowering
from the backend (``repro.kernels.interpret_mode``) is steered to the compiled
path here, in the test.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs.easi_ica import EASI, SMBGD
from repro.kernels.easi_gradient import ops as easi_ops
from repro.stream import BankState, SeparatorBank, make_sharded_bank_step

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # AOT entries built for a described chip cannot be read back without
    # one: keep them out of any persistent compilation cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    jax.clear_caches()  # no interpret-mode trace of these shapes may linger
    yield desc
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture
def compiled_lowering(monkeypatch):
    """Layouts and kernel calls take the compiled (Mosaic) branch."""
    monkeypatch.setattr(easi_ops, "interpret_mode", lambda: False)


def _kernel_args(sharding, S, lay):
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    sd = lay.storage_dtype
    return (
        sds((S, lay.P_pad, lay.m_pad), jnp.float32),
        sds((S, lay.P_pad, 1), jnp.float32),
        sds((S, lay.n_pad, lay.m_pad), sd),
        sds((S, lay.n_pad, lay.n_pad), sd),
        sds((S,), jnp.int32),
        sds((S,), jnp.float32),
        sds((S,), jnp.int32),
        sds((S,), jnp.float32),
    )


def _compile_kernel(topo, kind, S, *, prefetch=False, policy="f32",
                    n=EASI.n_components, m=EASI.n_features, nonlinearity="cubic"):
    """Compile one megakernel (the paper shape unless told) with the
    default block_s; returns (compiled, resolved block_s)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    lay = easi_ops.bank_layout(
        n, m, SMBGD.batch_size, interpret=False, dtype_policy=policy,
    )
    fn = easi_ops.smbgd_step_bank if kind == "step" else easi_ops.smbgd_probe_bank
    call = lambda *a: fn(
        *a, block_p=lay.block_p, interpret=False, prefetch=prefetch,
        moments=True, nonlinearity=nonlinearity,
    )
    compiled = jax.jit(call).lower(*_kernel_args(one_chip, S, lay)).compile()
    return compiled, easi_ops.default_block_s(S, lay, interpret=False)


@pytest.mark.parametrize("policy", ["f32", "bf16"])
@pytest.mark.parametrize("prefetch", [False, True], ids=["sync", "prefetch"])
@pytest.mark.parametrize("kind", ["step", "probe"])
def test_megakernel_compiles_at_default_block_s(topo, kind, prefetch, policy):
    """Step and probe, sync and prefetch, f32 and bf16 state: at S=1024 the
    default block_s compiles under the kernels' VMEM limit."""
    compiled, block_s = _compile_kernel(
        topo, kind, 1024, prefetch=prefetch, policy=policy
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert block_s % 8 == 0 and 1024 % block_s == 0


def test_width_not_a_multiple_of_8_compiles(topo):
    """36 sessions: the default block is the whole bank (a divisor such as
    18 breaks Mosaic's sublane rule for the (block_s, 1) side channels)."""
    compiled, block_s = _compile_kernel(topo, "step", 36)
    assert block_s == 36
    assert "tpu_custom_call" in compiled.as_text()


def test_step_megakernel_compiles_at_256_lanes(topo):
    """The high-density EEG cell's step: 512 sessions at m = n = 256, tanh.
    Nothing is padded, a stream holds about 5.4 MB of VMEM, so the default
    block_s is 8 (64 grid cells over the streams) under the 64 MiB limit,
    and the kernel runs in place of any HBM temporary."""
    compiled, block_s = _compile_kernel(
        topo, "step", 512, n=256, m=256, nonlinearity="tanh"
    )
    lay = easi_ops.bank_layout(256, 256, SMBGD.batch_size, interpret=False)
    assert (lay.n_pad, lay.m_pad) == (256, 256)
    assert block_s == 8
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_sharded_step_compiles_without_collectives(topo, compiled_lowering):
    """The public scale-out path: a fused bank of 4096 sessions sharded over
    the four chips holds one Mosaic kernel per device and no collectives
    (sessions are independent)."""
    mesh = Mesh(np.asarray(topo.devices), ("stream",))
    S = 4 * 1024
    bank = SeparatorBank(EASI, SMBGD, S, fused=True, moments=True)
    lay = bank.layout
    assert lay.n_pad == 128 and lay.m_pad == 128  # the compiled layout
    sh = NamedSharding(mesh, PartitionSpec("stream"))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sh)
    state = BankState(
        B=sds((S, lay.n_pad, lay.m_pad), jnp.float32),
        H_hat=sds((S, lay.n_pad, lay.n_pad), jnp.float32),
        step=sds((S,), jnp.int32),
        conv=sds((S,), jnp.float32),
        health=sds((S,), jnp.int32),
        moments=sds((S, 2), jnp.float32),
    )
    X = sds((S, lay.P, lay.m), jnp.float32)
    step = make_sharded_bank_step(bank, mesh, donate=True)
    hlo = step.lower(state, X).compile().as_text()
    assert "tpu_custom_call" in hlo
    assert not [c for c in COLLECTIVES if c in hlo]


@pytest.mark.parametrize("prefetch", [False, True], ids=["sync", "prefetch"])
@pytest.mark.parametrize("kind", ["step", "probe"])
def test_megakernel_carries_one_name_for_both_schedules(topo, kind, prefetch):
    """The kernel's name is fixed, not taken from the kernel function that
    the DMA schedule picks: a trace finds it by that name either way.
    Lowered, not compiled: the HLO keeps each instruction's ``op_name``."""
    lay = easi_ops.bank_layout(
        EASI.n_components, EASI.n_features, SMBGD.batch_size, interpret=False
    )
    fn = easi_ops.smbgd_step_bank if kind == "step" else easi_ops.smbgd_probe_bank
    call = lambda *a: fn(
        *a, block_p=lay.block_p, interpret=False, prefetch=prefetch, moments=True
    )
    args = _kernel_args(SingleDeviceSharding(topo.devices[0]), 64, lay)
    hlo = jax.jit(call).lower(*args).as_text(dialect="hlo", debug_info=True)
    assert "tpu_custom_call" in hlo
    assert f'op_name="smbgd_{kind}_bank/pallas_call"' in hlo


def test_bank_step_dispatches_under_its_name(topo, compiled_lowering):
    """The serving step's jitted function is ``bank_step``, the name its
    dispatch carries in a profiler trace."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    S = 64
    bank = SeparatorBank(EASI, SMBGD, S, fused=True, moments=True)
    lay = bank.layout
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    state = BankState(
        B=sds((S, lay.n_pad, lay.m_pad), jnp.float32),
        H_hat=sds((S, lay.n_pad, lay.n_pad), jnp.float32),
        step=sds((S,), jnp.int32),
        conv=sds((S,), jnp.float32),
        health=sds((S,), jnp.int32),
        moments=sds((S, 2), jnp.float32),
    )
    X = sds((S, lay.P_pad, lay.m_pad), jnp.float32)
    active = sds((S,), jnp.bool_)
    step = bank.make_step(donate=False)
    hlo = step.lower(state, X, active).as_text(dialect="hlo", debug_info=True)
    assert hlo.startswith("HloModule jit_bank_step")
    assert 'op_name="smbgd_step_bank/pallas_call"' in hlo
