"""The served outputs: one compiled program cuts every session's ``(P, n)``
output from the bank step's ``Y``.

``SeparationService.step`` (and so ``run_tick``) returns session id → the
session's slice of the step's ``Y``.  Each returned array is compared, bit
for bit, with ``np.asarray(Y)[slot, :P, :n]`` of the ``Y`` the jitted step
produced, on fused and vmap banks: with fewer sessions served than slots,
for a session evicted on the tick it is served, on the ticks after a
compaction, a grow and a shrink, and past one call's 128 outputs.  The
program slices straight from ``Y`` (no gather at the padded width), and
``prewarm`` compiles it at each width.
"""
import jax
import numpy as np
import pytest

from repro.core.easi import EASIConfig
from repro.core.smbgd import SMBGDConfig
from repro.data.sources import ReplaySource
from repro.serve import ConvergencePolicy, SeparationService
from repro.stream import SeparatorBank
from repro.stream.bank import _slot_outputs_jit

P, M, N = 8, 4, 2


def _bank(S, fused, P=P, M=M, N=N):
    easi = EASIConfig(n_components=N, n_features=M, mu=2e-3)
    opt = SMBGDConfig(batch_size=P, mu=2e-3, beta=0.9, gamma=0.5)
    return SeparatorBank(easi, opt, S, fused=fused)


@pytest.fixture
def recorded(monkeypatch):
    """Every jitted bank step made from here on records its host copy of
    ``Y`` and the service's slot map at the moment it runs."""
    seen, holder = [], {}
    make_step = SeparatorBank.make_step

    def recording(self, *args, **kw):
        fn = make_step(self, *args, **kw)

        def step(*operands):
            state, Y = fn(*operands)
            seen.append((np.asarray(Y), dict(holder["svc"].sessions)))
            return state, Y

        return step

    monkeypatch.setattr(SeparatorBank, "make_step", recording)

    def serve(svc):
        holder["svc"] = svc
        return svc

    return serve, seen


def _source(i):
    data = np.random.default_rng(i).standard_normal((16 * P, M)).astype(np.float32)
    return ReplaySource(data, loop=True)


def _assert_sliced(out, seen, served):
    """``out`` holds exactly ``served``, each the bits of its slot's slice
    of the last step's ``Y``."""
    Y, slots = seen[-1]
    assert sorted(out, key=str) == sorted(served, key=str)
    for sid, y in out.items():
        want = Y[slots[sid], :P, :N]
        got = np.asarray(y)
        assert got.shape == (P, N) and got.dtype == want.dtype
        assert got.tobytes() == want.tobytes(), sid


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "vmap"])
def test_outputs_are_the_slot_slices_of_y(recorded, fused):
    serve, seen = recorded
    svc = serve(SeparationService(_bank(8, fused), seed=0))
    for i in range(6):
        svc.admit(i, source=_source(i))
    for _ in range(3):
        _assert_sliced(svc.run_tick(), seen, range(6))


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "vmap"])
def test_one_pushed_session_in_a_bank_of_eight(recorded, fused):
    serve, seen = recorded
    svc = serve(SeparationService(_bank(8, fused), seed=0))
    for i in range(8):
        svc.admit(i)
    rng = np.random.default_rng(5)
    for sid in (3, 7, 0):
        batch = rng.standard_normal((P, M)).astype(np.float32)
        _assert_sliced(svc.step({sid: batch}), seen, [sid])


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "vmap"])
def test_a_session_evicted_on_its_tick_gets_its_output(recorded, fused):
    serve, seen = recorded
    # every session's statistic is under the threshold at its first tick
    policy = ConvergencePolicy(threshold=1e9, patience=1, min_ticks=1)
    svc = serve(SeparationService(_bank(4, fused), seed=0, policy=policy))
    for i in range(3):
        svc.admit(i, source=_source(i))
    out = svc.run_tick()
    _assert_sliced(out, seen, range(3))
    assert svc.sessions == {}
    assert all(svc.status(i) == "finished" for i in range(3))


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "vmap"])
def test_outputs_after_compact_grow_and_shrink(recorded, fused):
    serve, seen = recorded
    svc = serve(SeparationService(_bank(8, fused), seed=0))
    for i in range(6):
        svc.admit(i, source=_source(i))
    svc.run_tick()
    live = [2, 3, 4, 5]
    svc.evict(0)
    svc.evict(1)
    assert svc.compact() > 0
    _assert_sliced(svc.run_tick(), seen, live)
    svc.grow(16)
    for i in range(6, 10):
        svc.admit(i, source=_source(i))
    live += [6, 7, 8, 9]
    _assert_sliced(svc.run_tick(), seen, live)
    for sid in (2, 6, 9):
        svc.evict(sid)
        live.remove(sid)
    svc.shrink(8)
    assert svc.bank.n_streams == 8
    _assert_sliced(svc.run_tick(), seen, live)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "vmap"])
def test_the_program_slices_straight_from_y(fused):
    bank = _bank(16, fused)
    st = bank.init(jax.random.PRNGKey(0))
    X = np.random.default_rng(0).standard_normal((16, P, M)).astype(np.float32)
    _, Y = bank.step(st, bank.pad_batch(X) if fused else X)
    idx = np.zeros((16,), np.int32)
    jaxpr = jax.make_jaxpr(_slot_outputs_jit, static_argnums=(2, 3))(Y, idx, P, N)
    eqns = jaxpr.eqns[0].params["jaxpr"].eqns
    names = {e.primitive.name for e in eqns}
    assert "gather" not in names
    sizes = [e.params["slice_sizes"] for e in eqns if e.primitive.name == "dynamic_slice"]
    assert sizes == [(1, P, N)] * 16
    assert len(jaxpr.out_avals) == 16


@pytest.mark.parametrize("served", [1, 128, 129, 290])
def test_slot_outputs_past_one_call(served):
    """A bank wider than one call's outputs: every slot's slice, in order,
    whichever call cut it."""
    bank = _bank(300, False)
    Y = np.random.default_rng(1).standard_normal((300, P, N)).astype(np.float32)
    slots = np.random.default_rng(2).permutation(300)[:served].tolist()
    out = bank.slot_outputs(jax.numpy.asarray(Y), slots)
    assert len(out) == served
    for y, slot in zip(out, slots):
        assert np.asarray(y).tobytes() == Y[slot].tobytes()


def test_prewarm_compiles_the_output_program_at_each_width():
    # a geometry no other test serves, so the program's cache starts cold
    bank = _bank(4, True, P=4, M=5, N=3)
    svc = SeparationService(bank, seed=0)
    before = _slot_outputs_jit._cache_size()
    svc.prewarm([4, 8])
    assert _slot_outputs_jit._cache_size() == before + 2
    rng = np.random.default_rng(0)
    svc.admit("a")
    warm = _slot_outputs_jit._cache_size()
    svc.step({"a": rng.standard_normal((4, 5)).astype(np.float32)})
    svc.grow(8)
    svc.step({"a": rng.standard_normal((4, 5)).astype(np.float32)})
    assert _slot_outputs_jit._cache_size() == warm
    # a width not prewarmed compiles its program on its first tick
    svc.grow(16)
    svc.step({"a": rng.standard_normal((4, 5)).astype(np.float32)})
    assert _slot_outputs_jit._cache_size() == warm + 1
