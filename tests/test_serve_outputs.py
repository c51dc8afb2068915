"""The served outputs: one host read of the bank step's ``Y`` per tick, cut
there into every session's ``(P, n)`` output.

``SeparationService.step`` (and so ``run_tick``) returns session id → a host
view of the session's row of one fresh ``(S, P, n)`` array.  Each returned
array is compared, bit for bit, with ``np.asarray(Y)[slot, :P, :n]`` of the
``Y`` the jitted step produced, on fused and vmap banks: with fewer sessions
served than slots, for a session evicted on the tick it is served, on the
ticks after a compaction, a grow and a shrink, at any served count of a wide
bank, and at the chip's padded widths.  ``Y`` is read once per step, the
views pin no padded rows, a later tick leaves an earlier tick's outputs as
they were, and no width or served count compiles anything for outputs.
"""
import contextlib

import jax
import numpy as np
import pytest

from repro.core.easi import EASIConfig
from repro.core.smbgd import SMBGDConfig
from repro.data.sources import ReplaySource
from repro.serve import ConvergencePolicy, SeparationService
from repro.kernels.easi_gradient import ops as easi_ops
from repro.stream import SeparatorBank

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


class _CountedReads:
    """``Y`` behind a counter of its reads to the host."""

    def __init__(self, Y):
        self.Y, self.reads = Y, 0

    def __array__(self, dtype=None, copy=None):
        self.reads += 1
        return np.asarray(self.Y, dtype=dtype)


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "vmap"])
def test_the_program_slices_straight_from_y(fused):
    """One read of ``Y`` to the host per step; each output a host view of
    one compact ``(S, P, n)`` array that owns its data, so a kept output
    pins no padded row and nothing of ``Y``."""
    bank = _bank(16, fused)
    st = bank.init(jax.random.PRNGKey(0))
    X = np.random.default_rng(0).standard_normal((16, P, M)).astype(np.float32)
    _, Y = bank.step(st, bank.pad_batch(X) if fused else X)
    if fused:
        assert Y.shape[1:] != (P, N)  # padded rows and lanes to cut away
    counted = _CountedReads(Y)
    slots = [3, 0, 15, 7, 3]
    out = bank.slot_outputs(counted, slots)
    assert counted.reads == 1
    base = out[0].base
    assert isinstance(base, np.ndarray) and base.flags.owndata
    assert base.shape == (16, P, N) and base.flags.c_contiguous
    host = np.asarray(Y)
    for y, slot in zip(out, slots):
        assert type(y) is np.ndarray and y.base is base
        assert y.tobytes() == host[slot, :P, :N].tobytes()
    assert not np.shares_memory(base, host)


@pytest.mark.parametrize("served", [1, 128, 129, 290])
def test_slot_outputs_past_one_call(served):
    """A bank of 300 slots: every served slot's output, in order, at any
    served count, each a row of one host array."""
    bank = _bank(300, False)
    Y = np.random.default_rng(1).standard_normal((300, P, N)).astype(np.float32)
    slots = np.random.default_rng(2).permutation(300)[:served].tolist()
    out = bank.slot_outputs(jax.numpy.asarray(Y), slots)
    assert len(out) == served
    assert len({id(y.base) for y in out}) == 1
    for y, slot in zip(out, slots):
        assert np.asarray(y).tobytes() == Y[slot].tobytes()


@pytest.mark.parametrize(
    "S, P_, n", [(256, 8, 2), (256, 8, 22), (512, 8, 256)],
    ids=["paper", "eeg22", "gsn256"],
)
def test_host_cut_at_the_chips_padded_width(S, P_, n):
    """The cells' shapes at the layout the chip runs (lanes padded to 128
    or 256): every slot's output is the bits of ``Y[slot, :P, :n]``, and the
    array behind the views holds ``S·P·n`` elements, not the padded ones."""
    m = 4 if n == 2 else n
    lay = easi_ops.bank_layout(n, m, P_, interpret=False)
    bank = _bank(S, True, P=P_, M=m, N=n)
    Y = jax.numpy.asarray(
        np.random.default_rng(S + n).standard_normal((S, lay.P_pad, lay.n_pad)),
        np.float32,
    )
    assert Y.shape[2] == (128 if n < 128 else 256)
    slots = np.random.default_rng(n).permutation(S).tolist()
    out = bank.slot_outputs(Y, slots)
    host = np.asarray(Y)
    assert out[0].base.nbytes == S * P_ * n * 4
    for y, slot in zip(out, slots):
        assert y.shape == (P_, n) and y.dtype == np.float32
        assert y.tobytes() == host[slot, :P_, :n].tobytes()


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "vmap"])
def test_a_later_tick_leaves_earlier_outputs_intact(recorded, fused):
    """Each tick's outputs live in a buffer of their own: the next tick
    neither shares nor overwrites it."""
    serve, seen = recorded
    svc = serve(SeparationService(_bank(8, fused), seed=0))
    for i in range(6):
        svc.admit(i, source=_source(i))
    first = svc.run_tick()
    kept = {sid: y.copy() for sid, y in first.items()}
    second = svc.run_tick()
    _assert_sliced(second, seen, range(6))
    for sid in first:
        assert not np.shares_memory(first[sid], second[sid])
        assert first[sid].tobytes() == kept[sid].tobytes()
    # the two ticks' outputs differ, so an overwrite could not hide
    assert any(first[s].tobytes() != second[s].tobytes() for s in first)


@contextlib.contextmanager
def _compiles_in_outputs(monkeypatch):
    """Counts the programs lowered inside every ``slot_outputs`` call, and
    the calls."""
    seen = {"calls": 0, "compiles": 0}
    inside = [False]

    def listen(event, duration, **kw):
        if inside[0] and event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            seen["compiles"] += 1

    slot_outputs = SeparatorBank.slot_outputs

    def counted(self, Y, slots):
        seen["calls"] += 1
        inside[0] = True
        try:
            return slot_outputs(self, Y, slots)
        finally:
            inside[0] = False

    monkeypatch.setattr(SeparatorBank, "slot_outputs", counted)
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def test_prewarm_compiles_the_output_program_at_each_width(monkeypatch):
    """No program serves the outputs: across ``prewarm``, a prewarmed
    grow and a grow to a width never prewarmed, nothing compiles for them."""
    # a geometry no other test serves, so nothing of it is compiled yet
    bank = _bank(4, True, P=4, M=5, N=3)
    svc = SeparationService(bank, seed=0)
    rng = np.random.default_rng(0)
    with _compiles_in_outputs(monkeypatch) as seen:
        svc.prewarm([4, 8])
        svc.admit("a")
        svc.step({"a": rng.standard_normal((4, 5)).astype(np.float32)})
        svc.grow(8)
        svc.step({"a": rng.standard_normal((4, 5)).astype(np.float32)})
        svc.grow(16)
        out = svc.step({"a": rng.standard_normal((4, 5)).astype(np.float32)})
    assert seen == {"calls": 3, "compiles": 0}
    assert out["a"].shape == (4, 3)
