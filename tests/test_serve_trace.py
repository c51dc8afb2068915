"""The tick loop's phase spans, traced on the CPU.

``SeparationService.run_tick`` and ``step`` mark their phases with
``jax.profiler.TraceAnnotation`` spans named in ``engine.SPANS``.  A few ticks
are served under ``jax.profiler.trace`` and the spans read back with
``ProfileData``: each nests as the engine's docstring draws it, none takes a
name the benchmark harness gives its own spans, their number per tick does
not grow with the bank, and tracing changes no output and no state.  The
tick's outputs are one host read of the step's ``Y``: ``serve.outputs``
dispatches nothing at any bank width, and a change in the number of sessions
served compiles nothing.
"""
import contextlib
import glob
from collections import Counter

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.easi import EASIConfig
from repro.core.smbgd import SMBGDConfig
from repro.data.sources import ReplaySource
from repro.serve import (
    ConvergencePolicy,
    HealthPolicy,
    MomentPolicy,
    SeparationService,
)
from repro.serve.engine import SPANS
from repro.stream import SeparatorBank

# the spans the benchmark harness opens around its calls into the service
HARNESS = {"tick", "pull", "fetch", "admit"}
PARENT = {
    "serve.run_tick": None,
    "serve.backfill": "serve.run_tick",
    "serve.pull": "serve.run_tick",
    "serve.step": "serve.run_tick",
    "serve.stage": "serve.step",
    "serve.h2d": "serve.stage",
    "serve.launch": "serve.step",
    "serve.ready": "serve.step",
    "serve.outputs": "serve.step",
    "serve.moments": "serve.step",
    "serve.health": "serve.step",
    "serve.policy": "serve.step",
    "serve.release": "serve.run_tick",
    "serve.probe": "serve.run_tick",
    "serve.autoscale": "serve.run_tick",
}
TICKS = 3
P, M = 8, 4


def _service(S):
    """A fused bank with every per-tick sweep on: moments, health, policy;
    ``S - 1`` sessions pull from looping sources, one is pushed by hand."""
    easi = EASIConfig(n_components=2, n_features=M, mu=2e-3)
    opt = SMBGDConfig(batch_size=P, mu=2e-3, beta=0.9, gamma=0.5)
    bank = SeparatorBank(easi, opt, S, fused=True, moments=True)
    svc = SeparationService(
        bank, seed=3, policy=ConvergencePolicy(),
        health_policy=HealthPolicy(), moment_policy=MomentPolicy(),
    )
    rng = np.random.default_rng(0)
    for i in range(S - 1):
        data = rng.standard_normal((16 * P, M)).astype(np.float32)
        svc.admit(i, source=ReplaySource(data, loop=True))
    svc.admit("pushed")
    return svc


def _serve(svc):
    """``TICKS`` pull ticks, then one pushed batch through ``step``."""
    outs = [svc.run_tick() for _ in range(TICKS)]
    batch = np.random.default_rng(1).standard_normal((P, M)).astype(np.float32)
    outs.append(svc.step({"pushed": batch}))
    return [{k: np.asarray(v) for k, v in o.items()} for o in outs]


def _traced(S, log_dir):
    """The service, its outputs, its spans and its JAX dispatches (each as
    (name, parent)) over ``_serve``, traced."""
    svc = _service(S)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        outs = _serve(svc)
    spans = _host_spans(log_dir)
    return (
        svc, outs,
        [(n, p) for n, p in spans if not _is_dispatch(n)],
        [(n, p) for n, p in spans if _is_dispatch(n)],
    )


def _is_dispatch(name):
    return name.startswith(("PjitFunction(", "DevicePut"))


def _host_spans(log_dir):
    """(name, parent) of every program or harness span and every JAX
    dispatch on the host that no other dispatch holds, the parent being the
    innermost ``serve.*`` span around it on its thread."""
    path = sorted(glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True))[-1]
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = sorted(
                (
                    (e.start_ns, e.start_ns + e.duration_ns, e.name.split("#")[0])
                    for e in line.events
                    if e.name.startswith("serve.")
                    or e.name.split("#")[0] in HARNESS
                    or _is_dispatch(e.name)
                ),
                key=lambda e: (e[0], -e[1]),
            )
            stack = []
            for s, e, name in events:
                while stack and not (stack[-1][0] <= s and e <= stack[-1][1]):
                    stack.pop()
                if _is_dispatch(name) and any(_is_dispatch(n) for _, _, n in stack):
                    continue  # a dispatch inside another counts once
                parents = [n for _, _, n in stack if n.startswith("serve.")]
                found.append((name, parents[-1] if parents else None))
                stack.append((s, e, name))
    return found


@pytest.fixture(scope="module")
def traced4(tmp_path_factory):
    return _traced(4, tmp_path_factory.mktemp("trace4"))


@pytest.fixture(scope="module")
def traced16(tmp_path_factory):
    return _traced(16, tmp_path_factory.mktemp("trace16"))


def test_span_names_are_the_programs_own():
    assert len(set(SPANS)) == len(SPANS)
    assert set(SPANS) == set(PARENT)
    assert all(name.startswith("serve.") for name in SPANS)
    assert not HARNESS & set(SPANS)


def test_every_span_nests_as_documented(traced4):
    _, _, spans, _ = traced4
    assert not [name for name, _ in spans if name in HARNESS]
    names = Counter(name for name, _ in spans)
    assert set(names) == set(SPANS)
    assert names["serve.run_tick"] == TICKS
    # the pushed batch's step is a root of its own
    assert names["serve.step"] == TICKS + 1
    roots = Counter(name for name, parent in spans if parent is None)
    assert roots == Counter({"serve.run_tick": TICKS, "serve.step": 1})
    for name, parent in spans:
        if not (name == "serve.step" and parent is None):
            assert parent == PARENT[name], (name, parent)


def test_spans_per_tick_do_not_grow_with_the_bank(traced4, traced16):
    _, _, narrow, _ = traced4
    _, _, wide, _ = traced16
    assert Counter(narrow) == Counter(wide)


def test_tracing_changes_no_output_and_no_state(traced4):
    svc_on, outs_on, _, _ = traced4
    svc_off = _service(4)
    outs_off = _serve(svc_off)
    assert [sorted(o, key=str) for o in outs_on] == [
        sorted(o, key=str) for o in outs_off
    ]
    for on, off in zip(outs_on, outs_off):
        for sid in on:
            np.testing.assert_array_equal(on[sid], off[sid])
    for a, b in zip(jax.tree.leaves(svc_on.state), jax.tree.leaves(svc_off.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_outputs_are_one_dispatch_at_any_width(traced4, traced16):
    """The outputs are read, not computed: no dispatch inside
    ``serve.outputs`` at either width, though both served every tick."""
    for _, outs, spans, dispatches in (traced4, traced16):
        assert Counter(spans)[("serve.outputs", "serve.step")] == TICKS + 1
        assert all(outs)
        inside = Counter(n for n, parent in dispatches if parent == "serve.outputs")
        assert inside == Counter(), inside


@contextlib.contextmanager
def _compiles():
    """Counts the programs lowered inside the block, as the benchmark
    harness counts them in its window."""
    seen = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            seen.append(event)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def test_a_changing_served_count_compiles_nothing():
    svc = _service(8)
    # the eviction path's slot read, warmed as the benchmark harness warms it
    jax.block_until_ready(svc.bank.slot_state(svc.state, 0))
    svc.run_tick()
    batch = np.random.default_rng(2).standard_normal((P, M)).astype(np.float32)
    with _compiles() as seen:
        served = [len(svc.run_tick())]
        svc.evict(0)
        served.append(len(svc.run_tick()))
        served.append(len(svc.step({"pushed": batch})))
        served.append(len(svc.step({"pushed": batch, 1: batch, 2: batch})))
        served.append(len(svc.run_tick()))
    assert served == [7, 6, 1, 3, 6]
    assert seen == []


@pytest.fixture(scope="module")
def span_stats(tmp_path_factory):
    """The service of four slots served traced, and a function from a span
    name to the metadata of each of its spans, in order."""
    log_dir = tmp_path_factory.mktemp("stats")
    svc = _service(4)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        _serve(svc)
    path = sorted(glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True))[-1]
    events = [
        (e.name, dict(e.stats))
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for e in line.events
        if e.name.startswith("serve.")
    ]
    return svc, lambda name: [stats for n, stats in events if n == name]


def test_outputs_span_carries_sessions_and_width(span_stats):
    """``bytes`` is the whole padded ``(S, P_pad, n_pad)`` f32 ``Y`` read
    to the host, whatever the number of sessions served."""
    svc, stats = span_stats
    lay, S = svc.bank.layout, svc.bank.n_streams
    read = S * lay.P_pad * lay.n_pad * 4
    got = stats("serve.outputs")
    # three sessions pull from sources, then one batch is pushed by hand
    assert got == [{"sessions": 3, "width": 4, "bytes": read}] * TICKS + [
        {"sessions": 1, "width": 4, "bytes": read}
    ]


def test_h2d_span_carries_the_staged_bytes(span_stats):
    """``serve.h2d`` (inside ``serve.stage``) copies the whole staging
    buffer ``(S, P_pad, m_pad)`` f32 and the ``(S,)`` mask, whatever the
    number of sessions served."""
    svc, stats = span_stats
    lay, S = svc.bank.layout, svc.bank.n_streams
    staged = S * lay.P_pad * lay.m_pad * 4 + S
    assert stats("serve.h2d") == [{"bytes": staged}] * (TICKS + 1)


def test_launch_span_carries_the_grid(span_stats):
    """``serve.launch`` names the megakernel's resolved ``block_s`` and the
    grid's stream blocks, as ``ops.default_block_s`` resolves them."""
    from repro.kernels.easi_gradient import ops as easi_ops

    svc, stats = span_stats
    S = svc.bank.n_streams
    block_s = easi_ops.default_block_s(S, svc.bank.layout)
    assert svc.bank.resolved_block_s == block_s
    want = {"block_s": block_s, "stream_blocks": S // block_s}
    assert stats("serve.launch") == [want] * (TICKS + 1)
