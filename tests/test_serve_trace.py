"""The tick loop's phase spans, traced on the CPU.

``SeparationService.run_tick`` and ``step`` mark their phases with
``jax.profiler.TraceAnnotation`` spans named in ``engine.SPANS``.  A few ticks
are served under ``jax.profiler.trace`` and the spans read back with
``ProfileData``: each nests as the engine's docstring draws it, none takes a
name the benchmark harness gives its own spans, their number per tick does
not grow with the bank, and tracing changes no output and no state.
"""
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
    svc = _service(S)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        outs = _serve(svc)
    return svc, outs, _host_spans(log_dir)


def _host_spans(log_dir):
    """(name, parent) of every program or harness span on the host, the
    parent being the innermost ``serve.*`` span around it on its thread."""
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
                    if e.name.startswith("serve.") or e.name.split("#")[0] in HARNESS
                ),
                key=lambda e: (e[0], -e[1]),
            )
            stack = []
            for s, e, name in events:
                while stack and not (stack[-1][0] <= s and e <= stack[-1][1]):
                    stack.pop()
                parents = [n for _, _, n in stack if n.startswith("serve.")]
                found.append((name, parents[-1] if parents else None))
                stack.append((s, e, name))
    return found


@pytest.fixture(scope="module")
def traced4(tmp_path_factory):
    return _traced(4, tmp_path_factory.mktemp("trace4"))


def test_span_names_are_the_programs_own():
    assert len(set(SPANS)) == len(SPANS)
    assert set(SPANS) == set(PARENT)
    assert all(name.startswith("serve.") for name in SPANS)
    assert not HARNESS & set(SPANS)


def test_every_span_nests_as_documented(traced4):
    _, _, spans = traced4
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


def test_spans_per_tick_do_not_grow_with_the_bank(traced4, tmp_path):
    _, _, narrow = traced4
    _, _, wide = _traced(16, tmp_path)
    assert Counter(narrow) == Counter(wide)


def test_tracing_changes_no_output_and_no_state(traced4):
    svc_on, outs_on, _ = traced4
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
