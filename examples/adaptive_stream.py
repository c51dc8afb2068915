"""Adaptive separation under non-stationary mixing — single stream and bank.

    PYTHONPATH=src python examples/adaptive_stream.py

Part 1 (the paper's §I motivation): one mixing matrix rotates slowly while the
separator streams mini-batches through ``partial_fit``.  SMBGD's γ-momentum +
β-recency weighting is exactly the knob the paper describes: large γ for
smooth drift, small γ for abrupt change.  Prints tracking error over time for
SMBGD vs plain SGD.

Part 2 (the production shape): a ``SeparatorBank`` runs S such sessions at
once — every stream has its own sources, its own mixing matrix and its own
drift phase (``MixedSignals(streams=S)``), yet each tick is ONE fused array
program.  With ``use_pallas=True`` the gradient sums of all streams go through
a single (streams, P-tiles) kernel launch (interpreted on the CPU backend,
compiled on a TPU).

Part 3 (the serving shape): more sessions than slots.  A ``SeparationService``
with a ``ConvergencePolicy`` watches each session's in-bank convergence
statistic (relative update magnitude, computed inside the fused step) and
auto-evicts converged separators, backfilling their slots from the bounded
admission queue within the same tick — converged sessions stop wasting
hardware, exactly the utilization knob the paper's always-on datapath needs
at rack scale.

Part 4 (the drift-aware pipeline): a CORTEX-style ``ChannelBankSource``
session — a multi-channel ``.npy`` recording served through ``run_tick()``'s
pull loop — whose mixing rotates abruptly mid-recording.  The service has NO
ground truth (real recordings don't ship their mixing matrix): the
``DriftPolicy`` watchdog sees the in-kernel conv statistic rise on the
converged-hot session, fires a ``DriftEvent``, μ-boosts the stream through
the bank's per-stream hyperparameter rows, and the separator re-converges on
the new mixing — while the no-watchdog deployment would keep serving the
stale separator.

Part 5 (the containment shape): real feeds fail — sensors drop to NaN,
amplifiers rail to Inf, network reads stall.  The bank's megakernel folds a
per-stream *health word* into the same in-register reduction as the conv
statistic (non-finite B′/Ĥ′/Y bits + an update-magnitude blow-up bit) and
REFUSES an unhealthy commit in-kernel, so one poisoned mini-batch never
reaches persistent state.  A ``HealthPolicy`` turns the word into a
lifecycle: rollback to the last-known-good shadow snapshot + μ cut, then
quarantine under out-of-band health probes, then eviction with reason
``"diverged"`` — while ``ResilientSource`` retries transient source faults
before they ever become degraded ticks.  The drill injects faults with the
test suite's own ``FaultInjector`` chaos harness.

Part 6 (the real-time shape): serving is only as good as its worst tick.
Every tick the service stops a TIME-TO-READY clock (a ``block_until_ready``
on the bank's tiny conv telemetry leaf — honest on asynchronous backends,
where wall-clock around a jitted call times only the dispatch) and feeds a
streaming quantile sketch: ``svc.metrics()`` reports p50/p99/p999 live.  An
``SLOPolicy(deadline_budget_s=...)`` arms deadline accounting — over-budget
ticks count misses, per-session, and opt-in ``shed``/``gate_admissions``
levers turn sustained misses into load control.  The drill records a live
run's blocks through a ``RecordingSource`` tap, saves the ``.npz`` trace,
and replays it deterministically into a fresh service under a budget — the
same record→replay harness ``stream_throughput.py --slo`` gates in CI.

Part 7 (the adaptive-μ shape): the fixed drift boost of Part 4 is open-loop —
μ×4 for 40 ticks whether the separator needs 10 or 100.  With
``SeparatorBank(..., moments=True)`` the megakernel folds per-stream raw
moments [Σy², Σy⁴] into the same in-register reduction as the conv statistic
(8 bytes/stream/tick of extra HBM — the output leaf is the whole cost), and a
``MomentPolicy`` turns them into a closed-loop μ controller: per-session EMA
kurtosis, fast tracker vs slow reference; when drift re-mixes the output the
central limit theorem drags its kurtosis toward Gaussian, the fast EMA leaves
the reference, and μ scales with the deviation — then ANNEALS back to base as
re-convergence pulls the kurtosis home.  The drill serves the same abrupt
rotation twice, side by side: fixed boost vs moment-scaled.  Composition with
the other μ writers is pinned: a HealthPolicy μ-cut WINS while live, the
DriftPolicy boost and the controller MULTIPLY.

Part 8 (the elastic shape): a bank frozen at init either strands capacity or
turns every burst into queue wait.  ``AutoscalePolicy`` closes the loop: the
``run_tick`` autoscaler grows the bank (power-of-two ladder, pre-compilable
via ``svc.prewarm``) while sessions wait in the queue, and after the burst
drains it compacts the survivors to the low slots and shrinks the width back
— hysteresis bands plus cooldown ticks, so it never flaps.  Every resize is
a prefix copy and every compaction a verbatim row move: co-tenant
trajectories stay bit-identical to a fixed-width run (the tests/test_elastic
property sweep pins this on both execution paths).  The drill admits a burst
against a deliberately narrow bank and prints the width/utilization arc:
stranded-queue → grown → drained → compacted+shrunk.

Probe knobs (``DriftPolicy(mode="readmit")``, the parked alternative to the
hot watch used below): ``probe_every`` sets the out-of-band probe cadence in
run_ticks, and ``probe_batch`` sets how many parked sessions share one
no-commit probe-bank launch — at serving scale (thousands parked) the
watchdog costs O(parked / probe_batch) dispatches per probe tick instead of
O(parked); ``probe_batch=0`` falls back to the one-dispatch-per-session
loop.  See ``stream_throughput.py --probe`` for the measured gap at 256
parked sessions.

Memory-system knobs (the bank's bandwidth/capacity levers; all optional):

* ``SeparatorBank(..., dtype_policy="bf16")`` stores the persistent separator
  state (B, Ĥ) in bfloat16 while every gradient and commit still accumulates
  in f32 inside the kernel — casts happen only at the load/commit boundary.
  Capacity doubles per byte of HBM: ``bank.layout.persistent_bytes_per_session``
  drops 520 → 264 bytes for the paper's 4→2 shape.  Per-stream hyperparameter
  rows stay f32 regardless of policy.  The default (``None``) follows
  ``easi.dtype`` so existing configs keep their storage contract.
* Geometry (``block_p``, ``block_s``) resolves from the checked-in
  ``AUTOTUNE.json`` when the bank's (S, P, m, n, backend) key was swept —
  run ``benchmarks/stream_throughput.py --autotune`` once per deployment
  shape to refresh it.  Explicitly-passed knobs always win, and
  ``dtype_policy`` is recorded but never auto-applied (a numerics contract
  stays an explicit opt-in).  ``autotune=False`` opts out entirely.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AdaptiveICA, EASIConfig, SMBGDConfig, amari_index, global_system
from repro.data.pipeline import MixedSignals
from repro.serve.engine import ConvergencePolicy, SeparationService
from repro.stream import SeparatorBank


def run(algorithm: str, gamma: float, n_steps: int = 4000) -> list:
    ecfg = EASIConfig(n_components=2, n_features=4, mu=3e-3)
    ocfg = SMBGDConfig(batch_size=16, mu=3e-3, beta=0.9, gamma=gamma)
    ica = AdaptiveICA(ecfg, ocfg, algorithm=algorithm)
    state = ica.init(jax.random.PRNGKey(0))
    pipe = MixedSignals(m=4, n=2, batch=16, seed=0, drift_rate=3e-6)
    fit = jax.jit(lambda s, x: ica.partial_fit(s, x))
    errs = []
    for step in range(n_steps):
        state, _ = fit(state, pipe.batch_for_step(step))
        if step % 500 == 499:
            pi = float(amari_index(global_system(state.B, pipe.mixing_at(step))))
            errs.append((step, pi))
    return errs


def run_bank(n_streams: int = 8, n_steps: int = 2000) -> jnp.ndarray:
    """S drifting sessions, one fused program; returns per-stream Amari index."""
    ecfg = EASIConfig(n_components=2, n_features=4, mu=3e-3)
    ocfg = SMBGDConfig(batch_size=16, mu=3e-3, beta=0.9, gamma=0.5)
    bank = SeparatorBank(ecfg, ocfg, n_streams=n_streams)
    state = bank.init(jax.random.PRNGKey(0))
    pipe = MixedSignals(
        m=4, n=2, batch=16, seed=0, drift_rate=3e-6, streams=n_streams
    )
    step_fn = jax.jit(lambda s, x: bank.step(s, x))
    for step in range(n_steps):
        state, _ = step_fn(state, pipe.batch_for_step(step))
    # evaluate against the last-seen mixing (same convention as run())
    return bank.performance_index(state, pipe.mixing_at(n_steps - 1))


def run_service(n_slots: int = 4, n_sessions: int = 10, max_ticks: int = 1500):
    """Churning deployment: sessions queue for slots, converge, auto-evict.

    Returns (events, finished) — the lifecycle log and the eviction records
    (final separation matrix + serving stats per session).
    """
    P = 16
    ecfg = EASIConfig(n_components=2, n_features=4, mu=3e-3)
    ocfg = SMBGDConfig(batch_size=P, mu=3e-3, beta=0.9, gamma=0.5)
    events = []
    svc = SeparationService(
        SeparatorBank(ecfg, ocfg, n_streams=n_slots, fused=True),
        seed=0,
        policy=ConvergencePolicy(threshold=0.02, patience=5, min_ticks=50, ema=0.9),
        max_queue=n_sessions,
        on_admit=lambda sid, slot: events.append((svc.metrics["n_ticks"], "admit", sid, slot)),
        on_evict=lambda sid, rec: events.append((svc.metrics["n_ticks"], "evict", sid, rec.reason)),
    )
    pipe = MixedSignals(m=4, n=2, batch=P, seed=0, streams=n_sessions)
    sids = [f"user-{i}" for i in range(n_sessions)]
    for sid in sids:
        svc.admit(sid)  # first n_slots activate, the rest queue
    stream_of = {sid: i for i, sid in enumerate(sids)}
    for tick in range(max_ticks):
        active = [sid for sid in sids if svc.status(sid) == "active"]
        if not active:
            break
        X = np.asarray(pipe.batch_for_step(tick))
        svc.step({sid: X[stream_of[sid]] for sid in active})
    return events, svc.pop_finished(), svc.metrics


def run_drift_recording(n_ticks: int = 700, jump_tick: int = 300):
    """Part 4: serve a channel-bank recording whose mixing jumps mid-run.

    Returns (events, trace, first_converged) — the lifecycle/drift log,
    (tick, amari) samples against the recording's true piecewise mixing,
    and the tick the session first converged (= when a policy-only service
    would have evicted it).
    """
    import os
    import tempfile

    from repro.data import signals
    from repro.data.sources import ChannelBankSource, _givens
    from repro.serve import DriftPolicy

    P, m, n = 16, 4, 2
    T = n_ticks * P
    # synthesize the "recording": sub-Gaussian sources through a mixing that
    # is stationary, rotates ~1.2 rad abruptly at jump_tick, then stationary
    key = jax.random.PRNGKey(0)
    S = signals.source_bank(jax.random.PRNGKey(1), n, T)
    A0 = signals.random_mixing_matrix(key, m, n)
    A1 = _givens(m, 1.2) @ A0  # the same rotation plane the watchdog drills use
    t_jump = jump_tick * P
    At = jnp.where(
        (jnp.arange(T) < t_jump)[:, None, None],
        jnp.broadcast_to(A0, (T, m, n)),
        jnp.broadcast_to(A1, (T, m, n)),
    )
    X = signals.mix_nonstationary(At, S)  # (T, m)
    rec_fd, rec_path = tempfile.mkstemp(suffix=".npy")
    os.close(rec_fd)
    np.save(rec_path, np.asarray(X).T.astype(np.float32))  # channel-major

    ecfg = EASIConfig(n_components=n, n_features=m, mu=3e-3)
    ocfg = SMBGDConfig(batch_size=P, mu=3e-3, beta=0.9, gamma=0.5)
    events = []
    svc = SeparationService(
        SeparatorBank(ecfg, ocfg, n_streams=2),
        seed=0,
        policy=ConvergencePolicy(threshold=0.025, patience=5, min_ticks=50, ema=0.9),
        # mode="boost" keeps the session hot in its slot; mode="readmit"
        # would park it instead and probe the frozen separator out-of-band
        # every `probe_every` ticks, `probe_batch` parked sessions per
        # batched probe launch (the rack-scale watchdog configuration)
        drift_policy=DriftPolicy(
            retrigger=0.03, patience=2, ema=0.8, cooldown=3,
            mode="boost", boost=4.0, boost_ticks=40,
        ),
        on_drift=lambda sid, ev: events.append(
            (int(svc.metrics["n_ticks"]), "drift", sid, f"μ×4 (stat {ev.stat:.3f})")
        ),
        on_evict=lambda sid, r: events.append(
            (int(svc.metrics["n_ticks"]), "evict", sid, r.reason)
        ),
    )
    # the session IS the recording: memory-mapped windowed reads, no ground
    # truth exposed — the blind conv statistic alone drives the lifecycle
    svc.admit("eeg-0", source=ChannelBankSource(rec_path, center=False))
    first_converged = None
    trace = []
    try:
        for tick in range(n_ticks - 1):
            svc.run_tick()
            st = svc.status("eeg-0")
            if st == "converged" and first_converged is None:
                first_converged = tick
                events.append((tick, "hot", "eeg-0", "converged, kept hot"))
            if tick % 50 == 49 and st in ("active", "converged"):
                B = svc.bank.slot_state(svc.state, svc.sessions["eeg-0"]).B
                A = A0 if tick < jump_tick else A1
                trace.append((tick, float(amari_index(global_system(B, A)))))
    finally:
        os.unlink(rec_path)
    return events, trace, first_converged


def run_containment(n_ticks: int = 30):
    """Part 5: fault containment — a poisoned feed, a flaky feed, a clean one.

    Returns (events, metrics, statuses) — the containment log (rollback →
    quarantine → release for the poisoned session; nothing at all for the
    retried flaky one), the service counters, and each session's final status.
    """
    from repro.data.resilience import FaultInjector, ResilientSource
    from repro.data.sources import ReplaySource
    from repro.kernels.easi_gradient.ops import describe_health
    from repro.serve import HealthPolicy

    P, m, n = 16, 4, 2
    ecfg = EASIConfig(n_components=n, n_features=m, mu=3e-3)
    ocfg = SMBGDConfig(batch_size=P, mu=3e-3, beta=0.9, gamma=0.5)
    rng = np.random.default_rng(0)

    def feed():
        return ReplaySource(
            rng.standard_normal(((n_ticks + 2) * P, m)).astype(np.float32),
            loop=True,
        )

    events = []
    svc = SeparationService(
        SeparatorBank(ecfg, ocfg, n_streams=3),  # health_checks=True default
        seed=0,
        # threshold unreachable: the drill watches containment, not convergence
        policy=ConvergencePolicy(threshold=1e-12, patience=10**6, min_ticks=10**6),
        health_policy=HealthPolicy(
            max_rollbacks=1, window=30, mu_cut=0.25, cut_ticks=5,
            max_quarantines=1, probation=2, probe_every=2, shadow_every=4,
        ),
        on_health=lambda sid, ev: events.append(
            (ev.tick, ev.action, sid, describe_health(ev.word))
        ),
    )
    # NaN bursts at blocks 2 and 4: the first costs a rollback, the second
    # exhausts the rollback budget → quarantine; the clean blocks after serve
    # the probation under out-of-band health probes → warm release.  (A feed
    # still poisoned IN quarantine keeps failing probes on the same ladder
    # and exits with reason "diverged" instead.)
    svc.admit("poisoned", source=FaultInjector(feed(), {2: "nan", 4: "nan"}))
    # two transient raises, retried clean inside the source wrapper — the
    # service never even sees a degraded tick
    svc.admit("flaky", source=ResilientSource(
        FaultInjector(feed(), {3: "raise", 5: "raise"}), max_retries=3,
    ))
    svc.admit("clean", source=feed())
    for _ in range(n_ticks):
        svc.run_tick()
    statuses = {sid: svc.status(sid) for sid in ("poisoned", "flaky", "clean")}
    return events, svc.metrics, statuses


def run_slo_replay(n_blocks: int = 40, budget_factor: float = 5.0):
    """Part 6: latency SLOs over a recorded load.

    Records a 2-session live run through ``RecordingSource`` taps, saves the
    trace, then replays it into a fresh service with a deadline budget set at
    ``budget_factor`` x the live run's median time-to-ready.  Returns (live
    metrics, replay metrics, miss rate, budget) — and the replay's separated
    outputs are bit-identical to the live run's (tested in test_slo.py), so
    the tail you measure is the tail you shipped.
    """
    import tempfile

    from repro.data.sources import RecordingSource, load_recording, save_recording
    from repro.serve import SLOPolicy
    from repro.serve.slo import replay

    P, m, n = 16, 4, 2
    ecfg = EASIConfig(n_components=n, n_features=m, mu=3e-3)
    ocfg = SMBGDConfig(batch_size=P, mu=3e-3, beta=0.9, gamma=0.5)

    def fresh(slo=None):
        return SeparationService(
            SeparatorBank(ecfg, ocfg, n_streams=2), seed=0, slo=slo,
        )

    taps = {
        sid: RecordingSource(
            SyntheticSourceFactory(m=m, n=n, P=P, seed=seed)
        )
        for sid, seed in (("left", 7), ("right", 8))
    }
    live = fresh()
    for sid, tap in taps.items():
        live.admit(sid, source=tap)
    for _ in range(n_blocks):
        live.run_tick()
    live_m = live.metrics
    budget = budget_factor * live_m["p50_tick_s"]

    with tempfile.TemporaryDirectory() as d:
        path = Path(d) / "slo_demo.npz"
        save_recording(
            path, taps,
            events=[
                {"action": "admit", "sid": sid, "tick": 0, "order": i}
                for i, sid in enumerate(taps)
            ],
            meta={"P": P, "m": m, "n": n},
        )
        rec = load_recording(path)
        svc = fresh(slo=SLOPolicy(deadline_budget_s=budget))
        replay(svc, rec)
    rep_m = svc.metrics
    timed = rep_m["n_timed_ticks"] + rep_m["n_empty_ticks"]
    miss_rate = rep_m["n_deadline_misses"] / timed if timed else float("nan")
    return live_m, rep_m, miss_rate, budget


def run_moment_drill(n_ticks: int = 700, jump_tick: int = 300):
    """Part 7: fixed μ-boost vs the moment-scaled adaptive μ controller.

    The same abrupt ~1.2 rad mixing rotation (the Part-4 recipe) is served
    twice from identical seeds: once with the open-loop ``DriftPolicy``
    boost (μ×4 for 40 ticks on watchdog fire), once with a no-op boost plus
    a ``MomentPolicy`` controller reading the bank's in-kernel [Σy², Σy⁴]
    telemetry.  Returns (trace_fixed, trace_ctrl, scale_trace, reconv) —
    (tick, amari) samples for both services, the controller's (tick,
    μ-multiplier) trajectory, and the ticks-to-reconverge after the jump
    for each (None = never re-entered the pre-jump band).
    """
    from repro.data import signals
    from repro.data.sources import ReplaySource, _givens
    from repro.serve import DriftPolicy, MomentPolicy

    P, m, n = 16, 4, 2
    T = n_ticks * P
    src = signals.source_bank(jax.random.PRNGKey(1), n, T)
    A0 = signals.random_mixing_matrix(jax.random.PRNGKey(0), m, n)
    # a HARD jump (1.4 rad) at a conservative base μ: re-adaptation outlasts
    # the fixed 40-tick boost window, which is exactly where open-loop boost
    # mis-calibrates and the closed loop pays off
    A1 = _givens(m, 1.4) @ A0
    t_jump = jump_tick * P
    At = jnp.where(
        (jnp.arange(T) < t_jump)[:, None, None],
        jnp.broadcast_to(A0, (T, m, n)),
        jnp.broadcast_to(A1, (T, m, n)),
    )
    X = np.asarray(signals.mix_nonstationary(At, src)).astype(np.float32)

    ecfg = EASIConfig(n_components=n, n_features=m, mu=1e-3)
    ocfg = SMBGDConfig(batch_size=P, mu=1e-3, beta=0.9, gamma=0.5)

    def build(moment_policy=None, boost=4.0):
        svc = SeparationService(
            SeparatorBank(
                ecfg, ocfg, n_streams=2, moments=moment_policy is not None
            ),
            seed=0,
            policy=ConvergencePolicy(
                threshold=0.025, patience=5, min_ticks=50, ema=0.9
            ),
            # both services share the hot watchdog; the controller run makes
            # its boost a no-op (boost=1) so re-adaptation speed is the
            # moment controller's alone
            drift_policy=DriftPolicy(
                retrigger=0.03, patience=2, ema=0.8, cooldown=3,
                mode="boost", boost=boost, boost_ticks=40,
            ),
            moment_policy=moment_policy,
        )
        svc.admit("eeg-0", source=ReplaySource(X))
        return svc

    fixed = build()
    ctrl = build(
        moment_policy=MomentPolicy(
            ema_fast=0.3, ema_slow=0.005, warmup_ticks=20,
            deadband=0.05, gain=6.0, max_scale=8.0,
        ),
        boost=1.0,
    )
    traces = {"fixed": [], "ctrl": []}
    scale_trace = []
    for tick in range(n_ticks - 1):
        for name, svc in (("fixed", fixed), ("ctrl", ctrl)):
            svc.run_tick()
            if tick % 10 == 9 and svc.status("eeg-0") in ("active", "converged"):
                B = svc.bank.slot_state(svc.state, svc.sessions["eeg-0"]).B
                A = A0 if tick < jump_tick else A1
                traces[name].append(
                    (tick, float(amari_index(global_system(B, A))))
                )
        if tick % 10 == 9 and "eeg-0" in ctrl.sessions:
            scale_trace.append(
                (tick, ctrl.session_stats("eeg-0").get("mu_ctrl", 1.0))
            )

    def ticks_to_reconverge(trace):
        pre = [pi for t, pi in trace if t < jump_tick]
        band = 1.5 * pre[-1]  # "recovered" = back inside 1.5x pre-jump error
        for t, pi in trace:
            if t >= jump_tick + 10 and pi <= band:
                return t - jump_tick
        return None

    reconv = {k: ticks_to_reconverge(v) for k, v in traces.items()}
    return traces["fixed"], traces["ctrl"], scale_trace, reconv


class SyntheticSourceFactory:
    """A finite synthetic feed for the Part-6 drill: ``n_blocks`` of mixed
    signals, then ``SourceExhausted`` (so the replayed sessions drain and the
    replay loop terminates on its own)."""

    def __init__(self, m, n, P, seed, n_blocks: int = 40):
        from repro.data.sources import SyntheticSource

        self._src = SyntheticSource(MixedSignals(m=m, n=n, batch=P, seed=seed))
        self._left = n_blocks

    def next_block(self, n_samples):
        from repro.data.sources import SourceExhausted

        if self._left <= 0:
            raise SourceExhausted("demo feed drained")
        self._left -= 1
        return self._src.next_block(n_samples)


def run_elastic_drill(n_sessions: int = 8, n_blocks: int = 10):
    """Part 8: elastic capacity under a burst.

    A bank born at width 2 takes an ``n_sessions``-session burst of finite
    feeds: the autoscaler grows it while the queue holds work, the feeds
    drain and release their slots, and the autoscaler compacts + shrinks the
    width back down.  Returns the resize history, a per-tick width trace and
    the utilization arc (burst / peak / post-drain)."""
    from repro.serve import AutoscalePolicy

    m, n, P = 4, 2, 8
    ecfg = EASIConfig(n_components=n, n_features=m, mu=2e-3)
    ocfg = SMBGDConfig(batch_size=P, mu=2e-3, beta=0.9, gamma=0.5)
    pol = AutoscalePolicy(max_streams=8, min_streams=2, cooldown_ticks=2)
    svc = SeparationService(
        SeparatorBank(ecfg, ocfg, n_streams=2),
        seed=0,
        autoscale=pol,
        max_queue=n_sessions,
    )
    # compile the whole power-of-two ladder up front: the first post-resize
    # tick then pays zero XLA compile (the bench's resize-overhead gate)
    svc.prewarm([2, 4, 8])
    for k in range(n_sessions):
        # the last session's feed outlives the burst: it ends up stranded in
        # a HIGH slot when the others drain, so the shrink has to compact —
        # the full grow → drain → compact → shrink arc in one drill
        blocks = n_blocks * 4 if k == n_sessions - 1 else n_blocks
        svc.admit(
            f"s{k}",
            source=SyntheticSourceFactory(m, n, P, seed=k, n_blocks=blocks),
        )
    util_burst = svc.metrics["bank_utilization"]
    widths, util_peak = [], 0.0
    for _ in range(80):
        svc.run_tick()
        widths.append(svc.bank.n_streams)
        util_peak = max(util_peak, svc.metrics["bank_utilization"])
        if svc.n_active == 0 and svc.bank.n_streams == pol.min_streams:
            break
    metrics = svc.metrics
    return {
        "history": svc.lifecycle["resize_history"],
        "widths": widths,
        "util_burst": util_burst,
        "util_peak": util_peak,
        "util_final": metrics["bank_utilization"],
        "n_grows": int(metrics["n_grows"]),
        "n_shrinks": int(metrics["n_shrinks"]),
        "n_compactions": int(metrics["n_compactions"]),
        "final_width": svc.bank.n_streams,
    }


def main():
    print("streaming 4000 mini-batches with a slowly rotating mixing matrix")
    print(f"{'step':>6} | {'SGD':>8} | {'SMBGD γ=0.5':>12}")
    sgd = dict(run("sgd", gamma=0.0))
    smb = dict(run("smbgd", gamma=0.5))
    for step in sorted(sgd):
        print(f"{step:6d} | {sgd[step]:8.4f} | {smb[step]:12.4f}")
    final_sgd, final_smb = list(sgd.values())[-1], list(smb.values())[-1]
    print(
        f"\nfinal tracking Amari index: SGD {final_sgd:.4f}  vs  SMBGD {final_smb:.4f}"
        f"  ({'SMBGD tracks better' if final_smb < final_sgd else 'comparable'})"
    )

    S = 8
    print(f"\nSeparatorBank: {S} drifting sessions, one fused step per tick")
    pis = run_bank(n_streams=S)
    per = "  ".join(f"{float(p):.3f}" for p in pis)
    print(f"per-stream tracking Amari index after 2000 ticks: {per}")
    print(f"worst stream: {float(jnp.max(pis)):.4f} (each stream has its own "
          "sources, mixing matrix and drift phase)")

    n_slots, n_sessions = 4, 10
    print(f"\nSeparationService: {n_sessions} sessions contending for "
          f"{n_slots} slots (convergence-aware lifecycle)")
    events, finished, metrics = run_service(n_slots, n_sessions)
    for tick, kind, sid, extra in events:
        print(f"  tick {int(tick):4d}  {kind:<5}  {sid:<8}  {extra}")
    ticks = {sid: int(rec.stats.ticks) for sid, rec in finished.items()}
    print(f"all {len(finished)} sessions served and auto-evicted in "
          f"{int(metrics['n_ticks'])} ticks "
          f"(per-session data ticks: min {min(ticks.values())}, "
          f"max {max(ticks.values())}); queue drained via same-tick backfill")

    print("\nDrift-aware pipeline: a channel-bank recording (memory-mapped "
          ".npy,\nno ground truth) whose mixing rotates ~1.2 rad mid-run")
    events, trace, first_converged = run_drift_recording()
    for tick, kind, sid, extra in events:
        print(f"  tick {tick:4d}  {kind:<5}  {sid:<8}  {extra}")
    pre = [pi for t, pi in trace if t < 300]
    post_jump = [pi for t, pi in trace if 300 <= t < 400]
    final = trace[-1][1]
    print(f"tracking Amari index: {pre[-1]:.3f} just before the jump → "
          f"{max(post_jump):.3f} at the jump → {final:.3f} after "
          f"watchdog-boosted re-adaptation")
    print("(a policy-only service would have evicted at tick "
          f"{first_converged} and served the stale separator forever — "
          "see `stream_throughput.py --drift` for the measured gap)")

    print("\nFault containment: three sessions, one poisoned feed (NaN "
          "bursts),\none flaky feed (transient raises), one clean")
    events, metrics, statuses = run_containment()
    for tick, action, sid, word in events:
        print(f"  tick {tick:4d}  {action:<10}  {sid:<8}  kernel saw: {word}")
    print("final status: " + "  ".join(f"{s}={st}" for s, st in statuses.items()))
    print(f"counters: {int(metrics['n_rollbacks'])} rollbacks, "
          f"{int(metrics['n_quarantined'])} still in quarantine, "
          f"{int(metrics['n_diverged'])} diverged, "
          f"{int(metrics['n_source_retries'])} source retries, "
          f"{int(metrics['n_degraded_ticks'])} degraded ticks")
    print("(the kernel refused every poisoned commit in-register — the "
          "rollback/quarantine\nladder and the retry wrapper kept all three "
          "sessions' state finite; see\n`stream_throughput.py --health` for "
          "the overhead gate and `pytest -m chaos`\nfor the full drill suite)")

    print("\nLatency SLOs: record a 2-session live run, replay the trace "
          "under a\ndeadline budget (time-to-ready clock, not dispatch time)")
    live_m, rep_m, miss_rate, budget = run_slo_replay()
    print(f"live   : p50 {live_m['p50_tick_s']*1e3:.2f}ms  "
          f"p99 {live_m['p99_tick_s']*1e3:.2f}ms  "
          f"p999 {live_m['p999_tick_s']*1e3:.2f}ms over "
          f"{int(live_m['n_timed_ticks'])} ticks")
    print(f"replay : p50 {rep_m['p50_tick_s']*1e3:.2f}ms  "
          f"p99 {rep_m['p99_tick_s']*1e3:.2f}ms  "
          f"budget {budget*1e3:.2f}ms (5x live p50) -> "
          f"{int(rep_m['n_deadline_misses'])} misses "
          f"(miss rate {miss_rate:.3f})")
    print("(same blocks, same eviction order, bit-identical outputs — the "
          "recorded\ntrace is the load test; the demo tails include "
          "first-tick XLA compiles,\nwhich `stream_throughput.py --slo` — "
          "the CI-gated version over the\nchecked-in trace — warms away)")

    print("\nAdaptive μ: the same abrupt rotation served twice — fixed "
          "μ-boost vs the\nmoment-scaled controller over in-kernel "
          "[Σy², Σy⁴] telemetry")
    tr_fixed, tr_ctrl, scales, reconv = run_moment_drill()
    peak = max(s for _, s in scales)
    peak_tick = max(scales, key=lambda ts: ts[1])[0]
    final_scale = scales[-1][1]
    print(f"controller μ multiplier: 1.0 before the jump → {peak:.2f} peak "
          f"at tick {peak_tick} → {final_scale:.2f} after annealing "
          "(closed loop: scales with the kurtosis deviation, returns to "
          "base on its own)")
    fmt = lambda v: f"{v} ticks" if v is not None else "never"
    print(f"ticks to re-converge after the jump: fixed boost "
          f"{fmt(reconv['fixed'])}  vs  moment-scaled {fmt(reconv['ctrl'])}")
    print("(the fixed boost is open-loop — μ×4 for exactly 40 ticks, "
          "need it or not;\nsee `stream_throughput.py --adapt` for the "
          "CI-gated re-convergence ratio\nand the ≤5% telemetry HBM bar)")

    print("\nElastic capacity: an 8-session burst against a width-2 bank, "
          "autoscaler on\n(grow under queue pressure, compact+shrink after "
          "the drain)")
    drill = run_elastic_drill()
    for ev in drill["history"]:
        print(f"  tick {ev['tick']:4d}  {ev['action']:<8} "
              f"{ev['from']:>2} -> {ev['to']:<2}  ({ev['reason']})")
    arc = " ".join(str(w) for w in drill["widths"][:12])
    print(f"width per tick: {arc} ...")
    print(f"utilization: {drill['util_burst']:.2f} at the burst (queue "
          f"stranded) -> {drill['util_peak']:.2f} peak after growth -> "
          f"{drill['util_final']:.2f} after the drain at width "
          f"{drill['final_width']}")
    print(f"counters: {drill['n_grows']} grows, {drill['n_shrinks']} "
          f"shrinks, {drill['n_compactions']} compactions — every resize a "
          "prefix copy, every\ncompaction a verbatim row move; co-tenant "
          "trajectories bit-identical to a\nfixed-width run (see "
          "tests/test_elastic.py and `stream_throughput.py --elastic`)")


if __name__ == "__main__":
    main()
