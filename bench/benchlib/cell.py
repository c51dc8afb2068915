"""One run of one cell: set-up, the measured window, the reference, the
result line.

Set-up makes the traffic from the seed, builds the served path as a
deployment runs it (a ``SeparationService`` over a fused ``SeparatorBank``:
the whole-step megakernel, moment telemetry, health checks and the default
``HealthPolicy``), admits the mix's first sessions with their seed-drawn
starting separators, serves the mix's warm ticks and warms every program
the health ladder can launch.  The window then drives ``run_tick`` for
``--seconds``: before each tick the sessions the mix brings arrive, and
each tick is closed, the client takes every returned ``(P, n)`` output to
the host before the next tick starts; a session whose lifetime is over
drains and the service releases it.  After the window the device's peak
memory is read, the program's state is freed, and the reference replays
every session's blocks, at the stated precision and at the step below,
in lockstep, comparing each step's outputs as they come.
"""
from __future__ import annotations

import functools
import gc
import json
import resource
import shutil
import statistics
import sys
import time
from collections import defaultdict
from contextlib import nullcontext
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from benchlib import compare, registry, reference, spans, trace as trace_lib
from benchlib.traffic import Population, RingSource, make_traffic


class NoChip(RuntimeError):
    """The machine lacks the chips the cell asks for."""


class CompileCounter:
    """Counts programs lowered (a compile or a persistent-cache hit)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1


def build_bank(config: Dict, slots: int):
    """The served bank: the fused whole-step megakernel with moment
    telemetry and the configuration's blow-up bound."""
    from repro.core.easi import EASIConfig
    from repro.core.smbgd import SMBGDConfig
    from repro.stream import SeparatorBank

    easi = EASIConfig(
        n_components=int(config["n"]), n_features=int(config["m"]),
        mu=float(config["mu"]), nonlinearity=config["nonlinearity"],
    )
    opt = SMBGDConfig(
        batch_size=int(config["P"]), mu=float(config["mu"]),
        beta=float(config["beta"]), gamma=float(config["gamma"]),
    )
    return SeparatorBank(
        easi, opt, slots, fused=True, moments=True,
        blowup=float(config["health_blowup_bound"]),
    )


def _build_service(config: Dict, slots: int, seed: int, queue: int):
    from repro.serve import HealthPolicy, SeparationService

    return SeparationService(
        build_bank(config, slots), seed=seed % (2**31 - 1), health_policy=HealthPolicy(),
        max_queue=queue,
    )


def probe_widths(slots: int):
    """The widths of the quarantine probe banks the service can launch:
    powers of two up to its chunk of 64, and no wider than the bank."""
    widths, w = [], 1
    while w <= min(64, slots):
        widths.append(w)
        w *= 2
    return widths


def _warm_health_path(svc, widths) -> None:
    """Run once, on throwaway operands of the served shapes, every program
    the health ladder can launch: rollback, quarantine, probe banks of each
    power-of-two width, the μ cut.  The served state is not touched."""
    import jax
    import jax.numpy as jnp

    from repro.core.smbgd import BankHyperparams
    from repro.stream import SeparatorBank

    bank, st = svc.bank, svc.state
    jax.block_until_ready(bank.restore_slot(st, st, 0))
    jax.block_until_ready(bank.update_shadow(st, st, jnp.zeros((bank.n_streams,), bool)))
    one = bank.slot_state(st, 0)
    jax.block_until_ready(one)
    hp = BankHyperparams.broadcast(bank.opt, bank.n_streams)
    jax.block_until_ready(hp.mu * jnp.asarray(np.ones((bank.n_streams,), np.float32)))
    # the program has no public warm-up of its probe banks; without this
    # one their first quarantine would compile inside the window
    probe_bank = getattr(svc, "_probe_bank", None)
    if probe_bank is None:
        raise RuntimeError(
            "SeparationService._probe_bank is gone: the harness cannot warm "
            "the quarantine probe programs before the window"
        )
    lay = bank.layout
    for w in widths:
        pbank, probe_fn = probe_bank(int(w))
        state = pbank.pad_state(SeparatorBank.stack_states([one] * int(w)))
        X = np.zeros((int(w), lay.P_pad, lay.m_pad), np.float32)
        active = np.zeros((int(w),), np.int32)
        jax.block_until_ready(probe_fn(state, jnp.asarray(X), jnp.asarray(active)))


def _device(chips: int, require_chip: bool):
    import jax

    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(
            f"cell needs {chips} TPU chip(s); JAX finds {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})"
        )
    return devs[:chips]


def peak_rss_bytes() -> int:
    """The process's peak resident memory on the host so far."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _json_number(x: float):
    """A float as JSON allows it: a non-finite reading becomes its name."""
    return x if np.isfinite(x) else repr(float(x))


class TracedRun:
    """What a per-layer reader gets: the loaded and the reduced trace, and
    the cell."""

    def __init__(self, reduced, config, device_kind, sessions_per_tick, trace=None):
        self.reduced = reduced
        self.config = config
        self.device_kind = device_kind
        self.sessions_per_tick = sessions_per_tick
        self.trace = trace

    @functools.cached_property
    def phase_metrics(self) -> Optional[Dict[str, float]]:
        """The tick loop's phase metrics from the program's ``serve.*``
        spans (``spans.metrics``); None without a trace or without them."""
        if self.trace is None:
            return None
        return spans.metrics(spans.reduce(self.trace))

    def op_ms_per_tick(self, pattern: str) -> Optional[float]:
        """Device milliseconds per tick of the operations matching
        ``pattern``; None when the trace holds none."""
        s = self.reduced.kernel_seconds(pattern)
        return None if s <= 0 else s / self.reduced.ticks * 1e3


def run(
    root: Path,
    workload_name: str,
    seed: int,
    seconds: float,
    traced: bool,
    t_start: float,
    require_chip: bool = True,
    sessions: Optional[int] = None,
    out=sys.stdout,
    err=sys.stderr,
) -> Dict:
    """Run one cell once; print the checks to ``err`` and the result line to
    ``out``; return the result.  ``sessions`` overrides the configuration's
    slot count (CPU rehearsals, with ``require_chip=False``)."""
    import jax

    from repro.core.smbgd import SMBGDState
    from repro.data.sources import SourceExhausted

    wl = registry.workload(root, workload_name)
    config = registry.config(root, wl["config"])
    mix = registry.traffic(root, wl["traffic"])
    limits = registry.limits(root, wl["config"])
    devs = _device(int(wl["chips"]), require_chip)
    compiles = CompileCounter()

    S = int(config["sessions"] if sessions is None else sessions)
    P, n, m = int(config["P"]), int(config["n"]), int(config["m"])
    signals = registry.signal_model(root, config["signals"]["model"])
    traffic = make_traffic(config, mix, seed, signals, slots=S)
    pop = Population(mix, seed, S)
    span = jax.profiler.TraceAnnotation if traced else (lambda name: nullcontext())
    svc = _build_service(config, S, seed, int(mix.get("queue", 0)))
    zeros_h = np.zeros((n, n), np.float32)
    sources: Dict[int, RingSource] = {}
    outputs: Dict[int, List[np.ndarray]] = defaultdict(list)
    final: Dict[int, object] = {}
    counts = {"refused": 0, "departed": 0, "outputs": 0}

    def admit(ids) -> None:
        for sid in ids:
            src = RingSource(traffic, sid, pop.lifetimes[sid], SourceExhausted,
                             span if traced else None)
            state = SMBGDState(B=traffic.B0[traffic.stream_of(sid)],
                               H_hat=zeros_h, step=np.int32(0))
            try:
                svc.admit(sid, source=src, state=state)
            except RuntimeError:  # bank and queue full: the arrival is refused
                counts["refused"] += 1
                continue
            sources[sid] = src

    tick_ms: List[float] = []

    def tick(k: int) -> None:
        arriving = pop.arrivals(k, counts["departed"])
        if arriving:
            with span(trace_lib.ADMIT):
                admit(arriving)
        t0 = time.perf_counter()
        with span(trace_lib.TICK):
            got = svc.run_tick()
            with span(trace_lib.FETCH):
                vals = jax.device_get(list(got.values()))
        tick_ms.append((time.perf_counter() - t0) * 1e3)
        for sid, v in zip(got.keys(), vals):
            outputs[sid].append(v)
        counts["outputs"] += len(vals)
        left = svc.pop_finished()
        for sid, rec in left.items():
            final[sid] = jax.device_get(rec.state)
        counts["departed"] = len(left)

    def pulled() -> int:
        return sum(src.pulls for src in sources.values())

    admit(pop.initial())
    warm = int(mix["warm_ticks"])
    for k in range(warm):
        tick(k)
    _warm_health_path(svc, probe_widths(S))
    jax.block_until_ready(svc.state)
    tick_ms.clear()

    trace_dir = Path(root) / ".bench_out" / f"trace-{workload_name}"
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    n_compiles = compiles.n
    before = {"pulls": pulled(), "refused": counts["refused"], "outputs": counts["outputs"]}
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    deadline = t_window + seconds
    k = warm
    while time.perf_counter() < deadline:
        tick(k)
        k += 1
    window_s = time.perf_counter() - t_window
    compiles_in_window = compiles.n - n_compiles
    if traced:
        jax.profiler.stop_trace()
    timed = k - warm
    served_timed = counts["outputs"] - before["outputs"]
    attempted = pulled() - before["pulls"] + counts["refused"] - before["refused"]
    stats = devs[0].memory_stats() or {}
    peak = int(max(d.memory_stats().get("peak_bytes_in_use", 0) for d in devs)
               if stats else 0)

    N = len(pop.lifetimes)
    pulls = np.asarray([sources[i].pulls if i in sources else 0 for i in range(N)])
    K = int(max(pulls.max(initial=0), max((len(v) for v in outputs.values()), default=0)))
    Y = np.full((K, N, P, n), np.nan, np.float32)
    delivered = np.zeros((K, N), bool)
    for sid, ys in outputs.items():
        Y[:len(ys), sid] = np.stack(ys)
        delivered[:len(ys), sid] = True
    B_end = np.full((N, n, m), np.nan, np.float32)
    H_end = np.full((N, n, n), np.nan, np.float32)
    known = np.zeros((N,), bool)
    end = svc.bank.unpad_state(svc.state)
    slots = svc.sessions
    ids = np.asarray(sorted(slots), dtype=int)
    if len(ids):
        where = np.asarray([slots[i] for i in ids])
        B_end[ids] = np.asarray(end.B)[where]
        H_end[ids] = np.asarray(end.H_hat)[where]
        known[ids] = True
    for sid, st in final.items():
        B_end[sid], H_end[sid], known[sid] = st.B, st.H_hat, True
    served = {
        "Y": Y, "delivered": delivered, "pulls": pulls, "known": known,
        "B": B_end, "H": H_end,
        "flagged": {e.session_id for e in svc.health_events},
    }
    del svc, end, slots, outputs, final
    gc.collect()

    t_check = time.perf_counter()
    opts = compare.options(limits, config)
    ref, ctl = reference.replay_beside(config, traffic, np.arange(N), pulls, Y, delivered,
                                       head=opts["share_steps"])
    numbers = compare.compare(served, ref, ctl, **opts)
    numbers["compiles_in_window"] = float(compiles_in_window)
    held = dict(limits["limits"], compiles_in_window=0)
    correct = compare.verdict(numbers, held)
    diag = compare.diagnostics(served, ref)
    diag["check_s"] = time.perf_counter() - t_check
    diag["peak_rss_bytes"] = peak_rss_bytes()

    dev = devs[0]
    device = {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devs),
        "memory_peak_bytes": peak,
    }
    metrics: Dict[str, Dict] = {}
    result: Dict = {}
    if traced:
        loaded = trace_lib.load(trace_lib.find_xplane(trace_dir))
        red = trace_lib.reduce(loaded)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        sessions_per_tick = served_timed / max(timed, 1)
        tr = TracedRun(red, config, dev.device_kind, sessions_per_tick, trace=loaded)
        for spec in registry.metrics_of(root, workload_name, "per_layer"):
            value = registry.reader(root, spec["name"])(tr)
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in red.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps[:10]],
        }
    else:
        e2e = {
            "samples_per_s": served_timed * P / window_s,
            "hbm_bytes_per_session": peak / S,
            "setup_s": setup_s,
        }
        for spec in registry.metrics_of(root, workload_name, "end_to_end"):
            metrics[spec["name"]] = {"value": e2e[spec["name"]], "unit": spec["unit"]}

    print(
        f"window: {timed} ticks in {window_s:.3f} s after {warm} warm ticks; "
        f"setup {setup_s:.3f} s; tick ms median "
        f"{statistics.median(tick_ms) if tick_ms else float('nan'):.3f} p95 "
        f"{np.percentile(tick_ms, 95) if tick_ms else float('nan'):.3f}; "
        f"sessions {N} ({counts['refused']} refused); flagged served "
        f"{len(served['flagged'])} reference {int(ref['flagged'].sum())} differ "
        f"{int(numbers['flag_diff'])}; compared {int(numbers['compared'])} of "
        f"{N} sessions, {int(numbers['flagged_compared'])} of them flagged served, "
        f"{int(numbers['compared_worst'])} under max_update",
        file=err,
    )
    print("readings: " + " ".join(f"{k}={v:.4g}" for k, v in diag.items()), file=err)
    checks = {
        k: {"value": _json_number(numbers[k]), "limit": held[k]}
        for k in (*compare.ORDER, "compiles_in_window") if k in held
    }
    unheld = [k for k in compare.ORDER if k not in held]
    if unheld:
        print("not held: " + " ".join(f"{k}={numbers[k]:.4g}" for k in unheld), file=err)
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}", file=err)
    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": attempted - served_timed,
        "metrics": metrics,
        "device": device,
        **result,
        "compared": {"sessions": int(numbers["compared"]),
                     "worst_of": int(numbers["compared_worst"]), "of": N},
        "checks": checks,
    }
    print(json.dumps(result), file=out)
    out.flush()
    return result
