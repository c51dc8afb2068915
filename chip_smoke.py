"""Chip smoke test: serve the paper's deployment on a TPU through the normal
entry points and check the results against the plain f32 reference.

    python3 chip_smoke.py             # one chip (the default)
    python3 chip_smoke.py --chips 4   # the sharded bank step on four chips

One chip: a ``SeparationService`` over a fused ``SeparatorBank`` (the
whole-step megakernel, with moment telemetry and a ``HealthPolicy``) serves
4096 sessions of the paper's configuration (``configs/easi_ica.py``: m=4
mixtures, n=2 sources, cubic, P=8), each pulling from its own
``SyntheticSource``.  The script then

  * checks that the compiled step and probe hold a Mosaic kernel
    (``tpu_custom_call``) — no kernel may lower in interpret mode;
  * replays the served blocks through the vmap reference (``fused=False``)
    at f32 matmul precision: the two must flag the same sessions on the
    health bound (``FLAG_SLACK``), flag nothing non-finite, and agree on
    every other session (``REL_TOL``);
  * probes the live state once and compares with the reference probe;
  * runs a sample of the served sessions on through the bank's on-device
    epoch until they separate (Amari index below ``AMARI_BAR``).

Four chips: ``make_sharded_bank_step`` over a 4-device mesh with 4096
sessions per chip, against the same bank stepped on one device.

Every failed check exits non-zero.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
The script needs a TPU: on any other platform it exits non-zero at once.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Served vs reference, as max |served - ref| / max |ref| per quantity, over
# the sessions neither run flagged.  Both compute in f32 (the megakernel's
# matmuls at HIGHEST precision, the reference under an f32 matmul default)
# but sum in different orders, and a session near its blow-up bound
# amplifies that rounding.  On TPU v5e, after 8 ticks, the median session's
# B was 4e-8 (relative Frobenius) from the reference and the worst 9.4e-4;
# after 24 served ticks this check read 4.1e-4.  With f32 matmul operands
# rounded to bf16 (the TPU's default) it read 0.61 after 32 ticks.  1e-2
# sits between the two and still catches a wrong commit, which moves B by
# O(mu * P) relative.
REL_TOL = 1e-2
# About 1% of freshly initialised sessions of this configuration diverge in
# their first dozen ticks: the cubic update from a random B blows past the
# health bound (found on CPU at full f32: 47 of 4096 sessions reach inf
# unguarded).  The service contains them; the reference flags the same
# sessions.  One whose update lands on the bound can fall either side of it
# under rounding (data perturbed by 1e-3 relative on CPU moved 0 to 1 of
# about 30 flagged sessions), so the two flag sets may differ by this many.
FLAG_SLACK = 4
SESSIONS = 4096  # per chip: 537 MB of persistent state at the 128-lane layout
TICKS = 24  # served run_tick calls
SAMPLE = 256  # sessions run on to separation
EPOCH_BLOCKS = 1000  # mini-batches of P=8 each; the paper config needs ~250
AMARI_BAR = 0.1  # the bank bar for a separated session
AMARI_SHARE = 0.95  # share of the sampled sessions that must clear it
COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def serve_phase(
    sessions: int,
    ticks: int,
    seed: int,
    sample: int,
    epoch_blocks: int,
    on_chip: bool,
) -> dict:
    """Serve ``sessions`` through ``run_tick`` for ``ticks`` ticks, then
    check kernel lowering, containment, the reference, the probe and
    separation."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.easi_ica import EASI, SMBGD
    from repro.core.smbgd import BankHyperparams
    from repro.data.pipeline import MixedSignals
    from repro.data import sources as sources_lib
    from repro.data.sources import RecordingSource, SyntheticSource
    from repro.kernels.easi_gradient import ops as easi_ops
    from repro.serve import HealthPolicy, SeparationService
    from repro.stream import BankState, SeparatorBank

    S = sessions
    bank = SeparatorBank(EASI, SMBGD, S, fused=True, moments=True)
    lay = bank.layout
    block_s = bank.block_s or easi_ops.default_block_s(S, lay)
    print(
        f"bank: sessions={S} m={lay.m} n={lay.n} P={lay.P} "
        f"m_pad={lay.m_pad} n_pad={lay.n_pad} block_p={lay.block_p} "
        f"block_s={block_s} prefetch={bool(bank.prefetch)} "
        f"persistent_bytes_per_session={lay.persistent_bytes_per_session}"
    )
    svc = SeparationService(bank, seed=seed, health_policy=HealthPolicy())
    pipe = MixedSignals(
        m=EASI.n_features, n=EASI.n_components, batch=SMBGD.batch_size,
        seed=seed, streams=S,
    )
    t0 = time.perf_counter()
    taps = [RecordingSource(SyntheticSource(pipe, stream=s)) for s in range(S)]
    for s, tap in enumerate(taps):
        svc.admit(s, source=tap)
    check(svc.n_active == S, f"admitted {svc.n_active} of {S} sessions")
    jax.block_until_ready(svc.state)
    print(
        f"admit: {S} sessions in {time.perf_counter() - t0:.2f}s "
        f"(sources generate on {sources_lib._host_device()})"
    )
    # the reference's starting point, in session order: copied out now,
    # because the served state is donated to the kernel from the first tick
    slots = np.asarray([svc.sessions[s] for s in range(S)])
    start = bank.unpad_state(svc.state)
    st = BankState(*(leaf[slots] for leaf in start))
    del start

    hp = BankHyperparams.broadcast(SMBGD, S)
    X0 = jnp.zeros((S, lay.P_pad, lay.m_pad), jnp.float32)
    on = jnp.ones((S,), bool)
    t0 = time.perf_counter()
    step_hlo = (
        bank.make_step(with_hyperparams=True)
        .lower(svc.state, X0, on, hp).compile().as_text()
    )
    compile_s = time.perf_counter() - t0
    print(f"compile: served step in {compile_s:.2f}s")
    if on_chip:
        check("tpu_custom_call" in step_hlo, "served step holds no Mosaic kernel")
        print("kernel: served step lowers to tpu_custom_call")

    t0 = time.perf_counter()
    for _ in range(ticks):
        out = svc.run_tick()
    jax.block_until_ready(out)
    print(f"serve: {ticks} run_tick calls in {time.perf_counter() - t0:.2f}s")
    metrics = svc.metrics()
    events = svc.health_events
    degraded = int(metrics["n_degraded_ticks"])
    flag_served = {e.session_id for e in events}
    actions = sorted({e.action for e in events})
    print(
        f"health: events={len(events)} actions={actions} "
        f"sessions={len(flag_served)} n_degraded_ticks={degraded} "
        f"n_active={svc.n_active}"
    )
    check(degraded == 0, f"{degraded} degraded ticks")
    nonfinite = [e for e in events if e.word & ~easi_ops.HEALTH_BLOWUP]
    check(not nonfinite, f"non-finite values on the served path: {nonfinite[:4]}")
    gone = set(range(S)) - set(svc.sessions)
    check(gone <= flag_served, f"sessions left the bank unflagged: {sorted(gone - flag_served)[:8]}")

    # the plain f32 reference, fed the blocks the service actually pulled.
    # Its own health checks refuse a flagged commit (the service instead
    # rolls back and cuts mu), so flagged sessions part ways after their
    # first flag and are compared by flag, not by value.
    ref = SeparatorBank(EASI, SMBGD, S, fused=False, moments=True)
    X_ref = np.zeros((ticks, S, lay.P, lay.m), np.float32)
    for s, tap in enumerate(taps):
        # a quarantined session's later blocks fed health probes: it is
        # flagged, so only its first blocks matter
        blocks = tap.blocks[:ticks]
        check(len(blocks) == ticks or s in flag_served, f"session {s} pulled {len(blocks)} blocks")
        for k, b in enumerate(blocks):
            X_ref[k, s] = b.T
    ref_step = jax.jit(ref.step)
    flag_ref = np.zeros((S,), bool)
    words_ref = np.zeros((S,), np.int32)
    with jax.default_matmul_precision("float32"):
        for k in range(ticks):
            st, Y_ref = ref_step(st, jnp.asarray(X_ref[k]))
            words = np.asarray(st.health)
            flag_ref |= words != 0
            words_ref |= words
    check(not (words_ref & ~easi_ops.HEALTH_BLOWUP).any(), "non-finite values in the reference")
    flag_ref = set(np.flatnonzero(flag_ref).tolist())
    split = flag_served ^ flag_ref
    print(
        f"flags: served={len(flag_served)} reference={len(flag_ref)} "
        f"differ={sorted(split)} (slack {FLAG_SLACK})"
    )
    check(len(split) <= FLAG_SLACK, f"served and reference flag different sessions: {sorted(split)}")
    clean = np.asarray(sorted(set(range(S)) - flag_served - flag_ref))
    served = bank.unpad_state(svc.state)
    Y_served = np.stack([np.asarray(out[int(s)]) for s in clean])
    errs = {
        "B": rel_err(served.B[slots[clean]], st.B[clean]),
        "H_hat": rel_err(served.H_hat[slots[clean]], st.H_hat[clean]),
        "Y": rel_err(Y_served, Y_ref[clean]),
        "conv": rel_err(served.conv[slots[clean]], st.conv[clean]),
        "moments": rel_err(served.moments[slots[clean]], st.moments[clean]),
    }
    print(
        f"reference: {len(clean)} unflagged sessions, rel err "
        + " ".join(f"{k}={v:.3e}" for k, v in errs.items())
        + f" (tolerance {REL_TOL})"
    )
    check(max(errs.values()) <= REL_TOL, f"reference mismatch {errs}")

    # one probe of the live state, against the reference probe
    Xp = pipe.batch_for_step(ticks)  # every session's next block
    Xp_slots = jnp.zeros_like(Xp).at[slots].set(Xp)  # in slot order
    probe = bank.make_probe()
    probe_hlo = probe.lower(svc.state, Xp_slots, on).compile().as_text()
    if on_chip:
        check("tpu_custom_call" in probe_hlo, "probe holds no Mosaic kernel")
        print("kernel: probe lowers to tpu_custom_call")
    conv_p, health_p, _ = probe(svc.state, Xp_slots, on)
    with jax.default_matmul_precision("float32"):
        conv_r, health_r, _ = jax.jit(ref.probe)(st, Xp)
    health_p = np.asarray(health_p)[slots[clean]]
    probe_err = rel_err(np.asarray(conv_p)[slots[clean]], np.asarray(conv_r)[clean])
    print(
        f"probe: conv rel err {probe_err:.3e} "
        f"nonzero_health={int(np.count_nonzero(health_p))}"
    )
    check(probe_err <= REL_TOL, f"probe mismatch {probe_err}")
    check(not health_p.any(), "probe raised a health word")
    check(not np.asarray(health_r)[clean].any(), "reference probe raised a health word")

    # separation: a sample of the served sessions runs on, on device
    check(S % sample == 0, f"sample {sample} must divide {S}")
    samp = SeparatorBank(EASI, SMBGD, sample, fused=True)
    sub = slots[:sample]
    sst = samp.pad_state(BankState(*(leaf[sub] for leaf in served)))
    X_ep = np.concatenate(
        [
            np.asarray(pipe.batch_for_step(ticks + k, 0, S // sample))
            for k in range(epoch_blocks)
        ],
        axis=1,
    )  # sessions 0..sample-1, the blocks after the served ticks
    sst, _ = samp.make_epoch()(sst, X_ep)
    amari = np.asarray(samp.performance_index(sst, pipe.mixing_at(0)[:sample]))
    amari = amari[clean[clean < sample]]
    share = float(np.mean(amari < AMARI_BAR))
    print(
        f"separation: {amari.size} unflagged of {sample} sessions after "
        f"{ticks} ticks + {epoch_blocks} epoch blocks: amari "
        f"median={np.median(amari):.4f} max={amari.max():.4f} "
        f"share<{AMARI_BAR}={share:.3f}"
    )
    check(share >= AMARI_SHARE, f"only {share:.3f} of the sample separated")
    return {
        "compile_s": compile_s, "errs": errs,
        "flags": (len(flag_served), len(flag_ref), len(split)),
    }


def sharded_phase(n_dev: int, per_chip: int, ticks: int, seed: int, on_chip: bool):
    """``make_sharded_bank_step`` over an ``n_dev``-device mesh against the
    same bank stepped on one device."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.configs.easi_ica import EASI, SMBGD
    from repro.data.pipeline import MixedSignals
    from repro.kernels.easi_gradient.ops import HEALTH_BLOWUP
    from repro.stream import SeparatorBank, bank_sharding, make_sharded_bank_step

    devices = jax.devices()[:n_dev]
    check(len(devices) == n_dev, f"{len(jax.devices())} devices, need {n_dev}")
    S = n_dev * per_chip
    mesh = Mesh(np.asarray(devices), ("stream",))
    bank = SeparatorBank(EASI, SMBGD, S, fused=True, moments=True)
    pipe = MixedSignals(
        m=EASI.n_features, n=EASI.n_components, batch=SMBGD.batch_size,
        seed=seed, streams=S,
    )
    x_sharding = NamedSharding(mesh, PartitionSpec("stream"))
    key = jax.random.PRNGKey(seed)
    state = jax.jit(bank.init, out_shardings=bank_sharding(mesh))(key)
    spread = len(state.B.sharding.device_set)
    check(spread == n_dev, f"state spans {spread} devices, not {n_dev}")
    # identical start on one device (a copy: the sharded state is donated)
    one = jax.device_put(state, devices[0], may_alias=False)
    step = make_sharded_bank_step(bank, mesh, donate=True)
    X0 = jax.device_put(pipe.batch_for_step(0), x_sharding)
    t0 = time.perf_counter()
    hlo = step.lower(state, X0).compile().as_text()
    print(
        f"compile: sharded step over {n_dev} devices "
        f"({per_chip} sessions each) in {time.perf_counter() - t0:.2f}s"
    )
    found = [c for c in COLLECTIVES if c in hlo]
    check(not found, f"sharded step holds collectives {found}")
    if on_chip:
        check("tpu_custom_call" in hlo, "sharded step holds no Mosaic kernel")
        print("kernel: sharded step lowers to tpu_custom_call, no collectives")
    step_one = bank.make_step(donate=True)
    active_one = jax.device_put(np.ones((S,), bool), devices[0])
    t0 = time.perf_counter()
    for k in range(ticks):
        Xk = pipe.batch_for_step(k)
        state, Y = step(state, jax.device_put(Xk, x_sharding))
        one, Y_one = step_one(one, jax.device_put(Xk, devices[0]), active_one)
    jax.block_until_ready((state, one))
    print(f"step: {ticks} ticks on both paths in {time.perf_counter() - t0:.2f}s")
    spread = len(state.B.sharding.device_set)
    check(spread == n_dev, f"stepped state spans {spread} devices")
    fin = lambda a: np.where(np.isfinite(a), a, 0.0)  # diverged, refused slots
    pairs = {
        "B": (state.B, one.B), "H_hat": (state.H_hat, one.H_hat),
        "conv": (fin(state.conv), fin(one.conv)),
        "moments": (state.moments, one.moments), "Y": (fin(Y), fin(Y_one)),
    }
    errs = {k: rel_err(a, b) for k, (a, b) in pairs.items()}
    words = np.asarray(state.health)
    same = all(
        np.array_equal(np.asarray(a), np.asarray(b)) for a, b in pairs.values()
    )
    print(
        "sharded vs one device: rel err "
        + " ".join(f"{k}={v:.3e}" for k, v in errs.items())
        + f" bit_identical={same}"
    )
    # per-stream math is independent of the device a stream lives on and of
    # the stream-block it rides in, so anything beyond rounding is a fault
    check(max(errs.values()) <= 1e-6, f"sharded step disagrees {errs}")
    check(np.array_equal(words, np.asarray(one.health)), "health words disagree")
    # the bank refuses a blown-up commit; a non-finite value is a fault
    check(not (words & ~HEALTH_BLOWUP).any(), "a non-finite health word")
    return {"errs": errs, "bit_identical": same}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0, help="data and state seed")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    if dev.platform != "tpu":
        print(f"FAIL: no TPU (platform {dev.platform!r})", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache

    cache = Path(enable_compile_cache())
    entries = lambda: sum(1 for _ in cache.glob("*")) if cache.is_dir() else 0
    n_cached = entries()
    print(f"compile cache: {cache} ({n_cached} entries)")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            sharded_phase(4, SESSIONS, TICKS, args.seed, on_chip=True)
        else:
            serve_phase(
                SESSIONS, TICKS, args.seed, sample=SAMPLE,
                epoch_blocks=EPOCH_BLOCKS, on_chip=True,
            )
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"total: {time.perf_counter() - t0:.2f}s")
    # a rerun that compiled nothing new wrote nothing: every program hit
    print(f"compile cache: {entries() - n_cached} entries written")
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
