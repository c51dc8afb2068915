"""Readings for the limits that decide ``correct``, at the step counts a
fast served path reaches.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 [--marks 64,1024,4096,16384]

For each seed it builds the cell's bank as a run does (``cell.build_bank``:
the fused megakernel, moments, the configuration's blow-up bound) for the
cell's sessions, starts every session from its seed-drawn ``B0``, and
advances the bank by its jitted step (``SeparatorBank.make_step``, as the
service launches it) over the cell's traffic for as many steps as the
largest mark, reading each step's outputs ``Y[:, :P, :n]`` with one
transfer.  There is no health policy: a step the bank refuses is not
committed, as in the reference.  Then the reference and the control (one
matmul precision step below) replay the same blocks in lockstep, as a
run's check does, and at each mark the benchmark's own comparison reads
the served path.  Each line of output is one seed and one mark: every
compared number, whether the limits call it correct, and the diagnostics.
A last line per seed gives the time of each phase and the peak host
memory; ``check_s`` is what a run's check after its window would take at
the largest mark: the replays, the comparison and the diagnostics.  The
control in the served path's place reads 1 on every share by
construction; ``bench/control.py --ticks 64,1024,4096,16384`` reads it.
The benchmark's runs never run this.
"""
import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import cell, compare, reference, registry  # noqa: E402
from benchlib.traffic import make_traffic  # noqa: E402

MARKS = (64, 256, 1024, 4096, 16384)


def serve_steps(config, traffic, K: int, marks):
    """The served bank over ``K`` steps of every session: outputs
    ``(K, N, P, n)`` and, at each mark, the state and which sessions the
    bank flagged."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.stream.bank import BankState

    N = traffic.streams
    P, n, m = int(config["P"]), int(config["n"]), int(config["m"])
    bank = cell.build_bank(config, N)
    lay = bank.layout
    state = bank.pad_state(BankState(
        B=jnp.asarray(traffic.B0), H_hat=jnp.zeros((N, n, n), jnp.float32),
        step=jnp.zeros((N,), jnp.int32), conv=jnp.full((N,), jnp.inf, jnp.float32),
        health=jnp.zeros((N,), jnp.int32), moments=jnp.zeros((N, 2), jnp.float32),
    ))
    step = bank.make_step(with_hyperparams=True)
    hp = bank._bank_hyperparams()
    read = jax.jit(lambda st, Y, ever: (Y[:, :P, :n], ever | (st.health != 0)))
    ids = np.arange(N)
    X = np.zeros((N, lay.P_pad, lay.m_pad), np.float32)
    active = np.ones((N,), bool)
    ever = jnp.zeros((N,), bool)
    Ys = np.zeros((K, N, P, n), np.float32)
    at = {}
    for k in range(K):
        X[:, :P, :m] = traffic.batch(ids, k)
        state, Y = step(state, jnp.asarray(X), jnp.asarray(active), hp)
        y, ever = read(state, Y, ever)
        Ys[k] = np.asarray(y)
        if k + 1 in marks:
            end = bank.unpad_state(state)
            at[k + 1] = {"B": np.asarray(end.B), "H": np.asarray(end.H_hat),
                         "flagged": set(np.flatnonzero(np.asarray(ever)).tolist())}
    return Ys, at


def readings(root, workload_name: str, seed: int, marks=MARKS, sessions=None,
             out=sys.stdout):
    """Print and return, for each mark, the served path's compared numbers
    and verdict, then the phase times."""
    import numpy as np

    wl = registry.workload(root, workload_name)
    config = registry.config(root, wl["config"])
    mix = registry.traffic(root, wl["traffic"])
    limits = registry.limits(root, wl["config"])
    signals = registry.signal_model(root, config["signals"]["model"])
    traffic = make_traffic(config, mix, seed, signals, slots=sessions)
    N, K = traffic.streams, max(marks)
    opts = compare.options(limits, config)
    rows = []

    t0 = time.perf_counter()
    Ys, served_at = serve_steps(config, traffic, K, marks)
    times = {"serve_s": time.perf_counter() - t0}
    t0 = time.perf_counter()
    delivered = np.ones((K, N), bool)
    ref, ctl = reference.replay_beside(config, traffic, np.arange(N), np.full((N,), K),
                                       Ys, delivered, marks=marks, head=opts["share_steps"])
    times["replay_s"] = time.perf_counter() - t0
    for k in sorted(marks):
        t1 = time.perf_counter()
        served = {"Y": Ys[:k], "delivered": delivered[:k], "pulls": np.full((N,), k),
                  "known": np.ones((N,), bool), **served_at[k]}
        r, c = ref["at"][k], ctl["at"][k]
        numbers = compare.compare(served, r, c, **opts)
        diag = compare.diagnostics(served, r)
        if k == K:
            times["check_s"] = times["replay_s"] + time.perf_counter() - t1
        row = {"seed": seed, "steps": k,
               "correct": compare.verdict(numbers, limits["limits"]),
               **numbers, **diag}
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)
    times["peak_rss_bytes"] = cell.peak_rss_bytes()
    print(json.dumps({"seed": seed, "sessions": N, "steps": K, **times}), file=out,
          flush=True)
    return rows, times


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--marks", default=",".join(map(str, MARKS)))
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"FAIL: no TPU; JAX finds {dev.platform}", file=sys.stderr)
        return 2
    print(f"device: {dev.device_kind}", file=sys.stderr)
    marks = tuple(int(k) for k in args.marks.split(","))
    for seed in (int(s) for s in args.seeds.split(",")):
        readings(ROOT, args.workload, seed, marks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
