"""A second witness for the comparison that decides ``correct``: where the
served path's distance from the reference comes from.

    python3 bench/witness.py --workload <name> --seeds 1,2,3 --ticks <K>

For each seed, at the cell's own size, it serves ``K`` ticks of the cell's
traffic through the megakernel path (as a run does) and through the
program's own vmap path (``fused=False``), and replays the same blocks in
the plain reference at the stated precision on the host's CPU and on the
default device, and at the precision below (the control) on both.  Each
line of output is one seed and one pair: the worst compared session's
``y``, ``h``, ``b`` error (as ``compare`` reads them), over the sessions the
CPU reference never flagged whose update stayed under each ``max_update``.
The benchmark's runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from benchlib import cell, compare, reference, registry  # noqa: E402
from benchlib.traffic import RingSource, make_traffic  # noqa: E402

BOUNDS = (0.5, 1.0, float("inf"))


def serve(config, traffic, seed: int, ticks: int, fused: bool):
    """Outputs ``(K, N, P, n)`` and end state of the served path."""
    import jax
    import numpy as np

    from repro.core.smbgd import SMBGDState
    from repro.serve import HealthPolicy, SeparationService
    from repro.stream import SeparatorBank

    N, n = traffic.streams, int(config["n"])
    svc = cell._build_service(config, N, seed, 0)
    if not fused:
        b = svc.bank
        bank = SeparatorBank(b.easi, b.opt, N, fused=False, moments=True,
                             blowup=float(config["health_blowup_bound"]))
        svc = SeparationService(bank, seed=seed % (2**31 - 1),
                                health_policy=HealthPolicy())
    for s in range(N):
        svc.admit(s, source=RingSource(traffic, s), state=SMBGDState(
            B=traffic.B0[s], H_hat=np.zeros((n, n), np.float32), step=np.int32(0)))
    Y = np.full((ticks, N, int(config["P"]), n), np.nan, np.float32)
    for k in range(ticks):
        got = svc.run_tick()
        for sid, v in zip(got.keys(), jax.device_get(list(got.values()))):
            Y[k, sid] = v
    end = svc.bank.unpad_state(svc.state)
    B = np.full((N, n, int(config["m"])), np.nan, np.float32)
    H = np.full((N, n, n), np.nan, np.float32)
    for sid, slot in svc.sessions.items():  # a quarantined session has left
        B[sid], H[sid] = np.asarray(end.B[slot]), np.asarray(end.H_hat[slot])
    flagged = {e.session_id for e in svc.health_events}
    return {"Y": Y, "B": B, "H": H, "flagged": flagged}


def errors(a, b, ref_cpu, max_update):
    """Worst compared session's y, h, b error of ``a`` against ``b``."""
    import numpy as np

    clean = compare.compared_sessions(ref_cpu, max_update)
    clean = np.asarray([s for s in clean
                        if s not in a["flagged"] and s not in b["flagged"]], int)
    out = {"compared": int(len(clean))}
    per = {
        "y": compare._per_session(a["Y"][:, clean].transpose(1, 0, 2, 3),
                                  b["Y"][:, clean].transpose(1, 0, 2, 3)),
        "h": compare._per_session(a["H"][clean], b["H"][clean]),
        "b": compare._per_session(a["B"][clean], b["B"][clean]),
    }
    for k, e in per.items():
        out[k] = compare._worst(e)
        out[k + "_p50"] = float(np.median(e)) if e.size else 0.0
        out[k + "_p90"] = float(np.percentile(e, 90)) if e.size else 0.0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--ticks", type=int, required=True)
    ap.add_argument("--no-vmap", action="store_true")
    ap.add_argument("--sessions", type=int, default=None,
                    help="fewer sessions than the cell's (a CPU rehearsal)")
    args = ap.parse_args(argv)
    import jax
    import numpy as np

    wl = registry.workload(ROOT, args.workload)
    config = registry.config(ROOT, wl["config"])
    mix = registry.traffic(ROOT, wl["traffic"])
    signals = registry.signal_model(ROOT, config["signals"]["model"])
    stated = config["matmul_precision"]
    print(f"device: {jax.devices()[0].device_kind}", file=sys.stderr)
    for seed in (int(s) for s in args.seeds.split(",")):
        traffic = make_traffic(config, mix, seed, signals, slots=args.sessions)
        N = traffic.streams
        ids, steps = np.arange(N), np.full((N,), args.ticks)
        cpu = jax.devices("cpu")[0]
        runs = {
            "ref_cpu": reference.replay(config, traffic, ids, steps, stated, device=cpu),
            "ref_dev": reference.replay(config, traffic, ids, steps, stated),
            "ctl_cpu": reference.replay(config, traffic, ids, steps, "high", device=cpu),
            "ctl_dev": reference.replay(config, traffic, ids, steps, "high"),
            "kernel": serve(config, traffic, seed, args.ticks, fused=True),
        }
        if not args.no_vmap:
            runs["vmap"] = serve(config, traffic, seed, args.ticks, fused=False)
        ref_cpu = dict(runs["ref_cpu"])
        for name in ("ref_cpu", "ref_dev", "ctl_cpu", "ctl_dev"):
            runs[name]["flagged"] = set(np.flatnonzero(runs[name]["flagged"]).tolist())
        pairs = [("kernel", "ref_cpu"), ("kernel", "ref_dev"), ("ref_dev", "ref_cpu"),
                 ("ctl_dev", "ref_cpu"), ("ctl_cpu", "ref_cpu"), ("ctl_dev", "ref_dev")]
        if "vmap" in runs:
            pairs += [("vmap", "ref_cpu"), ("kernel", "vmap")]
        for a, b in pairs:
            for D in BOUNDS:
                row = {"seed": seed, "pair": f"{a}-{b}", "max_update": D,
                       **errors(runs[a], runs[b], ref_cpu, D)}
                print(json.dumps(row), flush=True)
        print(json.dumps({"seed": seed, "flagged": {
            k: sorted(int(x) for x in v["flagged"]) for k, v in runs.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
