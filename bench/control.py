"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --ticks <K>[,<K>...]

For each seed it runs the plain reference in the program's place one step
below the configuration's matmul precision (``"high"``, three bf16 passes,
for f32 at ``"highest"``), over the same blocks and as many ticks as a run
serves (or each of several tick counts, from one replay), and compares it
with the reference at the stated precision by the benchmark's own
comparison, both on the default device (the chip, where there is one).
Each line of output is one seed and tick count: the numbers and whether
the comparison (wrongly) calls them correct; a sound comparison says false
on every seed.  The control is its own yardstick for the ``*_ctl_med`` and
``*_ctl_share`` numbers, which read 1 here.  The benchmark's runs never
run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import compare, reference, registry  # noqa: E402
from benchlib.traffic import make_traffic  # noqa: E402


def control_readings(root, workload_name: str, seed: int, marks, sessions=None):
    """The control's numbers and verdict for one seed after each tick count
    in ``marks``, from one replay on the default device (the chip, where
    there is one): ``[(ticks, numbers, verdict), ...]``."""
    import numpy as np

    wl = registry.workload(root, workload_name)
    config = registry.config(root, wl["config"])
    mix = registry.traffic(root, wl["traffic"])
    limits_file = registry.limits(root, wl["config"])
    signals = registry.signal_model(root, config["signals"]["model"])
    traffic = make_traffic(config, mix, seed, signals, slots=sessions)
    N = traffic.streams
    ids, steps = np.arange(N), np.full((N,), max(marks))
    stated = config["matmul_precision"]
    ref = reference.replay(config, traffic, ids, steps, stated, marks=marks)
    ctl = reference.replay(config, traffic, ids, steps, reference.BELOW[stated], marks=marks)
    out = []
    for k in marks:
        r = {"Y": ref["Y"][:k], **ref["at"][k]}
        c = {"Y": ctl["Y"][:k], **ctl["at"][k]}
        served = {
            "Y": c["Y"],
            "delivered": np.ones(c["Y"].shape[:2], bool),
            "pulls": np.full((N,), k),
            "known": np.ones((N,), bool),
            "B": c["B"],
            "H": c["H"],
            "flagged": set(np.flatnonzero(c["flagged"]).tolist()),
        }
        numbers = compare.compare(served, r, c, **compare.options(limits_file, config))
        numbers.update(compare.diagnostics(served, r))
        out.append((k, numbers, compare.verdict(numbers, limits_file["limits"])))
    return out


def control_numbers(root, workload_name: str, seed: int, ticks: int, sessions=None):
    """The control's numbers and verdict for one seed after ``ticks``
    ticks."""
    _, numbers, ok = control_readings(root, workload_name, seed, (int(ticks),), sessions)[0]
    return numbers, ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--ticks", required=True, help="tick counts, comma-separated")
    args = ap.parse_args(argv)
    import jax

    print(f"device: {jax.devices()[0].device_kind}", file=sys.stderr)
    ticks = tuple(int(k) for k in args.ticks.split(","))
    for seed in (int(s) for s in args.seeds.split(",")):
        for k, numbers, ok in control_readings(ROOT, args.workload, seed, ticks):
            print(json.dumps({"seed": seed, "ticks": k, "correct": ok, **numbers}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
