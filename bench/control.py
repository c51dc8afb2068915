"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <name> --seeds 1,2,3 --ticks <K>

For each seed it runs the plain reference in the program's place one step
below the configuration's matmul precision (``"high"``, three bf16 passes,
for f32 at ``"highest"``), over the same blocks and as many ticks as a run
serves, and compares it with the reference at the stated precision by the
benchmark's own comparison, both on the default device (the chip, where
there is one).  Each line of output is one seed's numbers and
whether the comparison (wrongly) calls them correct; a sound comparison
says false on every seed.  The control is its own yardstick for the
``*_ctl_share`` numbers, which read 1 here; the medians separate it.  The
benchmark's runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "bench"))

from benchlib import compare, reference, registry  # noqa: E402
from benchlib.traffic import make_traffic  # noqa: E402


def control_numbers(root, workload_name: str, seed: int, ticks: int, sessions=None):
    """The control's numbers and verdict for one seed, both on the default
    device (the chip, where there is one)."""
    import numpy as np

    wl = registry.workload(root, workload_name)
    config = registry.config(root, wl["config"])
    mix = registry.traffic(root, wl["traffic"])
    limits_file = registry.limits(root, wl["config"])
    signals = registry.signal_model(root, config["signals"]["model"])
    traffic = make_traffic(config, mix, seed, signals, slots=sessions)
    N = traffic.streams
    ids, steps = np.arange(N), np.full((N,), ticks)
    stated = config["matmul_precision"]
    ref = reference.replay(config, traffic, ids, steps, precision=stated)
    ctl = reference.replay(config, traffic, ids, steps, precision=reference.BELOW[stated])
    served = {
        "Y": ctl["Y"],
        "delivered": np.ones(ctl["Y"].shape[:2], bool),
        "pulls": steps,
        "known": np.ones((N,), bool),
        "B": ctl["B"],
        "H": ctl["H"],
        "flagged": set(np.flatnonzero(ctl["flagged"]).tolist()),
    }
    numbers = compare.compare(served, ref, ctl, **compare.options(limits_file, config))
    numbers.update(compare.diagnostics(served, ref))
    return numbers, compare.verdict(numbers, limits_file["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--ticks", type=int, required=True)
    args = ap.parse_args(argv)
    import jax

    print(f"device: {jax.devices()[0].device_kind}", file=sys.stderr)
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers, ok = control_numbers(ROOT, args.workload, seed, args.ticks)
        print(json.dumps({"seed": seed, "ticks": args.ticks, "correct": ok, **numbers}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
